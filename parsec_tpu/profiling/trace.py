"""Event tracing.

Reference: parsec/profiling.c (PBT binary traces — per-stream buffers,
dictionary of paired begin/end keys with typed info payloads,
profiling.h:44-80) + tools/profiling/python/pbt2ptt.pyx (conversion to
pandas HDF5 tables).

Events are recorded in per-recording-thread RING buffers (the
reference's per-execution-stream buffer model: one writer per buffer, so
recording takes no lock — a previous build appended to one global list
under one global lock, which both contended the workers and grew without
bound in a persistent serving Context). Each ring holds at most
``profiling.trace_max_events`` events; when it wraps, the oldest event
is dropped and the per-ring ``dropped`` counter advances — bounded
memory is the contract, and ``Trace.dropped()`` is the honesty counter
(a wrapped serving trace says HOW MANY events it lost, never silently).

Export goes directly to records (``to_records``) or JSON — the offline
converter collapses into the runtime since the host side is already
Python. Dumped traces carry a ``meta`` block ({rank, t0,
clock_offset_s, dropped}) so the multi-rank merge in
:mod:`~parsec_tpu.profiling.tools` can align ranks onto one clock (the
offset is measured by the comm engine's pingpong handshake at dump
time — see ``SocketCommEngine.clock_offset_to``).

Request-scoped spans (profiling/spans.py) ride the same stream: the
task hooks attach ``{rid, span, parent, q_us}`` info to the begin/end
events of tasks whose taskpool carries a ``trace_rid``.
"""

from __future__ import annotations

import json
import threading
import time
from collections import defaultdict, deque
from typing import Any, Dict, List, Optional

from .pins import PinsEvent
from ..utils import mca_param

mca_param.register("profiling.trace_max_events", 100000,
                   help="per-recording-thread ring-buffer capacity of "
                        "the trace: a persistent serving Context stays "
                        "bounded; when a ring wraps the oldest events "
                        "are dropped and Trace.dropped() counts them")
mca_param.register("profiling.native_ring_events", 16384,
                   help="per-worker capacity (records) of the NATIVE "
                        "DTD engine's in-engine event rings "
                        "(pdtd_obs_enable — ISSUE 13): rings grow x4 "
                        "up to this cap, then drop-oldest with the "
                        "drop counter advancing (folded into "
                        "Trace.dropped() and the trace meta block)")
mca_param.register("profiling.trace_max_native_sources", 256,
                   help="native ring snapshots a Trace retains (one "
                        "per natively-executed pool): a persistent "
                        "serving context stays bounded — evicted "
                        "snapshots count into Trace.dropped()")


#: first slot of a combined request-span ring record (one entry per
#: rid'd task, expanded into the begin/end event pair at read time)
_SPAN_REC = 0


class _Ring:
    """One recording thread's event ring (single writer, no lock)."""

    __slots__ = ("dq", "dropped")

    def __init__(self, maxlen: int):
        self.dq: deque = deque(maxlen=maxlen)
        self.dropped = 0


class NativeRingAdapter:
    """Scrape-time bridge from ONE native DTD engine's in-engine event
    rings (``pdtd_obs_*`` in _native/core.cpp — ISSUE 13) into this
    trace: ``to_records`` drains the rings at dump/scrape time and
    expands each fixed-stride 48-byte record into the PR 9 trace-record
    shape byte-compatibly (same keys, span parenting via the completion
    dep edges the engine tracked, ``q_us`` from the native ready→select
    stamps), so chrome/critpath/spans/counts work unchanged on
    natively-executed pools. While the pool is live the drain is a
    non-consuming snapshot; at pool retirement :meth:`snapshot` pulls
    the raw arrays ONCE (one memcpy per ring, zero per-event Python
    cost) and releases the engine so its C rings can be freed."""

    def __init__(self, engine) -> None:
        self._lock = threading.Lock()
        self._engine = engine          # dsl.dtd_native.NativeDTD while live
        # rid/root_span read late-bound while the pool is live; the
        # snapshot at its retirement keeps the two and lets the pool go
        self.tp = engine.tp
        self._ids = (None, None)
        self.pool_name = engine.tp.name
        self.class_names = engine.class_names   # shared, insert-grown
        self.offset_s = engine.obs_offset_s
        self._frozen: Optional[List] = None
        self._frozen_dropped = 0

    def _arrays(self) -> List:
        with self._lock:
            if self._frozen is not None:
                return self._frozen
            eng = self._engine
        return eng.obs_drain() if eng is not None else []

    def dropped(self) -> int:
        """Records lost to native ring wraps (the honesty counter)."""
        with self._lock:
            if self._frozen is not None:
                return self._frozen_dropped
            eng = self._engine
        return eng.obs_dropped() if eng is not None else 0

    def event_count(self) -> int:
        return sum(len(a) for a in self._arrays())

    def raw_arrays(self) -> List:
        """The structured record arrays themselves (ring-fed consumers
        like the straggler watchdog's native path)."""
        return self._arrays()

    def snapshot(self) -> None:
        """Freeze at pool retirement: drain the rings into owned arrays
        and drop the engine reference (idempotent)."""
        with self._lock:
            if self._frozen is not None:
                return
            eng = self._engine
            self._engine = None
            tp, self.tp = self.tp, None
            if tp is not None:
                self._ids = (getattr(tp, "trace_rid", None),
                             getattr(tp, "root_span", None))
            if eng is None:
                self._frozen = []
                return
            self._frozen = eng.obs_drain()
            self._frozen_dropped = eng.obs_dropped()

    def to_records(self, t0: float) -> List[Dict[str, Any]]:
        """Expand the binary records into PR 9-format event dicts with
        times relative to the owning trace's ``t0``."""
        from .. import _native
        arrays = self._arrays()
        if not arrays:
            return []
        tp = self.tp
        rid, root = self._ids if tp is None else (
            getattr(tp, "trace_rid", None), getattr(tp, "root_span", None))
        names = self.class_names
        shift = self.offset_s - t0
        nonep = _native.OBS_PARENT_NONE
        span_of: Dict[int, int] = {}
        for a in arrays:
            for s, sp in zip(a["seq"].tolist(), a["span"].tolist()):
                span_of[s] = sp
        events: List[Dict[str, Any]] = []
        for a in arrays:
            t0s = (a["t0_ns"] * 1e-9 + shift).tolist()
            t1s = (a["t1_ns"] * 1e-9 + shift).tolist()
            qs = a["q_ns"].tolist()
            sps = a["span"].tolist()
            sqs = a["seq"].tolist()
            pss = a["parent_seq"].tolist()
            cls = a["cls"].tolist()
            wks = a["worker"].tolist()
            for i, seq in enumerate(sqs):
                sid = sps[i]
                name = names[cls[i]] if cls[i] < len(names) else "dtd_task"
                if rid is None:
                    # profiler shape (no request context): the classic
                    # begin/end pair, keyed by the unique span id
                    events.append({"key": "task", "phase": "begin",
                                   "t": t0s[i], "stream": wks[i],
                                   "object": sid, "info": {}})
                    events.append({"key": "task", "phase": "end",
                                   "t": t1s[i], "stream": -1,
                                   "object": sid,
                                   "info": {"class": name,
                                            "locals": [seq]}})
                    continue
                ps = pss[i]
                parent = root if ps == nonep else span_of.get(ps, root)
                binfo: Dict[str, Any] = {"rid": rid, "span": sid,
                                         "parent": parent}
                if ps != nonep:
                    binfo["q_us"] = round(qs[i] / 1e3, 1)
                events.append({"key": "task", "phase": "begin",
                               "t": t0s[i], "stream": wks[i],
                               "object": sid, "info": binfo})
                events.append({"key": "task", "phase": "end",
                               "t": t1s[i], "stream": -1, "object": sid,
                               "info": {"class": name, "locals": [seq],
                                        "span": sid, "rid": rid}})
        return events


class Trace:
    """In-memory trace with a key dictionary (parsec_profiling API analog:
    dictionary entries = add_dictionary_keyword, events = trace_flags)."""

    def __init__(self, max_events: Optional[int] = None) -> None:
        self._dict: Dict[str, Dict[str, Any]] = {}
        self._max_events = int(
            max_events if max_events is not None else
            mca_param.get("profiling.trace_max_events", 100000)) or 1
        self._rings: Dict[int, _Ring] = {}     # recording thread -> ring
        self._ring_lock = threading.Lock()     # ring creation only
        # native DTD engines' ring adapters (ISSUE 13): bounded, evicted
        # snapshots fold into dropped() so a truncated capture is loud
        self._native_sources: deque = deque()
        self._native_evicted = 0
        self._max_native = max(1, int(mca_param.get(
            "profiling.trace_max_native_sources", 256)))
        self.t0 = time.perf_counter()
        self.rank = 0
        self._comm = None                      # set by install()
        # hot-path span-id mint, bound once: rank bits | shared counter
        from . import spans as _spans
        self._span_base = 0
        self._span_next = _spans._counter.__next__

    # -- dictionary (profiling.h:44-80 analog) ----------------------------
    def add_keyword(self, name: str, attributes: str = "",
                    info_schema: Optional[Dict[str, str]] = None) -> str:
        self._dict[name] = {"attributes": attributes,
                            "info": info_schema or {}}
        return name

    # -- event recording --------------------------------------------------
    def _ring(self) -> _Ring:
        tid = threading.get_ident()
        ring = self._rings.get(tid)        # GIL-atomic read: hit is free
        if ring is None:
            with self._ring_lock:
                ring = self._rings.get(tid)
                if ring is None:
                    ring = self._rings[tid] = _Ring(self._max_events)
        return ring

    def _append(self, key: str, phase: str, t: float, stream_id: int,
                object_id: Any, info: Optional[Dict]) -> None:
        """Hot recording path: one TUPLE into this thread's ring (a
        dict per event measured ~3x the allocation cost on the
        null-task rate; to_records materializes dicts at READ time)."""
        ring = self._ring()
        dq = ring.dq
        if len(dq) == dq.maxlen:
            ring.dropped += 1              # ring wrap: honesty counter
        dq.append((key, phase, t, stream_id, object_id, info))

    def event(self, key: str, phase: str, stream_id: int = -1,
              object_id: Any = None, info: Optional[Dict] = None,
              t: Optional[float] = None) -> None:
        """Record one event. ``t`` (seconds relative to this trace's
        ``t0``) may be passed explicitly for after-the-fact spans (e.g.
        an admission park recorded once the wait resolves)."""
        self._append(key, phase,
                     (time.perf_counter() - self.t0) if t is None else t,
                     stream_id, object_id, info)

    def begin(self, key: str, **kw) -> None:
        self.event(key, "begin", **kw)

    def end(self, key: str, **kw) -> None:
        self.event(key, "end", **kw)

    def dropped(self) -> int:
        """Events lost to ring wraps across every recording thread,
        INCLUDING the native engines' in-engine rings and any evicted
        native snapshots (a truncated native capture must be loud)."""
        with self._ring_lock:
            n = sum(r.dropped for r in self._rings.values())
        return n + self.native_dropped()

    def native_dropped(self) -> int:
        """The native-ring share of :meth:`dropped` (meta/statusz row)."""
        with self._ring_lock:
            n = self._native_evicted
            sources = list(self._native_sources)
        return n + sum(src.dropped() for src in sources)

    # -- native DTD engine rings (ISSUE 13) -------------------------------
    def add_native_source(self, src: "NativeRingAdapter") -> None:
        """Attach one native engine's ring adapter: its records join
        ``to_records`` (expanded lazily at dump/scrape time) and its
        drop counter joins ``dropped()``. Bounded by
        ``profiling.trace_max_native_sources`` — the oldest snapshot is
        evicted with its event+drop counts folded into the drop total,
        so a persistent serving context cannot grow without bound."""
        with self._ring_lock:
            self._native_sources.append(src)
            while len(self._native_sources) > self._max_native:
                old = self._native_sources.popleft()
                self._native_evicted += old.event_count() + old.dropped()

    # hooks wired by install(). Paired by task.uid (an int — repr()
    # per event measured 2x the whole append cost); the human-readable
    # class/locals ride the end event's info. These two run once per
    # task on the null-task hot path, where every allocation is
    # visible in the obs_overhead_pct bench guard, so:
    # - ring appends are inlined (no _append call);
    # - a REQUEST-SCOPED task records ONE combined ring entry at
    #   completion (begin stamps parked in task.prof, all dict/info
    #   formatting deferred to to_records) — the begin/end event PAIR
    #   is materialized at read time, byte-identical to the classic
    #   shape. Tradeoff: a rid'd task that crashes mid-body leaves no
    #   event (the rid-less profiler pair still covers crash forensics).
    # What a ``task`` span times: from exec_begin to complete_task, on
    # the HOST clock (perf_counter). With a device body (a jitted call
    # that returns at enqueue) it ends at release, after the enqueue, not
    # when the device finishes; device time is in a jax.profiler trace,
    # beside the runtime's parsec: stage spans (core.context.StageSpan).
    def task_begin(self, es, task) -> None:
        tp = task.taskpool
        if tp.trace_rid is not None:
            # ONE fused prof store: (span id, begin stamp, stream) —
            # the combined span record picks it up at completion
            task.prof["b"] = (self._span_base | self._span_next(),
                              time.perf_counter(),
                              es.th_id if es is not None else -1)
            return
        ring = self._ring()
        dq = ring.dq
        if len(dq) == dq.maxlen:
            ring.dropped += 1
        dq.append(("task", "begin", time.perf_counter() - self.t0,
                   es.th_id if es is not None else -1, task.uid, None))

    def task_complete(self, task) -> None:
        prof = task.prof
        ring = self._rings.get(threading.get_ident())
        if ring is None:
            ring = self._ring()
        dq = ring.dq
        if len(dq) == dq.maxlen:
            ring.dropped += 1
        b = prof.get("b")
        if b is not None:
            tp = task.taskpool
            # combined span record (expanded by to_records); absolute
            # perf_counter stamps, converted at read time
            dq.append((_SPAN_REC, b[1], time.perf_counter(), b[2],
                       task.uid, task.task_class.name, task.locals,
                       b[0], prof.get("rid") or tp.trace_rid,
                       prof.get("parent_span", tp.root_span),
                       prof.get("q_t0")))
            return
        dq.append(("task", "end", time.perf_counter() - self.t0, -1,
                   task.uid, {"class": task.task_class.name,
                              "locals": task.locals}))

    def install(self, context) -> "Trace":
        """Subscribe to the context's PINS chains (task_profiler module
        analog, mca/pins/task_profiler) and, when a comm engine is
        attached, its per-message instrumentation (msg_size events)."""
        self.add_keyword("task", info_schema={"class": "str",
                                              "locals": "list"})
        self.add_keyword("wire", info_schema={"rid": "str", "span": "str",
                                              "nbytes": "int"})
        self.add_keyword("admission", info_schema={"rid": "str"})
        self.add_keyword("req", info_schema={"rid": "str"})
        # KV page lifecycle (alloc/retain/release/free/cow/write) —
        # consumed by analysis/conformance.py for model replay
        self.add_keyword("kvpage", info_schema={"pool": "str",
                                                "refs": "int"})
        context.trace = self
        self.rank = context.my_rank
        from .spans import _RANK_SHIFT
        self._span_base = self.rank << _RANK_SHIFT
        # native_ok: pools on the native DTD engine record the same
        # begin/end spans into the in-engine rings (ISSUE 13), so a
        # live trace no longer forces the instrumented Python path
        context.pins.register(PinsEvent.EXEC_BEGIN, self.task_begin,
                              native_ok=True)
        if context.comm is not None:
            self._comm = context.comm
            context.comm.install_trace(self)
        return self

    # -- export -----------------------------------------------------------
    def to_records(self) -> List[Dict[str, Any]]:
        with self._ring_lock:
            rings = list(self._rings.values())
            native = list(self._native_sources)
        t0 = self.t0
        events: List[Dict[str, Any]] = []
        for r in rings:
            # list(deque) is a C-level snapshot (GIL-atomic): recording
            # threads may append concurrently with a live dump — a
            # Python-level iteration over the live deque would raise
            # "deque mutated during iteration"
            for ev in list(r.dq):
                if ev[0] == _SPAN_REC:
                    # combined request-span record -> begin/end pair
                    (_k, tb, te, stream, uid, cls, locs, sid, rid,
                     parent, q_t0) = ev
                    binfo = {"rid": rid, "span": sid, "parent": parent}
                    if q_t0 is not None:
                        binfo["q_us"] = round((tb - q_t0) * 1e6, 1)
                    events.append({"key": "task", "phase": "begin",
                                   "t": tb - t0, "stream": stream,
                                   "object": uid, "info": binfo})
                    events.append({"key": "task", "phase": "end",
                                   "t": te - t0, "stream": -1,
                                   "object": uid,
                                   "info": {"class": cls,
                                            "locals": locs,
                                            "span": sid, "rid": rid}})
                    continue
                k, p, t, s, o, i = ev
                events.append({"key": k, "phase": p, "t": t,
                               "stream": s, "object": o,
                               "info": i or {}})
        for src in native:
            # natively-executed pools: the in-engine ring records,
            # expanded here to the byte-compatible event shape
            events.extend(src.to_records(t0))
        events.sort(key=lambda ev: ev["t"])
        return events

    def meta(self) -> Dict[str, Any]:
        """Per-rank trace metadata: rank, the local perf_counter origin
        (t0), the drop counter, and — when a multi-rank comm engine is
        attached — the wire-measured clock offset to rank 0 that makes
        the Perfetto merge align (tools.merge_chrome / spans)."""
        nd = self.native_dropped()
        with self._ring_lock:
            py_dropped = sum(r.dropped for r in self._rings.values())
        out: Dict[str, Any] = {"rank": self.rank, "t0": self.t0,
                               "dropped": py_dropped + nd,
                               "native_dropped": nd}
        comm = self._comm
        if comm is not None:
            try:
                out.update(comm.clock_meta())
            except Exception as exc:  # noqa: BLE001 — meta is best-effort
                out["clock_error"] = str(exc)[:120]
        return out

    def dump_json(self, path: str) -> None:
        with open(path, "w") as fh:
            json.dump({"dictionary": self._dict,
                       "meta": self.meta(),
                       "events": self.to_records()}, fh)

    def dump_chrome_trace(self, path: str) -> None:
        """Second trace backend (the reference's OTF2 drop-in,
        profiling_otf2.c): Chrome trace-event JSON — loadable by
        chrome://tracing / Perfetto. begin/end pairs become duration
        events per stream; unpaired events become instants."""
        out = []
        # pair on (key, object) — ends may be recorded by a different
        # stream than the begin (e.g. task completion on another worker),
        # so the stream id is display info (tid from the begin), not key
        open_begins: Dict[tuple, Dict] = {}
        for ev in self.to_records():
            us = ev["t"] * 1e6
            key = (ev["key"], ev["object"])
            if ev["phase"] == "begin":
                open_begins[key] = ev
            elif ev["phase"] == "end" and key in open_begins:
                b = open_begins.pop(key)
                out.append({"name": ev["key"], "ph": "X", "pid": 0,
                            "tid": b["stream"], "ts": b["t"] * 1e6,
                            "dur": us - b["t"] * 1e6,
                            "args": ev["info"] or {}})
            else:
                out.append({"name": f"{ev['key']}:{ev['phase']}",
                            "ph": "i", "pid": 0, "tid": ev["stream"],
                            "ts": us, "s": "t",
                            "args": ev["info"] or {}})
        for b in open_begins.values():      # still-open begins → instants
            out.append({"name": f"{b['key']}:begin", "ph": "i", "pid": 0,
                        "tid": b["stream"], "ts": b["t"] * 1e6, "s": "t",
                        "args": b["info"] or {}})
        with open(path, "w") as fh:
            json.dump({"traceEvents": out}, fh)

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = defaultdict(int)
        for ev in self.to_records():
            out[f"{ev['key']}:{ev['phase']}"] += 1
        return dict(out)
