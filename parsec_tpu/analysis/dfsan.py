"""dfsan: runtime dataflow race sanitizer (a PINS module).

FastTrack-style vector-clock race detection (Flanagan & Freund, PLDI
2009) adapted to a task-dataflow runtime: the synchronizing objects are
*dependency releases*, not mutexes, and — unlike thread-based
FastTrack — clocks advance along dependency edges ONLY, never along a
worker thread's incidental program order.  Two DAG-unordered tasks stay
incomparable even when this run's schedule serialized them on one
worker, so a declared-dataflow hazard is flagged on EVERY run, not just
the unlucky interleavings.  Every task instance gets a vector clock
(stored in ``Task.vc``) built from

- the joined clocks of every predecessor that released a dep into it
  (``observe_edge`` — called from the release path in
  ``Context.complete_task`` for each :class:`SuccessorRef`),
- a fresh per-task epoch, and
- a global barrier base advanced at taskpool termination (termdet *is*
  a full synchronization point, so tile state survives across
  sequentially-run taskpools without false positives).

Collection-tile accesses observed through the runtime's release paths —
terminal ``DataRef`` write-backs in ``complete_task``, DTD's
``write_tile`` at retire, PTG ``data_lookup`` reads — are stamped with
the accessing task's clock and checked: a WRITE unordered with the
previous write (WAW) or with a recorded read (R→W), or a read unordered
with the last write (W→R), is a race.  DTD *insert-time* snapshot reads
are synchronization (the tile lock + retire protocol orders them — see
dsl/dtd.py); they join the tile's write clock into the inserted task
instead of being race-checked, which is also what keeps later writers
of a quiesced tile ordered WITHOUT a materialized dep edge.

Extras, per the PR-3 fast-path guard brief:

- **lock-order tracking**: the striped dependency-table locks
  (``_PendingDeps``) and DTD seq-stripe locks report acquisitions here
  (``wrap_lock``); held-while-acquiring edges build a lock-order graph
  and any cycle is flagged as an inversion.
- **determinism digest**: every tile keeps its *version sequence* (the
  ordered labels of its committed writers).  ``digest()`` hashes the
  per-tile sequences — schedule-independent iff the DAG fully orders
  each tile's writers, so two runs under different schedulers /
  ``runtime.bypass_chain`` settings must produce bitwise-identical
  digests.
- **access-mode check**: at release, a body that returned a value for a
  READ/CTL flow (possible via dict returns) is flagged — the dynamic
  half of the lint's access-violation rule.

Install MCA-style (``pins = dfsan``) or explicitly::

    from parsec_tpu.analysis.dfsan import DataflowSanitizer
    san = DataflowSanitizer().install(ctx)
    ... run ...
    assert not san.races
    print(san.digest())

Overhead: every observed access takes one global sanitizer lock and
joins O(#threads) clock entries — runs measure 2-5x slowdown on
task-rate-bound workloads; it is a debugging/CI tool, not a production
default (the reference's PINS modules share this contract).
"""

from __future__ import annotations

import hashlib
import threading
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from ..core.task import FlowAccess
from ..profiling.pins import PinsEvent
from ..profiling.pins_modules import PinsModule

VC = Dict[int, int]


def _leq(a: VC, b: VC) -> bool:
    """a happens-before-or-equals b (componentwise ≤)."""
    for k, v in a.items():
        if v > b.get(k, -1):
            return False
    return True


def _join(into: VC, other: Optional[VC]) -> VC:
    if other:
        for k, v in other.items():
            if v > into.get(k, -1):
                into[k] = v
    return into


@dataclass
class RaceReport:
    """One detected race / violation."""
    kind: str                  # "waw" | "war" | "raw" | "lock-order" |
    #                            "access-violation"
    tile: str = ""
    task: str = ""
    other: str = ""
    message: str = ""

    def __str__(self) -> str:
        return f"[{self.kind}] {self.message}"


Epoch = Tuple[int, int]                # (component, clock)


class _TileState:
    __slots__ = ("write_epoch", "write_vc", "write_task", "reads", "seq")

    def __init__(self) -> None:
        self.write_epoch: Optional[Epoch] = None
        self.write_vc: Optional[VC] = None     # writer's full knowledge
        self.write_task: str = ""
        self.reads: List[Tuple[Epoch, str]] = []
        self.seq: List[str] = []       # committed writer labels, in order


class _OrderedLock:
    """Context-manager shim around a real lock that reports acquisition
    order to the sanitizer (returned by :meth:`DataflowSanitizer.
    wrap_lock`; the runtime only constructs it while a sanitizer is
    installed, so the un-sanitized hot path stays a bare Lock)."""

    __slots__ = ("_lock", "_san", "_domain", "_stripe")

    def __init__(self, lock, san: "DataflowSanitizer", domain: str,
                 stripe: int):
        self._lock = lock
        self._san = san
        self._domain = domain
        self._stripe = stripe

    def __enter__(self):
        self._lock.acquire()
        self._san.lock_acquired(self._domain, self._stripe)
        return self

    def __exit__(self, *exc):
        self._san.lock_released(self._domain, self._stripe)
        self._lock.release()
        return False


class DataflowSanitizer(PinsModule):
    """The ``dfsan`` PINS module (see module docstring)."""

    name = "dfsan"

    def __init__(self) -> None:
        super().__init__()
        self._lock = threading.Lock()
        self._comp: Dict[int, int] = {}          # thread ident -> component
        self._ncomp = 0                          # next component id (live
        #                                          threads AND replayed tasks)
        self._thread_vc: Dict[int, VC] = {}
        self._pending: Dict[Any, VC] = {}        # task key -> joined pred VC
        self._tiles: Dict[Tuple[str, Tuple], _TileState] = {}
        self._base: VC = {}                      # barrier base (termdet)
        self._max: VC = {}                       # join of every task VC
        self.races: List[RaceReport] = []
        self._seen_race_keys: set = set()
        # lock-order graph: (domain, stripe) -> set of locks acquired
        # while this one was held
        self._lock_edges: Dict[Tuple[str, int], set] = {}
        self._held = threading.local()
        self.stats = {"reads": 0, "writes": 0, "edges": 0, "tasks": 0,
                      "repo_accesses": 0, "lock_acquires": 0,
                      "native_replayed_pools": 0,
                      "native_replay_skipped": 0,
                      "native_lock_pairs": 0}

    # ------------------------------------------------------------ lifecycle
    def install(self, context) -> "DataflowSanitizer":
        super().install(context)
        context.dfsan = self
        # native_ok=True (ISSUE 14): these per-task hooks only fire on
        # the Python engine, and natively-executed DTD pools are
        # covered EXACTLY by the fold-time ring replay
        # (replay_native_pool) — so the sanitizer itself no longer
        # disqualifies the native engine via needs_python_engine()
        self._sub(PinsEvent.TASKPOOL_INIT, self._taskpool_init,
                  native_ok=True)
        self._sub(PinsEvent.RELEASE_DEPS_BEGIN, self._release_begin,
                  native_ok=True)
        self._sub(PinsEvent.COMPLETE_EXEC_END, self._complete_end,
                  native_ok=True)
        # adopt the taskpools running at install (a finished one has no
        # dependency left to count)
        with context._lock:
            pools = list(context._active_taskpools)
        for tp in pools:
            self._taskpool_init(tp)
        from ..core.datarepo import DataRepo
        DataRepo.observer = self._repo_access
        return self

    def uninstall(self) -> None:
        super().uninstall()
        from ..core.datarepo import DataRepo
        if DataRepo.observer is self._repo_access:
            DataRepo.observer = None
        if getattr(self.context, "dfsan", None) is self:
            self.context.dfsan = None
        with self.context._lock:
            pools = list(self.context._active_taskpools)
        for tp in pools:
            if getattr(tp.pending, "sanitizer", None) is self:
                tp.pending.sanitizer = None

    def _taskpool_init(self, tp) -> None:
        tp.pending.sanitizer = self      # striped-lock order reporting

    def reset(self) -> None:
        """Drop tile/race state (e.g. between digest comparison runs)."""
        with self._lock:
            self._tiles.clear()
            self._pending.clear()
            self.races.clear()
            self._seen_race_keys.clear()
            self._lock_edges.clear()

    # ------------------------------------------------------------- clocks
    def _alloc_comp(self) -> int:
        """Next clock component id (caller holds the sanitizer lock).
        Live worker threads get one component each; natively-REPLAYED
        tasks get one component PER TASK — with shared components an
        inherited later epoch would shadow an unordered earlier one
        (the per-thread approximation note below), and a fold-time
        replay on one thread would shadow everything."""
        c = self._ncomp
        self._ncomp += 1
        return c

    def _comp_of(self, tid: int) -> int:
        c = self._comp.get(tid)
        if c is None:
            c = self._comp[tid] = self._alloc_comp()
        return c

    def _clock_of_locked(self, task) -> Tuple[Epoch, VC]:
        """Task clock ``(epoch, vc)``, lazily initialized on first
        observation.  ``vc`` is the task's *inherited knowledge* —
        barrier base ⊔ joined predecessor releases; ``epoch`` is its own
        unique (component, clock) stamp, which enters OTHER tasks'
        clocks only through dependency-edge joins, never its own vc.

        Deliberately NOT joined with the executing thread's history
        (where classic thread-based FastTrack would): in a task-dataflow
        runtime the DAG is the program and the worker threads are
        incidental, so clocks advance along dependency edges only.  Two
        DAG-unordered tasks stay incomparable even when this run's
        schedule serialized them on one worker.  (Approximation note:
        components are per-thread for compactness, so an inherited
        LATER epoch on a component can shadow an unordered earlier one
        — a missed race is possible in that narrow pattern, a false
        race is not; the static lint is the exact check.)"""
        clk = task.vc
        if clk is not None:
            return clk
        tid = threading.get_ident()
        comp = self._comp_of(tid)
        tvc = self._thread_vc.setdefault(tid, {})
        tvc[comp] = tvc.get(comp, 0) + 1          # fresh epoch for the task
        epoch = (comp, tvc[comp])
        vc = dict(self._base)
        _join(vc, self._pending.pop(task.key, None))
        task.vc = clk = (epoch, vc)
        _join(self._max, vc)
        self._max[comp] = max(self._max.get(comp, 0), epoch[1])
        self.stats["tasks"] += 1
        return clk

    @staticmethod
    def _epoch_leq(e: Epoch, vc: VC) -> bool:
        """FastTrack's e ⊑ VC: has ``vc`` inherited epoch ``e``?"""
        return e[1] <= vc.get(e[0], 0)

    def barrier(self) -> None:
        """Full synchronization (taskpool termination): everything
        observed so far happens-before everything after (``_max`` holds
        the join of every issued epoch)."""
        with self._lock:
            _join(self._base, self._max)

    def base_snapshot(self) -> VC:
        """Copy of the current barrier base. The native driver takes
        one when an aborted pool enters the RETIRING state (still
        draining): its termination barrier advances ``_base`` before
        the pump folds the drained engine, and a replay seeded from
        the post-barrier base would retroactively order the pool's
        tasks after concurrent pools' accesses — excusing real
        races. ``replay_native_pool`` seeds from the snapshot when
        the engine carries one."""
        with self._lock:
            return dict(self._base)

    # ----------------------------------------------------------- HB edges
    def observe_edge(self, src_task, ref) -> None:
        """One dependency release src_task → ref (called by the release
        path BEFORE the dep is counted, so the successor's clock is
        ready before it can run)."""
        key = ref.task_class.make_key(ref.locals)
        with self._lock:
            epoch, vc = self._clock_of_locked(src_task)
            p = self._pending.setdefault(key, {})
            _join(p, vc)
            p[epoch[0]] = max(p.get(epoch[0], 0), epoch[1])
            self.stats["edges"] += 1

    def _complete_end(self, es, task) -> None:
        with self._lock:
            self._clock_of_locked(task)     # ensure every task is stamped

    # --------------------------------------------------------- tile access
    @staticmethod
    def _tile_key(dc, key) -> Tuple[str, Tuple]:
        # shared with the static lint so static findings and runtime
        # race reports / digests name tiles identically
        from .model import _tile_key
        return _tile_key(dc, key)

    def _race(self, kind: str, tile: str, task: str, other: str,
              message: str) -> None:
        rk = (kind, tile, task, other)
        if rk in self._seen_race_keys:
            return
        self._seen_race_keys.add(rk)
        self.races.append(RaceReport(kind=kind, tile=tile, task=task,
                                     other=other, message=message))

    def _write_locked(self, epoch: Epoch, vc: VC, label: str, tk) -> None:
        """Stamp one committed write (caller holds the sanitizer lock):
        the ONE copy of the WAW/RAW checks + tile-state update, shared
        by the live ``observe_write`` path and the native-pool replay
        so reports and digests cannot drift between engines."""
        st = self._tiles.setdefault(tk, _TileState())
        tile_s = f"{tk[0]}{tk[1]}"
        if st.write_epoch is not None and \
                not self._epoch_leq(st.write_epoch, vc):
            self._race("waw", tile_s, label, st.write_task,
                       f"unordered writes to {tile_s}: {label} vs "
                       f"{st.write_task} — final version is "
                       f"schedule-dependent")
        for repoch, rlabel in st.reads:
            if rlabel != label and not self._epoch_leq(repoch, vc):
                self._race("raw", tile_s, label, rlabel,
                           f"write to {tile_s} by {label} unordered "
                           f"with read by {rlabel}")
        st.write_epoch = epoch
        st.write_vc = dict(vc)
        st.write_task = label
        st.reads.clear()
        st.seq.append(label)
        self.stats["writes"] += 1

    def observe_write(self, task, dc, key) -> None:
        """A committed tile write (DataRef write-back / DTD retire)."""
        tk = self._tile_key(dc, key)
        label = repr(task)
        with self._lock:
            epoch, vc = self._clock_of_locked(task)
            self._write_locked(epoch, vc, label, tk)
        if self.context is not None:
            self.context.pins.data_write(task, dc, key)

    def observe_read(self, task, dc, key, sync: bool = False) -> None:
        """A tile read. ``sync=True`` (DTD insert-time snapshots, which
        the tile-lock/retire protocol already orders) joins the tile's
        write clock into the reader instead of race-checking."""
        tk = self._tile_key(dc, key)
        with self._lock:
            st = self._tiles.setdefault(tk, _TileState())
            if sync:
                if st.write_epoch is not None and task is not None:
                    p = self._pending.setdefault(task.key, {})
                    _join(p, st.write_vc)
                    c, k = st.write_epoch
                    p[c] = max(p.get(c, 0), k)
                self.stats["reads"] += 1
            else:
                epoch, vc = self._clock_of_locked(task)
                label = repr(task)
                tile_s = f"{tk[0]}{tk[1]}"
                if st.write_epoch is not None and \
                        st.write_task != label and \
                        not self._epoch_leq(st.write_epoch, vc):
                    self._race("war", tile_s, label, st.write_task,
                               f"read of {tile_s} by {label} unordered "
                               f"with write by {st.write_task} — may "
                               f"observe either version")
                st.reads.append((epoch, label))
                if len(st.reads) > 512:
                    st.reads = st.reads[-256:]
                self.stats["reads"] += 1
        if self.context is not None:
            self.context.pins.data_read(task, dc, key)

    def _repo_access(self, op: str, repo, key, flow_index: int) -> None:
        """DataRepo entry fill/take observer (datarepo release path)."""
        self.stats["repo_accesses"] += 1

    # ------------------------------------------------------- access modes
    def _release_begin(self, es, task) -> None:
        tc = task.task_class
        for name in task.output:
            flow = tc.flow_by_name.get(name)
            if flow is None:
                continue
            if flow.is_ctl or not (flow.access & FlowAccess.WRITE):
                with self._lock:    # _race mutates shared race state
                    self._race(
                        "access-violation", "", repr(task), name,
                        f"{task!r}: body returned a value for flow "
                        f"{name!r} declared {FlowAccess(flow.access).name}"
                        f" — only WRITE/RW flows are output flows "
                        f"(core.task)")

    # --------------------------------------------------------- lock order
    def wrap_lock(self, lock, domain: str, stripe: int) -> _OrderedLock:
        return _OrderedLock(lock, self, domain, stripe)

    def lock_acquired(self, domain: str, stripe: int) -> None:
        key = (domain, stripe)
        held = getattr(self._held, "stack", None)
        if held is None:
            held = self._held.stack = []
        self.stats["lock_acquires"] += 1
        if held:
            with self._lock:
                for h in held:
                    if h == key:
                        continue
                    self._lock_edges.setdefault(h, set()).add(key)
                    if self._lock_path(key, h):
                        self._race(
                            "lock-order", "", f"{domain}[{stripe}]",
                            f"{h[0]}[{h[1]}]",
                            f"lock-order inversion: {h[0]}[{h[1]}] held "
                            f"while acquiring {domain}[{stripe}], but the "
                            f"reverse order was also observed")
        held.append(key)

    def lock_released(self, domain: str, stripe: int) -> None:
        held = getattr(self._held, "stack", None)
        if held and (domain, stripe) in held:
            held.remove((domain, stripe))

    def _lock_path(self, src, dst) -> bool:
        """Is there an order-graph path src → dst? (caller holds lock)"""
        stack, seen = [src], set()
        while stack:
            u = stack.pop()
            if u == dst:
                return True
            if u in seen:
                continue
            seen.add(u)
            stack.extend(self._lock_edges.get(u, ()))
        return False

    def feed_native_lock_pairs(self, pairs: int) -> None:
        """Fold the C lock-discipline recorder's acquisition-pair
        bitmask (``pdtd_stats`` ``lock_pairs``, bit ``held*5+acquired``
        over ``_native.PDTD_LOCK_DOMAINS``) into the inversion
        detector. The pdtd hot loop's discipline is nesting-free, so a
        healthy engine contributes NOTHING here; any pair lands in the
        shared order graph (domains prefixed ``native-``), and a
        same-domain pair — two nested entry locks, the classic DTD
        deadlock shape — is an inversion by itself."""
        if not pairs:
            return
        from .. import _native
        doms = _native.PDTD_LOCK_DOMAINS
        n = len(doms)
        with self._lock:
            for held in range(n):
                for acq in range(n):
                    if not (pairs >> (held * n + acq)) & 1:
                        continue
                    self.stats["native_lock_pairs"] += 1
                    hk = (f"native-{doms[held]}", 0)
                    ak = (f"native-{doms[acq]}", 0)
                    if hk == ak:
                        self._race(
                            "lock-order", "", f"{ak[0]}[0]",
                            f"{hk[0]}[0]",
                            f"lock-order inversion: nested same-domain "
                            f"native pdtd locks ({doms[held]}) — the "
                            f"self-deadlock shape")
                        continue
                    self._lock_edges.setdefault(hk, set()).add(ak)
                    if self._lock_path(ak, hk):
                        self._race(
                            "lock-order", "", f"{ak[0]}[0]",
                            f"{hk[0]}[0]",
                            f"lock-order inversion: {hk[0]}[0] held "
                            f"while acquiring {ak[0]}[0], but the "
                            f"reverse order was also observed")

    # ---------------------------------------------------- native replay
    def replay_native_pool(self, engine) -> None:
        """Fold-time replay of a natively-executed DTD pool (ISSUE 14).

        The native engine runs insert→release entirely behind the C
        ABI, so the live per-access hooks never fire; instead it hands
        this method (from ``NativeDTD.obs_retire``, BEFORE the
        termination barrier advances ``_base``):

        - **insert-time access manifests** — per tile-bearing task, in
          program order: sync snapshot reads (the tile-lock/retire
          protocol orders them — replayed as clock joins, exactly the
          live ``observe_read(sync=True)``), linked-predecessor HB
          edges (the ``linked_out``-resolved goal edges — the same
          edges ``observe_edge`` sees live, and the superset of the
          ring records' ``parent_seq``), and declared writes;
        - **commit evidence** — which declared writes the body actually
          produced (``observe_write`` stamps only produced flows), plus
          dynamic access-mode violations captured at normalize time;
        - **the frozen event rings** — the completion ground truth: on
          a clean pool every inserted task completed (termination
          requires drain), on an ABORTED pool only ring-recorded seqs
          are replayed, and if the rings wrapped (records dropped) the
          replay is SKIPPED and counted, never guessed — a missing
          happens-before source would fabricate races;
        - **the C lock-discipline pair table** (``lock_pairs``), folded
          into the inversion detector either way.

        Tasks replay in seq order (= insertion program order, a
        topological order of the pool DAG — predecessor ids are always
        smaller). Each replayed task gets its OWN clock component, so
        the exactness matches live operation's per-thread components or
        better; labels are ``class(seq)``, identical to the Python
        engine's ``Task.__repr__``, which keeps race reports AND the
        per-tile version digests bitwise-comparable across engines."""
        manifests = getattr(engine, "_dfsan_manifest", None)
        if manifests is None:
            return
        stats = engine.stats()
        self.feed_native_lock_pairs(stats.get("lock_pairs", 0))
        # the C recorder's acquisition count folds into the same row
        # the Python _OrderedLock wrapper feeds — ONE "how much lock
        # traffic did the sanitizer actually see" surface per run
        self.stats["lock_acquires"] += stats.get("lock_acquires", 0)
        tp = engine.tp
        if tp.error is None:
            replay = sorted(manifests)
        elif stats.get("obs_dropped", 0) or not getattr(engine, "_obs",
                                                        False):
            # an aborted pool replays only ring-EVIDENCED completions;
            # wrapped rings — or rings that never enabled (allocation
            # failure) — mean the evidence is gone: skip LOUDLY, never
            # report a fabricated clean replay
            with self._lock:
                self.stats["native_replay_skipped"] += 1
            return
        else:
            done_seqs: set = set()
            for arr in engine.obs_drain():
                done_seqs.update(int(s) for s in arr["seq"])
            replay = sorted(s for s in manifests if s in done_seqs)
        commits = getattr(engine, "_dfsan_commits", {})
        names = engine.class_names
        completed = stats.get("completed_native", 0) + \
            stats.get("completed_python", 0)
        fired: List[Tuple[str, Any, Any]] = []
        with self._lock:
            # retiring-path folds run AFTER the pool's own termination
            # barrier — seed task clocks from the base snapshot taken
            # at termination (base_snapshot), not the advanced _base
            base = getattr(engine, "_dfsan_base", None)
            if base is None:
                base = self._base
            clocks: Dict[int, Tuple[Epoch, VC]] = {}
            # last replayed committed write PER RUNTIME TILE (collection
            # object identity + key): a sync snapshot read joins THIS,
            # not the label-keyed tile state — the tile-lock/retire
            # protocol only orders accesses through the same collection
            # tile, so label-aliased collections (two views of one
            # buffer, the seeded-WAW fixture) must NOT be retroactively
            # ordered by the replay. Writes from pools that already
            # terminated are covered by the barrier base. (Known
            # approximation, stricter than live: a CONCURRENT pool's
            # commit that a live insert-time read would have observed
            # is not joined — same-label cross-pool traffic without an
            # intervening termination is flagged, not excused.)
            rt_last: Dict[Tuple[int, Tuple], Tuple[Epoch, VC]] = {}
            for seq in replay:
                cls_id, accesses = manifests[seq]
                label = f"{names[cls_id]}({seq})"
                vc = dict(base)
                committed = commits.get(seq, ())
                writes = []
                for acc in accesses:
                    op = acc[0]
                    if op == "edge":
                        pc = clocks.get(acc[1])
                        if pc is not None:
                            pep, pvc = pc
                            _join(vc, pvc)
                            if pep[1] > vc.get(pep[0], -1):
                                vc[pep[0]] = pep[1]
                        self.stats["edges"] += 1
                    elif op == "sync":
                        tk = self._tile_key(acc[1], acc[2])
                        self._tiles.setdefault(tk, _TileState())
                        last = rt_last.get((id(acc[1]), tk[1]))
                        if last is not None:
                            pep, pvc = last
                            _join(vc, pvc)
                            if pep[1] > vc.get(pep[0], -1):
                                vc[pep[0]] = pep[1]
                        self.stats["reads"] += 1
                        fired.append(("r", acc[1], acc[2]))
                    elif acc[3] in committed:   # "write", produced
                        writes.append(acc)
                comp = self._alloc_comp()
                epoch = (comp, 1)
                clocks[seq] = (epoch, vc)
                for acc in writes:
                    tk = self._tile_key(acc[1], acc[2])
                    self._write_locked(epoch, vc, label, tk)
                    rt_last[(id(acc[1]), tk[1])] = (epoch, vc)
                    fired.append(("w", acc[1], acc[2]))
                _join(self._max, vc)
                if 1 > self._max.get(comp, -1):
                    self._max[comp] = 1
            for (seq, cls_name, fname, access) in \
                    getattr(engine, "_dfsan_violations", ()):
                self._race(
                    "access-violation", "", f"{cls_name}({seq})", fname,
                    f"{cls_name}({seq}): body returned a value for "
                    f"flow {fname!r} declared "
                    f"{FlowAccess(access).name} — only WRITE/RW flows "
                    f"are output flows (core.task)")
            self.stats["tasks"] += completed
            self.stats["native_replayed_pools"] += 1
        if self.context is not None:
            pins = self.context.pins
            for kind, dc, key in fired:
                # same PINS rebroadcast as the live paths; the replay
                # has no Task object, so observers receive task=None
                if kind == "w":
                    pins.data_write(None, dc, key)
                else:
                    pins.data_read(None, dc, key)

    # ------------------------------------------------------------- digest
    def digest(self) -> str:
        """Per-tile version-sequence digest: sha256 over the committed
        writer sequences, keyed by tile.  Schedule-independent iff the
        DAG fully orders every tile's writers — the regression handle
        for scheduler / release-path optimizations."""
        h = hashlib.sha256()
        with self._lock:
            for tk in sorted(self._tiles, key=repr):
                st = self._tiles[tk]
                h.update(repr((tk, tuple(st.seq))).encode())
        return h.hexdigest()

    def version_sequences(self) -> Dict[Tuple[str, Tuple], List[str]]:
        with self._lock:
            return {tk: list(st.seq) for tk, st in self._tiles.items()}

    # ------------------------------------------------------------- report
    def report(self) -> Dict[str, Any]:
        return {"races": [str(r) for r in self.races],
                "digest": self.digest(), **self.stats}


def get(context) -> Optional[DataflowSanitizer]:
    """The installed sanitizer of ``context`` (None when off)."""
    return getattr(context, "dfsan", None)
