"""Request-scoped distributed tracing: span context + reconstruction.

Dapper-shaped (Sigelman et al., Google TR 2010) over the existing
:class:`~parsec_tpu.profiling.trace.Trace` event stream: every serving
``Submission`` mints a trace id (*rid*), and the runtime records
causally-parented spans as ordinary trace events whose ``info`` carries
``{rid, span, parent}``:

- ``req``        — the submission root (serving/runtime.py, begin at
                   submit, end at pool termination);
- ``admission``  — a backpressure park in the tenant window (recorded
                   only when the insert actually waited);
- ``task``       — one task execution; the begin event also carries
                   ``q_us`` (ready→select queue wait) so the queue
                   share costs no extra event;
- ``wire``       — one tree-edge/wire hop: the SENDER records phase
                   ``sent`` (minting the hop's span id, parented to the
                   sending task), every receiver records ``recv`` with
                   the same span id; tasks released by the payload are
                   parented to the hop.

Span ids are INTEGERS — ``(rank << 44) | n`` with a per-process
monotonic counter — so ids from different ranks never collide and the
merged multi-rank tree needs no coordination; the mint is one shift+or
(it runs once per task on the null-task hot path, where a formatted
string measurably moved the obs_overhead_pct guard). The only
non-integer ids are submission ROOT spans
(``"req:<pool>#root<rank>"`` strings, serving/runtime.py) — the
reconstruction treats ids as opaque keys either way.

Cross-rank timestamp alignment: each rank's dumped trace carries
``meta = {rank, t0, clock_offset_s}`` where ``clock_offset_s`` is the
wire-measured offset of this process's ``perf_counter`` domain to rank
0's (pingpong handshake, ``SocketCommEngine.clock_offset_to``); a span
at local time ``t`` aligns to ``t + t0 + clock_offset_s`` in rank-0's
clock. :func:`align_shift` returns that shift per trace.

Reconstruction (:func:`build_spans`, :func:`critpath`) powers the
``tools critpath`` CLI: the request's span tree, its latency breakdown
(admission / queue / exec / wire), and the critical path walked over
executed dependency edges (the parent links ARE dep edges).
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, List, Optional, Sequence

_counter = itertools.count(1)

#: rank field width of an integer span id (ids are ints, not strings:
#: the mint runs once per task on the null-task hot path, where the
#: f-string version measurably moved the obs_overhead_pct guard)
_RANK_SHIFT = 44

#: bit set in every NATIVELY-minted span id (the pdtd event rings mint
#: ids in C++ from their own process-global counter — ISSUE 13): it
#: partitions the sub-rank id space so a native id can never collide
#: with this module's Python counter on the same rank, with zero
#: cross-engine coordination
_NATIVE_BIT = 43


def next_span_id(rank: int = 0) -> int:
    """Mint a process-unique span id; the rank rides the high bits so
    ids from different ranks never collide in a merged trace."""
    return (rank << _RANK_SHIFT) | next(_counter)


def native_span_base(rank: int = 0) -> int:
    """Base ORed into every span id the native pdtd event rings mint
    (``pdtd_obs_enable``): rank in the high bits like
    :func:`next_span_id`, plus the native marker bit so the two mint
    domains stay disjoint within a rank."""
    return (rank << _RANK_SHIFT) | (1 << _NATIVE_BIT)


def mint_rid(name: str) -> str:
    """Deterministic request/trace id for a submission: derived from
    the taskpool NAME (the cross-rank registry identity), so every rank
    of a distributed submission mints the SAME rid without any wire
    exchange — one span tree spans the mesh."""
    return f"req:{name}"


# ---------------------------------------------------------------------------
# reconstruction over dumped traces
# ---------------------------------------------------------------------------

def align_shift(trace: Dict[str, Any]) -> float:
    """Seconds to ADD to a trace's event times to land in the root
    rank's perf_counter domain (0.0 for metadata-less traces — the
    pre-span single-process format stays byte-compatible)."""
    meta = trace.get("meta") or {}
    return float(meta.get("t0", 0.0)) + float(
        meta.get("clock_offset_s", 0.0))


def _rank_of(trace: Dict[str, Any], fallback: int) -> int:
    meta = trace.get("meta") or {}
    return int(meta.get("rank", fallback))


def build_spans(traces: Sequence[Dict[str, Any]],
                rid: Optional[str] = None) -> Dict[str, Dict]:
    """Reconstruct the span graph from dumped rank traces.

    Returns ``{span_id: node}`` with nodes shaped::

        {"kind": "req"|"admission"|"task"|"wire", "rid", "rank",
         "t0", "t1",            # aligned seconds (root-rank clock)
         "parent": span_id|None,
         "name", "q_us",        # task nodes
         "src", "dst", "nbytes",  # wire nodes (per-edge children in
                                   "edges": [{src, dst, t_sent, t_recv}])
        }

    ``rid=None`` keeps every request; pass a rid to filter."""
    nodes: Dict[str, Dict] = {}
    wire_sent: Dict[tuple, Dict] = {}     # (span, dst) -> sent record
    wire_recv: List[Dict] = []
    open_begins: Dict[str, Dict] = {}
    for fallback_rank, tr in enumerate(traces):
        shift = align_shift(tr)
        rank = _rank_of(tr, fallback_rank)
        for ev in tr["events"]:
            info = ev.get("info") or {}
            sid = info.get("span")
            if sid is None or (rid is not None and
                               info.get("rid") != rid):
                continue
            t = ev["t"] + shift
            key, phase = ev["key"], ev["phase"]
            if key == "wire":
                if phase == "sent":
                    wire_sent[(sid, info.get("dst"))] = {
                        "t": t, "rank": rank, "info": info}
                elif phase == "recv":
                    wire_recv.append({"t": t, "rank": rank,
                                      "info": info})
                continue
            if phase == "begin":
                node = nodes.get(sid)
                if node is None:
                    node = nodes[sid] = {
                        "kind": key, "rid": info.get("rid"),
                        "rank": rank, "t0": t, "t1": t,
                        "parent": info.get("parent"),
                        "name": str(ev.get("object") or key)}
                    if "q_us" in info:
                        node["q_us"] = info["q_us"]
                open_begins[sid] = node
            elif phase == "end":
                node = open_begins.pop(sid, None) or nodes.get(sid)
                if node is not None:
                    node["t1"] = max(node["t1"], t)
    # wire hops: one node per span id, one edge per (src, dst) pair;
    # the node's [t0, t1] covers send-of-first-edge .. recv-of-last
    for rec in wire_recv:
        info = rec["info"]
        sid = info["span"]
        sent = wire_sent.get((sid, rec["rank"]))
        t_sent = sent["t"] if sent is not None else rec["t"]
        node = nodes.get(sid)
        if node is None:
            node = nodes[sid] = {
                "kind": "wire", "rid": info.get("rid"),
                "rank": info.get("src", -1), "t0": t_sent,
                "t1": rec["t"], "parent": info.get("parent"),
                "name": f"wire:{sid}", "nbytes": info.get("nbytes", 0),
                "edges": []}
        node["t0"] = min(node["t0"], t_sent)
        node["t1"] = max(node["t1"], rec["t"])
        node.setdefault("edges", []).append(
            {"src": info.get("src"), "dst": rec["rank"],
             "t_sent": t_sent, "t_recv": rec["t"]})
    # a sent hop whose recv trace is missing still shows up (dur 0)
    for (sid, dst), sent in wire_sent.items():
        if sid not in nodes:
            info = sent["info"]
            nodes[sid] = {"kind": "wire", "rid": info.get("rid"),
                          "rank": sent["rank"], "t0": sent["t"],
                          "t1": sent["t"], "parent": info.get("parent"),
                          "name": f"wire:{sid}",
                          "nbytes": info.get("nbytes", 0), "edges": []}
    return nodes


def rids(traces: Sequence[Dict[str, Any]]) -> List[str]:
    """Every rid present in the traces, in first-seen order."""
    seen: List[str] = []
    for tr in traces:
        for ev in tr["events"]:
            r = (ev.get("info") or {}).get("rid")
            if r is not None and r not in seen:
                seen.append(r)
    return seen


def breakdown(nodes: Dict[str, Dict]) -> Dict[str, float]:
    """Latency shares in milliseconds: admission (backpressure parks),
    queue (ready→select waits), exec (task bodies), wire (send→recv
    per hop edge)."""
    out = {"admission_ms": 0.0, "queue_ms": 0.0, "exec_ms": 0.0,
           "wire_ms": 0.0, "spans": len(nodes)}
    for node in nodes.values():
        kind = node["kind"]
        dur_ms = (node["t1"] - node["t0"]) * 1e3
        if kind == "admission":
            out["admission_ms"] += dur_ms
        elif kind == "task":
            out["exec_ms"] += dur_ms
            out["queue_ms"] += node.get("q_us", 0.0) / 1e3
        elif kind == "wire":
            for e in node.get("edges", ()):
                out["wire_ms"] += max(e["t_recv"] - e["t_sent"], 0.0) \
                    * 1e3
    for k in ("admission_ms", "queue_ms", "exec_ms", "wire_ms"):
        out[k] = round(out[k], 4)
    return out


def critpath(traces: Sequence[Dict[str, Any]], rid: str) -> Dict:
    """Reconstruct ``rid``'s span tree and report its latency breakdown
    plus the critical path over executed dep edges: starting from the
    last-finishing task span, walk parent links (task → wire hop →
    producing task → ... → submission root)."""
    nodes = build_spans(traces, rid=rid)
    if not nodes:
        raise ValueError(f"rid {rid!r}: no spans found "
                         f"(have {rids(traces)[:8]})")
    bd = breakdown(nodes)
    tasks = [n for n in nodes.values() if n["kind"] == "task"]
    tail = max(tasks or nodes.values(), key=lambda n: n["t1"])
    t_base = min(n["t0"] for n in nodes.values())
    path: List[Dict] = []
    cur: Optional[Dict] = tail
    seen: set = set()
    while cur is not None and id(cur) not in seen:
        seen.add(id(cur))
        path.append({
            "kind": cur["kind"], "name": cur["name"],
            "rank": cur["rank"],
            "start_ms": round((cur["t0"] - t_base) * 1e3, 4),
            "dur_ms": round((cur["t1"] - cur["t0"]) * 1e3, 4),
            "queue_us": cur.get("q_us")})
        cur = nodes.get(cur.get("parent"))
    path.reverse()
    ranks = sorted({n["rank"] for n in nodes.values()})
    return {
        "rid": rid,
        "ranks": ranks,
        "n_spans": len(nodes),
        "n_tasks": len(tasks),
        "request_ms": round((tail["t1"] - t_base) * 1e3, 4),
        "breakdown": bd,
        "critical_path": path,
        # the root "req" span covers the whole request; only the work
        # spans along the walk sum into the path length
        "critical_path_ms": round(sum(p["dur_ms"] for p in path
                                      if p["kind"] != "req"), 4),
    }


def render_critpath(rep: Dict) -> str:
    """Human-readable critical-path report (the CLI output)."""
    bd = rep["breakdown"]
    lines = [
        f"request {rep['rid']}: {rep['request_ms']:.3f} ms across "
        f"ranks {rep['ranks']} ({rep['n_spans']} spans, "
        f"{rep['n_tasks']} tasks)",
        f"  breakdown: admission {bd['admission_ms']:.3f} ms | "
        f"queue {bd['queue_ms']:.3f} ms | exec {bd['exec_ms']:.3f} ms "
        f"| wire {bd['wire_ms']:.3f} ms",
        f"  critical path ({len(rep['critical_path'])} spans, "
        f"{rep['critical_path_ms']:.3f} ms):",
    ]
    for p in rep["critical_path"]:
        q = f" q={p['queue_us']:.0f}us" if p.get("queue_us") else ""
        lines.append(f"    [{p['kind']:9s}] r{p['rank']} "
                     f"+{p['start_ms']:9.3f} ms  {p['dur_ms']:9.3f} ms"
                     f"{q}  {p['name']}")
    return "\n".join(lines)
