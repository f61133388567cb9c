"""Always-on metrics plane: counters, gauges, log₂ histograms.

Reference role: parsec/mca/pins + the SDE software counters expose
runtime state, but only as offline traces or pull-by-hand dicts
(PINS: Danalis et al., VPA/SC 2014). A serving runtime needs the same
signals LIVE and cheap enough to leave enabled, so this module is a
small Prometheus-style registry:

- **Counters** shard per recording thread (one plain dict slot per
  thread — no locks, no CAS on the hot path; the GIL makes the
  single-writer-per-shard increment safe) and aggregate at read time.
- **Gauges** are either set directly or computed at scrape time by
  registered *collectors* (closures reading live runtime state:
  scheduler queue depth, wfq ``pool_stats``, tenant windows, HBM
  residency, compile-cache hits). Nothing is paid until someone
  scrapes.
- **Histograms** bucket by log₂ (one ``math.frexp`` per observation) —
  the per-tenant request-latency distribution ships as a standard
  Prometheus histogram.

Export: :func:`to_prometheus_text` (text exposition format 0.0.4) and
:func:`to_dict` (JSON), both served by the optional stdlib HTTP
listener (``serving.metrics_port``: ``/metrics`` + ``/statusz``) and by
``Context.statusz()``.

The registry is process-global (like the Prometheus client default
registry): comm engines, contexts, and serving runtimes all register
into ONE export surface instead of keeping parallel ad-hoc dicts.
``profiling.metrics = 0`` disables the runtime's hot-path increments
and collectors — the A/B switch the observability bench measures the
always-on cost with; the registry object itself always exists.
"""

from __future__ import annotations

import itertools
import json
import math
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..utils import mca_param

mca_param.register("profiling.metrics", 1,
                   help="always-on metrics plane: hot-path counters + "
                        "scrape-time collectors (0 = off: the hot "
                        "path as it was before the plane)")
mca_param.register("serving.metrics_port", 0,
                   help="serve /metrics (Prometheus text) and /statusz "
                        "(JSON) on this localhost port via a stdlib "
                        "HTTP listener (0 = off)")


def enabled() -> bool:
    return str(mca_param.get("profiling.metrics", 1)).lower() not in (
        "0", "off", "false")


def _label_key(labelnames: Tuple[str, ...], kv: Dict[str, Any]) -> Tuple:
    try:
        return tuple(str(kv[n]) for n in labelnames)
    except KeyError as exc:
        raise ValueError(
            f"metric labels {labelnames} require {exc.args[0]!r}") from exc


class _Counter:
    """One labeled counter child: per-thread shards, summed at read."""

    __slots__ = ("_shards",)

    def __init__(self) -> None:
        self._shards: Dict[int, float] = {}

    def inc(self, n: float = 1.0) -> None:
        # single writer per shard key (the recording thread), so the
        # read-modify-write below cannot interleave with another
        # writer; readers only ever sum a snapshot
        s = self._shards
        tid = threading.get_ident()
        s[tid] = s.get(tid, 0.0) + n

    def value(self) -> float:
        return sum(self._shards.values())


class _Gauge:
    """One labeled gauge child: last-set value or a callable source."""

    __slots__ = ("_value", "_fn")

    def __init__(self) -> None:
        self._value = 0.0
        self._fn: Optional[Callable[[], float]] = None

    def set(self, v: float) -> None:
        self._value = float(v)

    def set_function(self, fn: Callable[[], float]) -> None:
        self._fn = fn

    def value(self) -> float:
        if self._fn is not None:
            try:
                return float(self._fn())
            except Exception:  # noqa: BLE001 — a scrape must not raise
                return float("nan")
        return self._value


class _Histogram:
    """One labeled log₂-bucket histogram child (per-thread shards).

    Bucket *i* counts observations with ``value <= 2**i`` (and above the
    next-lower power of two); the exposition renders the standard
    cumulative ``_bucket{le=...}`` series plus ``_sum``/``_count``."""

    __slots__ = ("_shards",)

    def __init__(self) -> None:
        # tid -> [bucket-counts dict, sum, count]
        self._shards: Dict[int, List] = {}

    def observe(self, v: float) -> None:
        v = float(v)
        if v <= 0.0:
            exp = -64                      # underflow bucket
        else:
            m, exp = math.frexp(v)         # v = m * 2**exp, 0.5 <= m < 1
            if m == 0.5:                   # exact power of two: le=2**(exp-1)
                exp -= 1
        s = self._shards
        tid = threading.get_ident()
        shard = s.get(tid)
        if shard is None:
            shard = s[tid] = [{}, 0.0, 0]
        b = shard[0]
        b[exp] = b.get(exp, 0) + 1
        shard[1] += v
        shard[2] += 1

    def snapshot(self) -> Tuple[Dict[int, int], float, int]:
        buckets: Dict[int, int] = {}
        total, count = 0.0, 0
        for b, s, c in list(self._shards.values()):
            # list(items) snapshots the bucket dict (GIL-atomic): a
            # concurrent observe() may insert a NEW log2 bucket while a
            # scrape iterates — live iteration would raise "dictionary
            # changed size during iteration" out of the HTTP handler
            for exp, n in list(b.items()):
                buckets[exp] = buckets.get(exp, 0) + n
            total += s
            count += c
        return buckets, total, count


class _Family:
    """A named metric family holding one child per label-value tuple."""

    def __init__(self, name: str, help_: str, kind: str,
                 labelnames: Tuple[str, ...], child_cls):
        self.name = name
        self.help = help_
        self.kind = kind
        self.labelnames = tuple(labelnames)
        self._child_cls = child_cls
        self._children: Dict[Tuple, Any] = {}
        self._lock = threading.Lock()

    def labels(self, **kv):
        key = _label_key(self.labelnames, kv)
        child = self._children.get(key)
        if child is None:
            with self._lock:
                child = self._children.get(key)
                if child is None:
                    child = self._children[key] = self._child_cls()
        return child

    def clear(self) -> None:
        """Drop every child."""
        with self._lock:
            self._children.clear()

    def remove(self, **kv) -> None:
        """Unexport one child (a caller-held reference keeps working —
        removal only stops the registry from exporting it). Collectors
        prune dead pools/tenants with this so a persistent serving
        Context's registry stays bounded."""
        self.remove_key(_label_key(self.labelnames, kv))

    def remove_key(self, key: Tuple) -> None:
        with self._lock:
            self._children.pop(key, None)

    def samples(self) -> List[Tuple[Dict[str, str], Any]]:
        with self._lock:
            items = list(self._children.items())
        return [(dict(zip(self.labelnames, key)), child)
                for key, child in items]


class MetricsRegistry:
    """Process-global metric registry (Prometheus-client shaped)."""

    def __init__(self) -> None:
        self._families: Dict[str, _Family] = {}
        self._lock = threading.Lock()
        self._collectors: List[Callable[[], None]] = []
        self.collector_errors = 0

    # ------------------------------------------------------- registration
    def _family(self, name: str, help_: str, kind: str,
                labelnames: Tuple[str, ...], child_cls) -> _Family:
        fam = self._families.get(name)
        if fam is not None:
            if fam.kind != kind or fam.labelnames != tuple(labelnames):
                raise ValueError(
                    f"metric {name!r} re-registered as {kind}"
                    f"{labelnames} but exists as {fam.kind}"
                    f"{fam.labelnames}")
            return fam
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = self._families[name] = _Family(
                    name, help_, kind, labelnames, child_cls)
            return fam

    def counter(self, name: str, help_: str = "",
                labelnames: Tuple[str, ...] = ()) -> _Family:
        return self._family(name, help_, "counter", labelnames, _Counter)

    def gauge(self, name: str, help_: str = "",
              labelnames: Tuple[str, ...] = ()) -> _Family:
        return self._family(name, help_, "gauge", labelnames, _Gauge)

    def histogram(self, name: str, help_: str = "",
                  labelnames: Tuple[str, ...] = ()) -> _Family:
        return self._family(name, help_, "histogram", labelnames,
                            _Histogram)

    def prune_ranks(self, gone_ranks, label: str = "rank") -> int:
        """Unexport every child whose ``label`` value names a rank in
        ``gone_ranks`` — the elastic-capacity pruning pass: when the
        live set shrinks (a rank drained or died), its rank-labeled
        children (wire counters of a loopback fabric, per-rank capacity
        gauges, pool/tenant rows of a departed rank) must not linger in
        ``/metrics`` forever. Caller-held references keep working
        (``_Family.remove`` semantics). Returns the number of children
        pruned; a rank re-admitted later simply re-creates its children
        on the next record/scrape."""
        gone = {str(int(r)) for r in gone_ranks}
        if not gone:
            return 0
        n = 0
        with self._lock:
            families = list(self._families.values())
        for fam in families:
            if label not in fam.labelnames:
                continue
            idx = fam.labelnames.index(label)
            for labels, _child in fam.samples():
                key = tuple(labels[name] for name in fam.labelnames)
                if key[idx] in gone:
                    fam.remove_key(key)
                    n += 1
        return n

    def register_collector(self, fn: Callable[[], None]) -> None:
        """``fn`` runs at every scrape and sets gauges from live state."""
        with self._lock:
            if fn not in self._collectors:
                self._collectors.append(fn)

    def unregister_collector(self, fn: Callable[[], None]) -> None:
        with self._lock:
            try:
                self._collectors.remove(fn)
            except ValueError:
                pass

    # ------------------------------------------------------------- export
    def _run_collectors(self) -> None:
        with self._lock:
            collectors = list(self._collectors)
        for fn in collectors:
            try:
                fn()
            except Exception:  # noqa: BLE001 — one bad collector must not
                self.collector_errors += 1     # sink the whole scrape

    @staticmethod
    def _esc(v: str) -> str:
        return str(v).replace("\\", r"\\").replace('"', r'\"') \
            .replace("\n", r"\n")

    @classmethod
    def _labelstr(cls, labels: Dict[str, str],
                  extra: Tuple[Tuple[str, str], ...] = ()) -> str:
        parts = [f'{k}="{cls._esc(v)}"' for k, v in labels.items()]
        parts += [f'{k}="{cls._esc(v)}"' for k, v in extra]
        return "{" + ",".join(parts) + "}" if parts else ""

    def to_prometheus_text(self) -> str:
        """Text exposition format 0.0.4 (the /metrics payload)."""
        self._run_collectors()
        out: List[str] = []
        with self._lock:
            families = list(self._families.values())
        for fam in families:
            out.append(f"# HELP {fam.name} {fam.help}")
            out.append(f"# TYPE {fam.name} {fam.kind}")
            for labels, child in fam.samples():
                ls = self._labelstr(labels)
                if fam.kind == "histogram":
                    buckets, total, count = child.snapshot()
                    cum = 0
                    for exp in sorted(buckets):
                        cum += buckets[exp]
                        le = self._labelstr(
                            labels, (("le", repr(float(2.0 ** exp))),))
                        out.append(f"{fam.name}_bucket{le} {cum}")
                    inf = self._labelstr(labels, (("le", "+Inf"),))
                    out.append(f"{fam.name}_bucket{inf} {count}")
                    out.append(f"{fam.name}_sum{ls} {total}")
                    out.append(f"{fam.name}_count{ls} {count}")
                else:
                    out.append(f"{fam.name}{ls} {child.value()}")
        return "\n".join(out) + "\n"

    def to_dict(self) -> Dict[str, Any]:
        """JSON view of every family (the /statusz metrics block)."""
        self._run_collectors()
        out: Dict[str, Any] = {}
        with self._lock:
            families = list(self._families.values())
        for fam in families:
            rows = []
            for labels, child in fam.samples():
                if fam.kind == "histogram":
                    buckets, total, count = child.snapshot()
                    rows.append({"labels": labels, "count": count,
                                 "sum": total,
                                 "buckets": {repr(float(2.0 ** e)): n
                                             for e, n in
                                             sorted(buckets.items())}})
                else:
                    rows.append({"labels": labels,
                                 "value": child.value()})
            out[fam.name] = {"type": fam.kind, "help": fam.help,
                             "values": rows}
        return out


_REGISTRY = MetricsRegistry()
_ENGINE_IDS = itertools.count(1)


def registry() -> MetricsRegistry:
    """The process-global registry every runtime layer exports into."""
    return _REGISTRY


def next_engine_id() -> int:
    """Unique per-process comm-engine id (the ``engine`` label that
    keeps two in-process loopback engines' wire counters separable)."""
    return next(_ENGINE_IDS)


# ---------------------------------------------------------------------------
# Context collectors: live runtime state -> gauges at scrape time
# ---------------------------------------------------------------------------

def install_context_collectors(context) -> Callable[[], None]:
    """Register one scrape-time collector for ``context`` (weakly held)
    covering scheduler depth/steal rates, wfq ``pool_stats``, tenant
    admission windows, HBM residency/evictions, and compile-cache hits.
    Returns the uninstall closure (called from ``Context.fini``).

    Bounded by construction: every gauge child this collector sets is
    tracked, children for pools/tenants that disappeared are pruned at
    the next scrape, and the uninstall closure removes them all — a
    persistent serving Context minting one pool per request cannot grow
    the registry without bound."""
    import weakref
    reg = registry()
    ref = weakref.ref(context)
    rank = str(context.my_rank)
    owned: Dict[Any, set] = {}        # family -> label keys set by us

    g_done = reg.gauge("parsec_tasks_completed_total",
                       "tasks completed by the host runtime (sum of "
                       "the per-stream executed counters + device "
                       "completions + native-DTD engine completions; "
                       "computed at scrape time — the hot path pays "
                       "nothing)", ("rank",))
    g_native = reg.gauge("parsec_native_dtd",
                         "native DTD engine counters (inserted/"
                         "ready_pushed/stolen/released_edges/"
                         "completed_native/completed_python/"
                         "ring_highwater/inflight/ready plus the "
                         "observability-plane rows obs_recorded/"
                         "obs_dropped/obs_ring_depth of the in-engine "
                         "event rings, read from the engine's C++ "
                         "atomics at scrape time)",
                         ("rank", "key"))
    g_ready = reg.gauge("parsec_sched_ready_tasks",
                        "tasks queued in the scheduler", ("rank",))
    g_pools = reg.gauge("parsec_active_taskpools",
                        "live taskpools in the context", ("rank",))
    g_stream = reg.gauge("parsec_stream_events",
                         "per-context stream totals (selected/stolen/"
                         "starved/executed)", ("rank", "event"))
    g_pool = reg.gauge("parsec_pool_tasks",
                       "wfq per-pool service accounting "
                       "(enqueued/selected/pending)",
                       ("rank", "pool", "tenant", "event"))
    g_tenant = reg.gauge("parsec_tenant_state",
                         "serving tenant admission state (inflight/"
                         "window/hbm_reserved/quarantined and the "
                         "runtime stats rows)", ("rank", "tenant", "key"))
    g_hbm = reg.gauge("parsec_hbm_stats",
                      "HBM residency manager counters "
                      "(resident_tiles/stage_in/spills/bytes_staged/"
                      "bytes_spilled/peak_bytes/evict_belady/evict_lru)",
                      ("rank", "key"))
    g_cc = reg.gauge("parsec_compile_cache",
                     "compile-cache hit/miss counters "
                     "(utils.compile_cache.cache_stats)", ("key",))
    g_cap = reg.gauge("parsec_capacity",
                      "elastic-capacity state (configured/world/live/"
                      "departed/dead rank counts from the comm "
                      "engine's world_status, plus the autoscaler's "
                      "desired count when a controller is attached)",
                      ("rank", "key"))
    g_kv_pages = reg.gauge("parsec_kv_pages_in_use",
                           "KV state layer: pages currently allocated "
                           "(prefix cache + live requests + draft "
                           "branches) — the autoscaler's KV-pressure "
                           "signal", ("rank",))
    g_kv_hit = reg.gauge("parsec_kv_hit_rate",
                         "KV prefix-cache hit rate (prompt tokens "
                         "served from cached pages / prompt tokens "
                         "looked up, cumulative)", ("rank",))
    g_kv = reg.gauge("parsec_kv_state",
                     "KV state layer counters (pages_free/capacity/"
                     "cow_copies/evict_reclaims/peak_in_use/exhausted/"
                     "tokens_prefilled/requests/requests_hit/"
                     "spec_windows/spec_accepted_steps/"
                     "spec_rejected_windows/spec_cancelled_branches "
                     "plus the radix-tree nodes/cached_pages/"
                     "evicted_* rows), read at scrape time",
                     ("rank", "key"))

    pruned_ranks: set = set()         # gone ranks already swept

    def _prune() -> None:
        for fam, keys in owned.items():
            for key in keys:
                fam.remove_key(key)
        owned.clear()

    def collect() -> None:
        ctx = ref()
        if ctx is None:
            reg.unregister_collector(collect)
            _prune()
            return
        seen: Dict[Any, set] = {}

        def setg(fam, value, **labels) -> None:
            key = _label_key(fam.labelnames, labels)
            fam.labels(**labels).set(value)
            seen.setdefault(fam, set()).add(key)

        setg(g_ready, ctx.scheduler.pending_tasks(), rank=rank)
        with ctx._lock:
            setg(g_pools, len(ctx._active_taskpools), rank=rank)
        agg = {"selected": 0, "stolen": 0, "starved": 0, "executed": 0}
        for es in ctx.streams:
            for k in agg:
                agg[k] += es.stats.get(k, 0)
        for k, v in agg.items():
            setg(g_stream, v, rank=rank, event=k)
        # native DTD engines complete tasks outside the stream counters
        # (the whole point of the native loop) — fold them in so the
        # completed-total stays correct whichever engine ran the pool
        nstats = ctx.native_dtd_stats()
        for k, v in nstats.items():
            setg(g_native, v, rank=rank, key=k)
        setg(g_done, agg["executed"] +
             ctx.stats.get("device_completed", 0) +
             nstats.get("completed_native", 0) +
             nstats.get("completed_python", 0), rank=rank)
        sched = ctx.scheduler
        if hasattr(sched, "pool_stats"):
            for pool, row in sched.pool_stats().items():
                ten = row.get("tenant") or ""
                for k in ("enqueued", "selected", "pending"):
                    setg(g_pool, row[k], rank=rank, pool=pool,
                         tenant=ten, event=k)
        srv = ctx.serving
        if srv is not None:
            for name, ten in srv.tenants().items():
                rows = {"inflight": ten.inflight, "window": ten.window,
                        "hbm_reserved": ten.hbm_reserved,
                        "quarantined": 1 if ten.quarantined else 0,
                        **ten.stats}
                for k, v in rows.items():
                    setg(g_tenant, v, rank=rank, tenant=name, key=k)
        # native-engine completions per tenant (ISSUE 13): native pools
        # bypass the per-task tenant hooks — the engine atomics carry
        # the truth, folded here at scrape time
        for ten, n in ctx.native_tenant_stats().items():
            if n:
                setg(g_tenant, n, rank=rank, tenant=ten,
                     key="native_tasks")
        kvl = getattr(ctx, "kv_state", None)
        if kvl is not None:
            # scrape-time collectors ONLY (ISSUE 15 contract: the KV
            # hot path pays nothing for observability) — the layer's
            # snapshot is a lock-guarded dict copy
            snap = kvl.snapshot()
            pool_snap = snap.pop("pool", {})
            tree_snap = snap.pop("tree", {})
            setg(g_kv_pages, pool_snap.get("pages_in_use", 0),
                 rank=rank)
            setg(g_kv_hit, snap.get("hit_rate", 0.0), rank=rank)
            for k in ("pages_free", "capacity", "cow_copies",
                      "evict_reclaims", "peak_in_use", "exhausted"):
                setg(g_kv, pool_snap.get(k, 0), rank=rank, key=k)
            for k in ("nodes", "cached_pages", "evicted_nodes",
                      "evicted_pages"):
                setg(g_kv, tree_snap.get(k, 0), rank=rank,
                     key=f"tree_{k}")
            for k in ("tokens_prefilled", "requests", "requests_hit",
                      "spec_windows", "spec_accepted_steps",
                      "spec_rejected_windows",
                      "spec_cancelled_branches"):
                setg(g_kv, snap.get(k, 0), rank=rank, key=k)
        hbm = ctx.hbm
        if hbm is not None:
            with hbm._lock:
                resident = sum(1 for e in hbm._entries.values()
                               if e.get("offset") is not None)
                stats = dict(hbm.stats)
            setg(g_hbm, resident, rank=rank, key="resident_tiles")
            for k, v in stats.items():
                setg(g_hbm, v, rank=rank, key=k)
        try:
            from ..utils import compile_cache
            for k, v in compile_cache.cache_stats().items():
                setg(g_cc, v, key=k)
        except Exception:  # noqa: BLE001 — optional surface
            pass
        comm = ctx.comm
        if comm is not None and hasattr(comm, "world_status"):
            ws = comm.world_status()
            for k in ("configured", "world"):
                setg(g_cap, ws.get(k, 0), rank=rank, key=k)
            for k in ("live", "departed", "dead"):
                setg(g_cap, len(ws.get(k) or ()), rank=rank, key=k)
            el = getattr(srv, "elastic", None) if srv is not None \
                else None
            if el is not None:
                setg(g_cap, el.desired, rank=rank, key="desired")
            # elastic-capacity pruning (the live set shrank): children
            # labeled with a drained/dead rank — wire counters of an
            # in-process loopback fabric, stale pool/tenant rows, a
            # departed rank's capacity gauges — must not linger in
            # /metrics forever. Own-rank children are never pruned,
            # and each gone rank is swept ONCE (the scrape after the
            # shrink), not re-scanned on every later scrape of a
            # long-lived context; a re-admitted rank drops out of the
            # swept set so a later departure prunes it again.
            gone = (set(ws.get("departed") or ()) |
                    set(ws.get("dead") or ())) - {ctx.my_rank}
            pruned_ranks.intersection_update(gone)
            fresh = gone - pruned_ranks
            if fresh:
                reg.prune_ranks(fresh)
                pruned_ranks.update(fresh)
        # prune children for pools/tenants that disappeared since the
        # last scrape — the per-request pool gauges would otherwise
        # accumulate one frozen child-set per finished submission
        for fam, keys in list(owned.items()):
            for key in keys - seen.get(fam, set()):
                fam.remove_key(key)
        owned.clear()
        owned.update(seen)

    def uninstall() -> None:
        reg.unregister_collector(collect)
        _prune()

    reg.register_collector(collect)
    return uninstall


# ---------------------------------------------------------------------------
# HTTP listener (serving.metrics_port): /metrics + /statusz
# ---------------------------------------------------------------------------

class MetricsServer:
    """Stdlib HTTP listener serving the registry (daemon thread)."""

    def __init__(self, port: int, statusz_fn: Optional[Callable] = None,
                 host: str = "127.0.0.1"):
        import http.server

        reg = registry()
        statusz = statusz_fn or (lambda: {"metrics": reg.to_dict()})

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 — stdlib API
                if self.path.startswith("/metrics"):
                    body = reg.to_prometheus_text().encode()
                    ctype = "text/plain; version=0.0.4; charset=utf-8"
                elif self.path.startswith("/statusz"):
                    try:
                        body = json.dumps(statusz()).encode()
                    except Exception as exc:  # noqa: BLE001
                        body = json.dumps(
                            {"error": str(exc)[:200]}).encode()
                    ctype = "application/json"
                else:
                    self.send_response(404)
                    self.end_headers()
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):  # noqa: D102 — silence stderr
                pass

        self._httpd = http.server.ThreadingHTTPServer((host, port),
                                                      Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name="parsec-metrics-http",
                                        daemon=True)
        self._thread.start()

    def shutdown(self) -> None:
        try:
            self._httpd.shutdown()
            self._httpd.server_close()
        except OSError:
            pass
        self._thread.join(timeout=2.0)


def serve_http(port: int, statusz_fn: Optional[Callable] = None
               ) -> MetricsServer:
    """Start the /metrics + /statusz listener on ``port`` (0 = pick a
    free port; read it back from ``server.port``)."""
    return MetricsServer(port, statusz_fn)
