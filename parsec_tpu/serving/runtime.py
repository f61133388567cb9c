"""Serving runtime: tenants, admission control, deadlines, quarantine.

Design (ROADMAP item 4; reference divergence documented in PARITY —
PaRSEC's context is single-application, so everything here is
beyond-reference):

- **Tenants** are the isolation and accounting unit. Every submission
  binds a taskpool to a tenant; the taskpool carries the tenant's
  weight (``fair_weight``, read by the wfq scheduler), its name (read
  by the ``tenant`` PINS module) and a ``rank_scope`` so a peer death
  only fails pools whose scope contains the dead rank.
- **Admission** is a two-level window grown from the PR 3 DTD insertion
  throttle: inserts past the tenant's *soft* threshold park briefly
  (backpressure, event-driven wakeup on retire), and past the *hard*
  window — or past the backpressure timeout, or past the tenant's HBM
  reservation cap — raise :class:`AdmissionRejected` instead of parking
  unboundedly. Rejection is explicit so an open-loop client learns to
  back off; parking forever would just move the queue into the clients.
- **Deadlines**: ``submit(tp, deadline_s=...)`` registers the pool with
  a reaper thread; on expiry the pool is *cancelled* — queued tasks are
  dropped at select time, in-flight ones drain, the tenant's window and
  HBM reservations are released, and device-resident tiles of the
  pool's collections are swept from the HBM manager. Termination is
  idempotent (PR 6), so the cancelled pool's draining tasks cannot
  poison any other pool's termdet.
- **Quarantine**: a pool that fails for any non-cancellation reason
  (poison body, lint-gate :class:`~parsec_tpu.analysis.lint.
  HazardError` at registration, rank death aborting a scoped pool)
  quarantines its tenant — later submissions raise
  :class:`TenantQuarantined` until ``release_quarantine``. The failed
  pool's error is *owned* here (``Taskpool.error_owned``) so it never
  poisons an unrelated caller's ``Context.wait``.
- **Load shedding**: when the ready-queue depth or the measured
  per-task runtime overhead (PR 3 stage timers) crosses its watermark,
  new submissions from every tenant below the top live weight are
  rejected with ``AdmissionRejected("overload shed ...")`` — degrading
  by dropping the cheapest traffic instead of collapsing throughput for
  everyone.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Iterable, List, Optional, Union

from ..core.taskpool import CancelledError, Taskpool
from ..utils import mca_param
from ..utils.debug import debug_verbose, warning

mca_param.register("serving.tenant_window", 4096,
                   help="per-tenant HARD cap of in-flight inserted DTD "
                        "rows across the tenant's pools; inserts beyond "
                        "it raise AdmissionRejected")
mca_param.register("serving.tenant_backpressure", 0.5,
                   help="soft fraction of serving.tenant_window at which "
                        "inserts park (backpressure) before rejecting")
mca_param.register("serving.backpressure_timeout_s", 5.0,
                   help="max seconds an insert may park in tenant "
                        "backpressure before AdmissionRejected")
mca_param.register("serving.tenant_max_pools", 64,
                   help="per-tenant cap of concurrently live submissions")
mca_param.register("serving.tenant_hbm_mb", 0,
                   help="per-tenant HBM reservation cap for submissions "
                        "declaring hbm_bytes (0 = unlimited)")
mca_param.register("serving.shed_watermark", 0,
                   help="ready-queue depth above which new submissions "
                        "from below-top-weight tenants are shed "
                        "(0 = shedding off)")
mca_param.register("serving.shed_overhead_us", 0.0,
                   help="measured per-task runtime overhead (stage "
                        "timers: select+dispatch+release µs/task) above "
                        "which shedding also triggers (0 = off)")
mca_param.register("serving.deadline_poll_s", 0.02,
                   help="deadline reaper poll interval")
mca_param.register("serving.strict_fair", 1,
                   help="serving mode disables the bypass-slot chain so "
                        "every ready task goes through the weighted-fair "
                        "scheduler (0 keeps the throughput-path bypass)")


class AdmissionRejected(RuntimeError):
    """A submission or insert was refused by admission control (tenant
    window / HBM reservation / overload shed) — the caller should back
    off and retry, not treat this as a crash."""


class TenantQuarantined(AdmissionRejected):
    """The tenant is quarantined after a failure (poison body, lint
    gate, rank death); submissions are refused until
    ``ServingRuntime.release_quarantine``."""


class DeadlineExceeded(CancelledError):
    """A submission's deadline passed: its not-yet-running tasks were
    dropped, in-flight ones drained, and its reservations released."""


class Tenant:
    """One isolation/accounting unit sharing the persistent context."""

    def __init__(self, name: str, weight: float, window: int,
                 soft: int, max_pools: int, hbm_bytes: int):
        self.name = name
        self.weight = float(weight)
        self.window = int(window)          # hard in-flight row cap
        self.soft = int(soft)              # backpressure threshold
        self.max_pools = int(max_pools)
        self.hbm_bytes = int(hbm_bytes)    # reservation cap (0 = unlimited)
        self.cv = threading.Condition()
        self.inflight = 0                  # admitted-not-retired rows
        self.hbm_reserved = 0
        self.quarantined: Optional[BaseException] = None
        self.active: Dict[Taskpool, "Submission"] = {}
        self._waiters = 0
        self.stats = {"submitted": 0, "completed": 0, "failed": 0,
                      "rejected": 0, "shed": 0, "cancelled": 0,
                      "rows_admitted": 0, "rows_retired": 0,
                      "parked": 0}

    def __repr__(self) -> str:
        return (f"<Tenant {self.name} w={self.weight} "
                f"inflight={self.inflight}"
                f"{' QUARANTINED' if self.quarantined else ''}>")


class _PoolAdmission:
    """Per-(tenant, taskpool) window bookkeeping installed as the DTD
    pool's ``admission``/``on_retire`` hooks. ``close()`` releases the
    residue of admitted-but-never-retired rows when the pool ends (a
    cancelled pool's dropped tasks never retire), after which late
    retires from draining tasks are ignored — the window can neither
    leak nor double-release."""

    __slots__ = ("runtime", "tenant", "admitted", "retired", "closed")

    def __init__(self, runtime: "ServingRuntime", tenant: Tenant):
        self.runtime = runtime
        self.tenant = tenant
        self.admitted = 0
        self.retired = 0
        self.closed = False

    def admit(self, tp: Taskpool, n: int) -> None:
        ten = self.tenant
        timeout = float(mca_param.get("serving.backpressure_timeout_s",
                                      5.0))
        deadline = time.monotonic() + timeout
        park_t0 = None        # perf_counter stamp of the first park
        with ten.cv:
            while True:
                if ten.quarantined is not None:
                    ten.stats["rejected"] += 1
                    raise TenantQuarantined(
                        f"tenant {ten.name} is quarantined: "
                        f"{ten.quarantined}")
                if tp.error is not None:
                    raise RuntimeError(
                        f"taskpool {tp.name} aborted: {tp.error}") \
                        from tp.error
                if ten.inflight + n > ten.window:
                    # hard window: explicit rejection, never unbounded
                    # parking (the client is open-loop — parking forever
                    # just moves its queue into this thread)
                    ten.stats["rejected"] += 1
                    raise AdmissionRejected(
                        f"tenant {ten.name}: queue depth "
                        f"{ten.inflight}+{n} exceeds window "
                        f"{ten.window} (serving.tenant_window)")
                if ten.inflight <= ten.soft:
                    # backpressure keys on the EXISTING depth: a batch
                    # that fits the hard window admits even when it
                    # alone exceeds the soft threshold — an idle tenant
                    # has nothing in flight to retire, so parking such a
                    # batch could only ever exit via the timeout
                    break
                # soft window: backpressure park, bounded
                left = deadline - time.monotonic()
                if left <= 0:
                    ten.stats["rejected"] += 1
                    raise AdmissionRejected(
                        f"tenant {ten.name}: backpressure park exceeded "
                        f"{timeout:.1f}s "
                        f"(serving.backpressure_timeout_s) at depth "
                        f"{ten.inflight}")
                if park_t0 is None:
                    park_t0 = time.perf_counter()
                ten._waiters += 1
                try:
                    ten.cv.wait(min(left, 0.25))
                finally:
                    ten._waiters -= 1
            ten.inflight += n
            self.admitted += n
            ten.stats["rows_admitted"] += n
            inflight_now = ten.inflight
            if park_t0 is not None:
                # admission-park counter: one of the autoscaler's
                # scale-up signals (serving/elastic.py) — parks piling
                # up mean the tenant windows are the bottleneck
                ten.stats["parked"] += 1
        tr = self.runtime.ctx.trace
        if tr is not None:
            # admission protocol event (analysis/conformance.py replays
            # these through the admission_budget model): rows admitted,
            # depth after, and the window the decision was made against
            tr.event("admission", "admit", object_id=tp.name,
                     info={"tenant": ten.name, "rows": n,
                           "inflight": inflight_now,
                           "window": ten.window, "soft": ten.soft})
        if park_t0 is not None:
            self.runtime._bump("parked")
            self._record_park(tp, ten, park_t0, n)

    def _record_park(self, tp: Taskpool, ten: Tenant,
                     park_t0: float, n: int) -> None:
        """Record a backpressure park as an ``admission`` span of the
        request's trace (only actual waits — an unthrottled admit adds
        zero events). Recorded after the fact with explicit times."""
        tr = self.runtime.ctx.trace
        rid = getattr(tp, "trace_rid", None)
        if tr is None or rid is None:
            return
        from ..profiling import spans as spans_mod
        sid = spans_mod.next_span_id(self.runtime.ctx.my_rank)
        now = time.perf_counter()
        info = {"rid": rid, "span": sid,
                "parent": getattr(tp, "root_span", None),
                "tenant": ten.name, "rows": n}
        tr.event("admission", "begin", t=park_t0 - tr.t0,
                 object_id=tp.name, info=info)
        tr.event("admission", "end", t=now - tr.t0,
                 object_id=tp.name, info=info)

    def on_retire(self, _tp: Taskpool) -> None:
        ten = self.tenant
        with ten.cv:
            if self.closed:
                return          # residue already reconciled by close()
            self.retired += 1
            ten.inflight -= 1
            ten.stats["rows_retired"] += 1
            inflight_now = ten.inflight
            if ten._waiters:
                ten.cv.notify_all()
        tr = self.runtime.ctx.trace
        if tr is not None:
            tr.event("admission", "retire", object_id=_tp.name,
                     info={"tenant": ten.name, "rows": 1,
                           "inflight": inflight_now})

    def close(self) -> None:
        ten = self.tenant
        with ten.cv:
            if self.closed:
                return
            self.closed = True
            residue = self.admitted - self.retired
            if residue > 0:
                ten.inflight -= residue
            inflight_now = ten.inflight
            ten.cv.notify_all()
        if residue > 0:
            tr = self.runtime.ctx.trace
            if tr is not None:
                # end-of-pool residue reconciliation (cancelled pools'
                # dropped tasks never retire) — replayed as a bulk
                # retire by the conformance pass
                tr.event("admission", "reconcile", object_id="close",
                         info={"tenant": ten.name, "rows": residue,
                               "inflight": inflight_now})


class Submission:
    """Handle for one submitted taskpool (returned by Context.submit)."""

    def __init__(self, runtime: "ServingRuntime", tp: Taskpool,
                 tenant: Tenant, deadline_s: Optional[float],
                 hbm_bytes: int):
        self.runtime = runtime
        self.tp = tp
        self.tenant = tenant
        self.submitted_t = time.monotonic()
        self.deadline_t = (self.submitted_t + deadline_s
                           if deadline_s is not None else None)
        self.finished_t: Optional[float] = None
        self.hbm_bytes = int(hbm_bytes)

    @property
    def done(self) -> bool:
        return self.tp.completed

    @property
    def error(self) -> Optional[BaseException]:
        return self.tp.error

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the pool terminates. Raises the pool's error —
        :class:`DeadlineExceeded`/:class:`~parsec_tpu.core.taskpool.
        CancelledError` for cancellations, the original failure
        otherwise. Returns False on wait timeout."""
        ok = self.tp._complete_evt.wait(timeout)
        err = self.tp.error
        if err is not None:
            if isinstance(err, (CancelledError, AdmissionRejected)):
                raise err
            raise RuntimeError(
                f"taskpool {self.tp.name} aborted: {err}") from err
        return ok

    def cancel(self, exc: Optional[BaseException] = None) -> bool:
        """Cancel this submission (idempotent): drop queued tasks, drain
        in-flight ones, release the tenant's window/HBM reservations and
        sweep its device-resident tiles. True when this call performed
        the cancellation."""
        return self.runtime._cancel(self, exc)

    def latency_s(self) -> Optional[float]:
        return (self.finished_t - self.submitted_t
                if self.finished_t is not None else None)


class ServingRuntime:
    """Multi-tenant serving supervisor attached to one Context."""

    def __init__(self, context, strict_fair: Optional[bool] = None):
        self.ctx = context
        context.serving = self
        self._lock = threading.RLock()
        self._tenants: Dict[str, Tenant] = {}
        self._deadlines: List[Submission] = []
        self._reaper: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.stats = {"submitted": 0, "completed": 0, "failed": 0,
                      "rejected": 0, "shed": 0, "quarantined": 0,
                      "cancelled": 0, "deadline_cancelled": 0,
                      "parked": 0}
        # elastic-capacity controller (serving/elastic.py) — attached
        # by ElasticController so statusz/metrics can surface the
        # autoscaler's state next to the tenant report
        self.elastic = None
        self._stats_lock = threading.Lock()
        if strict_fair is None:
            strict_fair = str(mca_param.get(
                "serving.strict_fair", 1)).lower() not in ("0", "off",
                                                           "false")
        if strict_fair:
            # every ready task goes through the scheduler so wfq's
            # weighted-fair arbitration actually sees it (the bypass
            # slot would hand a tenant's successor straight to the
            # worker, starving the arbitration)
            context._bypass_chain = False
        # always-on per-tenant request-latency distribution
        # (profiling/metrics.py): observed once per finished
        # submission, exported as a log2-bucket Prometheus histogram
        from ..profiling import metrics as metrics_mod
        self._m_latency = metrics_mod.registry().histogram(
            "parsec_request_latency_seconds",
            "submission latency (submit -> pool termination) per "
            "tenant", ("tenant",)) if metrics_mod.enabled() else None

    # ------------------------------------------------------------ tenants
    def tenant(self, name: str, weight: float = 1.0,
               window: Optional[int] = None,
               max_pools: Optional[int] = None,
               hbm_bytes: Optional[int] = None) -> Tenant:
        """Get-or-create the named tenant (idempotent; parameters only
        apply at creation)."""
        with self._lock:
            ten = self._tenants.get(name)
            if ten is None:
                window = int(window if window is not None else
                             mca_param.get("serving.tenant_window", 4096))
                frac = float(mca_param.get("serving.tenant_backpressure",
                                           0.5))
                soft = max(1, int(window * min(max(frac, 0.0), 1.0)))
                ten = Tenant(
                    name, weight, window, soft,
                    max_pools if max_pools is not None else
                    int(mca_param.get("serving.tenant_max_pools", 64)),
                    hbm_bytes if hbm_bytes is not None else
                    int(mca_param.get("serving.tenant_hbm_mb", 0))
                    * (1 << 20))
                self._tenants[name] = ten
            return ten

    def tenants(self) -> Dict[str, Tenant]:
        with self._lock:
            return dict(self._tenants)

    def release_quarantine(self, tenant: Union[str, Tenant]) -> None:
        ten = self.tenant(tenant) if isinstance(tenant, str) else tenant
        with ten.cv:
            ten.quarantined = None
            ten.cv.notify_all()

    def _bump(self, key: str) -> None:
        """Locked runtime-counter increment: submit paths run on many
        client threads, and a bare dict += is a read-modify-write that
        drops counts under preemption — these totals are the shedding/
        quarantine evidence ``report()`` and PARITY give."""
        with self._stats_lock:
            self.stats[key] += 1

    def _quarantine(self, ten: Tenant, exc: BaseException) -> None:
        with ten.cv:
            first = ten.quarantined is None
            if first:
                ten.quarantined = exc
            ten.cv.notify_all()
        if first:
            self._bump("quarantined")
            warning("serving", "tenant %s quarantined: %s", ten.name, exc)

    # ----------------------------------------------------------- overload
    def _overload_reason(self) -> Optional[str]:
        wm = int(mca_param.get("serving.shed_watermark", 0))
        if wm > 0:
            depth = self.ctx.scheduler.pending_tasks()
            if depth > wm:
                return (f"ready-queue depth {depth} > watermark {wm} "
                        "(serving.shed_watermark)")
        ov = float(mca_param.get("serving.shed_overhead_us", 0.0))
        # what was asked for, not ctx.stage_timers: a live profiler
        # session turns the timers on too, and must not start shedding
        if ov > 0 and self.ctx.stage_timers_asked:
            total_s = executed = 0
            for es in self.ctx.streams:
                total_s += (es.stats.get("select_s", 0.0) +
                            es.stats.get("dispatch_s", 0.0) +
                            es.stats.get("release_s", 0.0))
                executed += es.stats.get("executed", 0)
            if executed:
                per_us = total_s / executed * 1e6
                if per_us > ov:
                    return (f"runtime overhead {per_us:.1f} µs/task > "
                            f"budget {ov:.1f} (serving.shed_overhead_us)")
        return None

    def _top_live_weight(self) -> float:
        with self._lock:
            live = [t.weight for t in self._tenants.values()
                    if t.quarantined is None]
        return max(live) if live else 0.0

    # ------------------------------------------------------------- submit
    def submit(self, tp: Taskpool, tenant=None,
               deadline_s: Optional[float] = None,
               weight: Optional[float] = None,
               rank_scope=None, hbm_bytes: int = 0) -> Submission:
        ten = tenant if isinstance(tenant, Tenant) else \
            self.tenant(tenant or "default",
                        weight=weight if weight is not None else 1.0)
        if ten.quarantined is not None:
            ten.stats["rejected"] += 1
            self._bump("rejected")
            raise TenantQuarantined(
                f"tenant {ten.name} is quarantined: {ten.quarantined}")
        reason = self._overload_reason()
        if reason is not None and ten.weight < self._top_live_weight():
            # graceful degradation: shed the lowest-weight NEW traffic
            # instead of letting queue growth collapse everyone's p99
            ten.stats["shed"] += 1
            self._bump("shed")
            raise AdmissionRejected(
                f"overload shed (tenant {ten.name}, weight "
                f"{ten.weight:g} < top {self._top_live_weight():g}): "
                f"{reason}")
        scope = self._resolve_scope(rank_scope)   # may raise: validate
        #                                           BEFORE reserving
        sub = Submission(self, tp, ten, deadline_s, hbm_bytes)
        with ten.cv:
            # check AND reserve in ONE critical section: concurrent
            # client threads racing this cap must not both observe the
            # pre-reservation count (the many-callers shape is the
            # whole point of the runtime)
            if len(ten.active) >= ten.max_pools:
                ten.stats["rejected"] += 1
                self._bump("rejected")
                raise AdmissionRejected(
                    f"tenant {ten.name}: {len(ten.active)} live "
                    f"submissions >= cap {ten.max_pools} "
                    "(serving.tenant_max_pools)")
            if ten.hbm_bytes and \
                    ten.hbm_reserved + hbm_bytes > ten.hbm_bytes:
                ten.stats["rejected"] += 1
                self._bump("rejected")
                raise AdmissionRejected(
                    f"tenant {ten.name}: HBM reservation "
                    f"{ten.hbm_reserved + hbm_bytes} exceeds cap "
                    f"{ten.hbm_bytes} (serving.tenant_hbm_mb)")
            ten.hbm_reserved += hbm_bytes
            ten.active[tp] = sub

        # pool attributes are written only AFTER every admission check
        # passed: a rejected taskpool leaves submit() untouched, so a
        # caller falling back to plain add_taskpool doesn't inherit a
        # serving-scoped rank_scope or an error_owned flag that would
        # hide its failures from Context.wait
        tp.tenant_name = ten.name
        tp.fair_weight = weight if weight is not None else ten.weight
        tp.rank_scope = scope
        tp.error_owned = True
        # request-scoped distributed tracing (profiling/spans.py): the
        # rid derives from the taskpool NAME (the cross-rank registry
        # identity), so every rank of a distributed submission mints
        # the SAME rid without any exchange — one span tree spans the
        # mesh; the root span parents startup tasks and admission parks
        from ..profiling import spans as spans_mod
        if getattr(tp, "trace_rid", None) is None:
            tp.trace_rid = spans_mod.mint_rid(tp.name)
        tp.root_span = f"{tp.trace_rid}#root{self.ctx.my_rank}"
        tr = self.ctx.trace
        if tr is not None:
            tr.event("req", "begin", object_id=tp.trace_rid,
                     info={"rid": tp.trace_rid, "span": tp.root_span,
                           "parent": None, "tenant": ten.name})
        adm = None
        if hasattr(tp, "insert_task") and hasattr(tp, "admission"):
            adm = _PoolAdmission(self, ten)
            tp.admission = adm
            tp.on_retire = adm.on_retire
        prev_on_complete = tp.on_complete
        tp.on_complete = lambda pool, _sub=sub, _prev=prev_on_complete: \
            self._pool_finished(_sub, _prev)
        try:
            self.ctx.add_taskpool(tp)
        except Exception as exc:
            # the registration-time lint gate fired (analysis.lint=error
            # HazardError) or registration failed outright: charge the
            # TENANT, release what we reserved, leave everyone else
            # untouched
            with ten.cv:
                ten.active.pop(tp, None)
                ten.hbm_reserved -= hbm_bytes
            if adm is not None:
                adm.close()
            ten.stats["failed"] += 1
            self._bump("failed")
            self._quarantine(ten, exc)
            raise
        with ten.cv:
            ten.stats["submitted"] += 1
        self._bump("submitted")
        if sub.deadline_t is not None:
            with self._lock:
                self._deadlines.append(sub)
                self._ensure_reaper()
        debug_verbose(3, "serving", "submitted %s for tenant %s "
                      "(weight %g, deadline %s)", tp.name, ten.name,
                      tp.fair_weight, deadline_s)
        return sub

    def _resolve_scope(self, rank_scope) -> Optional[frozenset]:
        """Serving submissions default to a LOCAL failure scope: only
        this rank's death can fail them, so one tenant's dead rank
        cannot cascade into every tenant's pools. Pass ``"all"`` (or
        None explicitly via a distributed submission's iterable of
        ranks) for pools that genuinely span the mesh."""
        if rank_scope == "all":
            return None
        if rank_scope is None:
            return frozenset({self.ctx.my_rank})
        if isinstance(rank_scope, Iterable):
            return frozenset(int(r) for r in rank_scope)
        raise ValueError(f"rank_scope {rank_scope!r}: expected 'all', "
                         "None, or an iterable of ranks")

    # ----------------------------------------------------- pool lifecycle
    def _pool_finished(self, sub: Submission, prev_on_complete) -> None:
        """Taskpool on_complete hook (fires inside _on_terminated,
        before the context removes the pool): reconcile accounting,
        quarantine on failure, hand off to any user hook."""
        tp = sub.tp
        ten = sub.tenant
        sub.finished_t = time.monotonic()
        if self._m_latency is not None:
            self._m_latency.labels(tenant=ten.name).observe(
                sub.finished_t - sub.submitted_t)
        tr = self.ctx.trace
        rid = getattr(tp, "trace_rid", None)
        if tr is not None and rid is not None:
            tr.event("req", "end", object_id=rid,
                     info={"rid": rid, "span": tp.root_span,
                           "error": (str(tp.error)[:120]
                                     if tp.error else None)})
        adm = getattr(tp, "admission", None)
        if isinstance(adm, _PoolAdmission):
            adm.close()
        with ten.cv:
            ten.active.pop(tp, None)
            ten.hbm_reserved -= sub.hbm_bytes
        err = tp.error
        if err is None:
            ten.stats["completed"] += 1
            self._bump("completed")
        elif isinstance(err, CancelledError):
            ten.stats["cancelled"] += 1
            self._bump("cancelled")
            if isinstance(err, DeadlineExceeded):
                self._bump("deadline_cancelled")
        else:
            # poison body / rank death: per-taskpool failure unit — the
            # tenant is quarantined, survivors keep serving
            ten.stats["failed"] += 1
            self._bump("failed")
            self._quarantine(ten, err)
        with self._lock:
            if sub in self._deadlines:
                self._deadlines.remove(sub)
        if prev_on_complete is not None:
            prev_on_complete(tp)

    def _release_tiles(self, tp: Taskpool) -> int:
        """Sweep the HBM manager's entries for the pool's collections —
        a cancelled tenant's device-resident KV/working tiles must not
        squat in the budget."""
        hbm = self.ctx.hbm
        if hbm is None:
            return 0
        dc_ids = set()
        tiles = getattr(tp, "tiles", None)       # DTD tile bank
        if tiles is not None:
            for t in tiles.all():
                dc_ids.add(id(t.collection))
        g = getattr(tp, "g", None)               # PTG globals
        for obj in vars(g).values() if g is not None else ():
            if hasattr(obj, "data_of") and hasattr(obj, "write_tile"):
                dc_ids.add(id(obj))
        if not dc_ids:
            return 0
        return hbm.sweep(lambda k, e: isinstance(k, tuple) and k
                         and k[0] in dc_ids)

    def _cancel(self, sub: Submission,
                exc: Optional[BaseException] = None) -> bool:
        tp = sub.tp
        if tp.completed or tp.cancelled:
            return False
        tp.cancel(exc if exc is not None else CancelledError(
            f"submission {tp.name} cancelled"))
        self._release_tiles(tp)
        return True

    # ------------------------------------------------------------- reaper
    def _ensure_reaper(self) -> None:
        if self._reaper is None or not self._reaper.is_alive():
            t = threading.Thread(target=self._reaper_main,
                                 name="parsec-serving-reaper",
                                 daemon=True)
            self._reaper = t
            t.start()

    def _reaper_main(self) -> None:
        poll = float(mca_param.get("serving.deadline_poll_s", 0.02))
        while not self._stop.is_set():
            now = time.monotonic()
            with self._lock:
                due = [s for s in self._deadlines
                       if s.deadline_t is not None and s.deadline_t <= now]
            for sub in due:
                age = now - sub.submitted_t
                self._cancel(sub, DeadlineExceeded(
                    f"submission {sub.tp.name} (tenant "
                    f"{sub.tenant.name}) exceeded its deadline "
                    f"({age:.3f}s elapsed)"))
                with self._lock:
                    if sub in self._deadlines:
                        self._deadlines.remove(sub)
            self._stop.wait(poll)

    def shutdown(self) -> None:
        self._stop.set()
        t = self._reaper
        if t is not None:
            t.join(timeout=2.0)

    # ------------------------------------------------------ observability
    def report(self) -> Dict:
        """Aggregate serving stats + per-tenant rows + (when wfq is
        installed) the scheduler's per-pool service accounting."""
        out = {"stats": dict(self.stats), "tenants": {}}
        for name, ten in self.tenants().items():
            out["tenants"][name] = {
                "weight": ten.weight, "inflight": ten.inflight,
                "hbm_reserved": ten.hbm_reserved,
                "quarantined": (str(ten.quarantined)
                                if ten.quarantined else None),
                **ten.stats}
        sched = self.ctx.scheduler
        if hasattr(sched, "pool_stats"):
            out["pools"] = sched.pool_stats()
        if self.elastic is not None:
            out["elastic"] = self.elastic.status()
        kvl = getattr(self.ctx, "kv_state", None)
        if kvl is not None:
            out["kv"] = kvl.snapshot()
        return out


def enable(context, strict_fair: Optional[bool] = None) -> ServingRuntime:
    """Attach a serving runtime to ``context`` (idempotent) and return
    it. For weighted-fair arbitration build the context with
    ``scheduler="wfq"`` (or ``--mca sched wfq``)."""
    if context.serving is not None:
        return context.serving
    return ServingRuntime(context, strict_fair=strict_fair)
