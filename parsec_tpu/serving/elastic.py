"""Elastic capacity: autoscale, drain, and rebalance the serving mesh.

PaRSEC treats the rank set as fixed for the life of the context
(parsec_init → parsec_fini over one MPI world); this reproduction's
PR 6 already rejoins a dead rank and PR 8 sheds load, but nothing
closed the control loop. This module is the policy-driven elasticity
subsystem a production serving runtime needs (ROADMAP item 4) — wired
from parts that already exist:

- **Signals** come from the PR 9 metrics plane and the serving runtime:
  ready-queue/backlog depth (per-rank heartbeats over a dedicated
  ``AMTag.ELASTIC`` channel), admission parks/rejections and the shed
  counter (``ServingRuntime.stats``), and p99-vs-deadline headroom
  (a rolling completion-latency window).
- **Policy** (:class:`AutoscalePolicy`): signals → desired serving-rank
  count, with hysteresis (separate up/down thresholds + consecutive
  idle rounds before a shrink) and a cooldown between acts so the
  controller cannot flap. ``serving.autoscale = off | advise | act``:
  ``advise`` computes and records decisions without executing them.
- **Scale-up** rides the PR 6 rejoin path extended to FRESH ranks:
  the controller picks the next slot (reusing drained slots first so
  the world stays dense), asks the harness to spawn it
  (``spawn_rank`` callback), and the socket engine admits it beyond
  the original world size (``comm.elastic``) — peer tables, termdet
  waves, barriers and recovery allgathers all run over the enlarged
  live set. A joiner stalled past ``comm.rejoin_timeout`` (e.g. the
  ``slowjoin`` fault injection) is ABANDONED cleanly: the decision is
  recorded failed and the loop keeps running.
- **Scale-down** is quiesce → checkpoint-cut → drain: the victim's
  tenants are migrated off first (each shard travels through the PR 6
  checkpoint vehicle: owner saves a single-rank step, adopter
  restores it), then the victim receives ``drain``, finishes its
  in-flight work, acks, and leaves with an orderly BYE — peers record
  it DEPARTED, never dead: no failure path, no quarantine, no abort
  sweep.
- **Tenant migration** (:meth:`ElasticController.migrate_tenant`) is
  also exposed directly for hot-spot isolation: routing for the tenant
  pauses, the shard moves, routing resumes — the pause window is the
  ``migration_pause`` the controller records.

The module is workload-agnostic: the request/serving integration
(what a "tenant" actually runs — e.g. the continuous-batching decode
engine) plugs in through :class:`ElasticWorker` callbacks and the
controller's routing-pause hooks. ``tests/test_elastic.py`` grows a
live mesh 2 -> 4 ranks, migrates a tenant and drains back under traffic.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from ..comm.engine import AMTag
from ..utils import mca_param
from ..utils.debug import debug_verbose, warning
from ..utils.stats import pctl as _pctl

mca_param.register("serving.autoscale", "off",
                   help="elastic-capacity autoscaler mode: off | "
                        "advise (compute + record decisions, never "
                        "act) | act (execute scale-up/down/rebalance)",
                   choices=("off", "advise", "act"))
mca_param.register("serving.autoscale_poll_s", 0.25,
                   help="autoscaler control-loop poll interval")
mca_param.register("serving.autoscale_cooldown_s", 2.0,
                   help="minimum seconds between autoscaler ACTS (a "
                        "decision inside the cooldown is recorded but "
                        "holds the current count — anti-flap)")
mca_param.register("serving.autoscale_min_ranks", 1,
                   help="lower bound of the serving-rank count (the "
                        "controller rank is not a serving rank)")
mca_param.register("serving.autoscale_max_ranks", 0,
                   help="upper bound of the serving-rank count "
                        "(0 = unbounded; the spawn callback may still "
                        "refuse)")
mca_param.register("serving.autoscale_up_backlog", 8.0,
                   help="scale up when the per-serving-rank backlog "
                        "(queued + in-flight requests) exceeds this")
mca_param.register("serving.autoscale_down_backlog", 1.0,
                   help="a poll with per-rank backlog below this "
                        "counts toward the idle-rounds shrink trigger")
mca_param.register("serving.autoscale_idle_rounds", 4,
                   help="consecutive below-down-backlog polls before "
                        "the policy proposes a scale-down (hysteresis)")
mca_param.register("serving.autoscale_headroom", 0.8,
                   help="scale up when the rolling p99 latency exceeds "
                        "this fraction of the request deadline (only "
                        "when a deadline is configured)")
mca_param.register("serving.drain_timeout_s", 30.0,
                   help="seconds the controller waits for a victim "
                        "rank's drained ack before recording the "
                        "scale-down failed")
mca_param.register("serving.migrate_timeout_s", 30.0,
                   help="seconds the controller waits for each tenant "
                        "migration leg (drop / adopt ack)")


# ---------------------------------------------------------------------------
# signals + policy
# ---------------------------------------------------------------------------

@dataclass
class Signals:
    """One control-loop observation (everything the policy reads)."""
    serving_ranks: int = 0
    backlog: float = 0.0             # queued + in-flight requests, mesh-wide
    per_rank: Dict[int, float] = field(default_factory=dict)
    parks: int = 0                   # cumulative admission parks
    rejections: int = 0              # cumulative admission rejections
    shed: int = 0                    # cumulative overload sheds
    p99_s: Optional[float] = None    # rolling completion p99
    deadline_s: Optional[float] = None


class AutoscalePolicy:
    """Signals → desired serving-rank count, with hysteresis + cooldown.

    Scale-up fires on ANY pressure signal: per-rank backlog over
    ``serving.autoscale_up_backlog``, new admission parks/rejections or
    sheds since the last poll, or rolling p99 past
    ``serving.autoscale_headroom`` × the deadline. Scale-down needs
    ``serving.autoscale_idle_rounds`` CONSECUTIVE polls under
    ``serving.autoscale_down_backlog`` per rank — one busy poll resets
    the streak. Acts are separated by ``serving.autoscale_cooldown_s``;
    a decision landing inside the cooldown holds the current count with
    reason ``"cooldown"`` (recorded, not acted)."""

    def __init__(self, min_ranks: Optional[int] = None,
                 max_ranks: Optional[int] = None,
                 up_backlog: Optional[float] = None,
                 down_backlog: Optional[float] = None,
                 idle_rounds: Optional[int] = None,
                 cooldown_s: Optional[float] = None,
                 headroom: Optional[float] = None):
        g = mca_param.get
        self.min_ranks = int(min_ranks if min_ranks is not None
                             else g("serving.autoscale_min_ranks", 1))
        self.max_ranks = int(max_ranks if max_ranks is not None
                             else g("serving.autoscale_max_ranks", 0))
        self.up_backlog = float(
            up_backlog if up_backlog is not None
            else g("serving.autoscale_up_backlog", 8.0))
        self.down_backlog = float(
            down_backlog if down_backlog is not None
            else g("serving.autoscale_down_backlog", 1.0))
        self.idle_rounds = int(
            idle_rounds if idle_rounds is not None
            else g("serving.autoscale_idle_rounds", 4))
        self.cooldown_s = float(
            cooldown_s if cooldown_s is not None
            else g("serving.autoscale_cooldown_s", 2.0))
        self.headroom = float(headroom if headroom is not None
                              else g("serving.autoscale_headroom", 0.8))
        self._idle_streak = 0
        self._last_act_t: Optional[float] = None
        # None until the first observation: the runtime's counters are
        # cumulative since process start, so the first poll must
        # BASELINE them, not read the historical total as a one-poll
        # delta (which would fire a spurious scale-up on attach)
        self._last_parks: Optional[int] = None
        self._last_rejections: Optional[int] = None
        self._last_shed: Optional[int] = None

    def note_act(self, now: float) -> None:
        """The controller EXECUTED a decision — start the cooldown."""
        self._last_act_t = now
        self._idle_streak = 0

    def cooldown_remaining(self, now: float) -> float:
        if self._last_act_t is None:
            return 0.0
        return max(0.0, self._last_act_t + self.cooldown_s - now)

    def _up_reason(self, sig: Signals) -> Optional[str]:
        n = max(sig.serving_ranks, 1)
        per = sig.backlog / n
        if per > self.up_backlog:
            return (f"backlog {per:.1f}/rank > "
                    f"{self.up_backlog:g} (serving.autoscale_up_backlog)")
        if self._last_parks is not None:
            d_park = sig.parks - self._last_parks
            d_rej = sig.rejections - self._last_rejections
            d_shed = sig.shed - self._last_shed
            if d_park > 0 or d_rej > 0:
                return (f"admission pressure (+{d_park} parks, "
                        f"+{d_rej} rejections since last poll)")
            if d_shed > 0:
                return f"load shedding fired (+{d_shed})"
        if sig.p99_s is not None and sig.deadline_s:
            if sig.p99_s > self.headroom * sig.deadline_s:
                return (f"p99 {sig.p99_s * 1e3:.1f}ms > "
                        f"{self.headroom:g}x deadline "
                        f"{sig.deadline_s * 1e3:.0f}ms "
                        "(serving.autoscale_headroom)")
        return None

    def decide(self, sig: Signals, now: float) -> Tuple[int, str]:
        """Returns ``(desired_serving_ranks, reason)``. Counter deltas
        (parks/rejections/shed) are consumed even during cooldown so a
        burst inside the cooldown doesn't double-fire after it."""
        n = sig.serving_ranks
        up = self._up_reason(sig)
        self._last_parks = sig.parks
        self._last_rejections = sig.rejections
        self._last_shed = sig.shed
        if self.cooldown_remaining(now) > 0:
            # hysteresis state still advances during cooldown, so an
            # idle mesh doesn't need idle_rounds MORE polls after it
            if up is None and n > 0 and \
                    sig.backlog / max(n, 1) < self.down_backlog:
                self._idle_streak += 1
            return n, "cooldown"
        if up is not None:
            cap = self.max_ranks if self.max_ranks > 0 else n + 1
            if n < cap:
                self._idle_streak = 0
                return n + 1, up
            self._idle_streak = 0
            return n, f"at max_ranks {cap}: {up}"
        if n > 0 and sig.backlog / max(n, 1) < self.down_backlog:
            self._idle_streak += 1
            if self._idle_streak >= self.idle_rounds and \
                    n > self.min_ranks:
                self._idle_streak = 0
                return n - 1, (f"idle {self.idle_rounds} rounds "
                               f"(backlog {sig.backlog:g} < "
                               f"{self.down_backlog:g}/rank)")
        else:
            self._idle_streak = 0
        return n, "steady"


# ---------------------------------------------------------------------------
# AM channel (AMTag.ELASTIC): op-keyed dispatch shared by both roles
# ---------------------------------------------------------------------------

class _ElasticChannel:
    """Op-dispatching wrapper of ``AMTag.ELASTIC``. ONE handler per
    engine (controller and worker roles register their ops into it);
    handlers run on the comm thread and must not block — both roles
    only enqueue/flag and do the real work on their own threads."""

    def __init__(self, comm):
        self.comm = comm
        self._handlers: Dict[str, Callable[[int, Dict], None]] = {}
        existing = getattr(comm, "_elastic_channel", None)
        if existing is not None:
            # same-process controller+worker (loopback tests): share
            self._handlers = existing._handlers
        else:
            comm.tag_register(AMTag.ELASTIC, self._dispatch)
            comm._elastic_channel = self

    def on(self, op: str, fn: Callable[[int, Dict], None]) -> None:
        self._handlers[op] = fn

    def send(self, dst: int, op: str, **kw) -> None:
        msg = {"op": op}
        msg.update(kw)
        self.comm.send_am(AMTag.ELASTIC, dst, msg)

    def _dispatch(self, src: int, msg: Dict) -> None:
        fn = self._handlers.get(msg.get("op"))
        if fn is None:
            warning("elastic", "no handler for elastic op %r from %d",
                    msg.get("op"), src)
            return
        fn(src, msg)


# ---------------------------------------------------------------------------
# controller
# ---------------------------------------------------------------------------

class ElasticController:
    """The autoscaler control loop (runs on the front-end rank).

    ``spawn_rank(rank, world, live_peers)`` is the harness-provided
    launcher of a fresh rank process; ``tenants`` seeds the placement
    (tenant → serving rank, round-robin). Attach routing hooks with
    :meth:`set_router` so migrations can pause/resume a tenant's
    traffic, and feed completions through :meth:`record_latency` for
    the p99-headroom signal. ``runtime`` (a ``ServingRuntime``) is
    optional — when given, its park/reject/shed counters become policy
    signals and ``statusz``/``report`` surface :meth:`status`."""

    def __init__(self, ctx, runtime=None,
                 spawn_rank: Optional[Callable] = None,
                 tenants=(), policy: Optional[AutoscalePolicy] = None,
                 mode: Optional[str] = None,
                 deadline_s: Optional[float] = None):
        self.ctx = ctx
        self.comm = ctx.comm
        if self.comm is None:
            raise ValueError("ElasticController needs a comm engine "
                             "(the mesh it scales)")
        self.runtime = runtime
        if runtime is not None:
            runtime.elastic = self
        self.spawn_rank = spawn_rank
        self.policy = policy or AutoscalePolicy()
        self.mode = (mode if mode is not None else
                     str(mca_param.get("serving.autoscale",
                                       "off"))).lower()
        self.deadline_s = deadline_s
        live = [r for r in self.comm.world_status()["live"]
                if r != ctx.my_rank]
        self.serving_ranks: List[int] = sorted(live)
        self.placement: Dict[str, int] = {}
        # last checkpoint step holding each tenant's shard: the adopt
        # source for a tenant whose placement is None (either never
        # placed, or a migration's drop leg succeeded and its adopt
        # leg failed — the shard sits durable in the step, not lost)
        self.shard_steps: Dict[str, Optional[int]] = {}
        self._place(tenants)
        self.draining: set = set()
        self.desired = len(self.serving_ranks)
        self.last_decision: Optional[Dict] = None
        self.decisions: List[Dict] = []      # ACTED scale ops (full log)
        self.advisories: List[Dict] = []     # notable non-acted (last 32)
        self.failed_joins = 0
        self.migration_pauses_ms: List[float] = []
        self._hb: Dict[int, Dict] = {}
        self._hb_lock = threading.Lock()
        self._lat: deque = deque(maxlen=512)
        self._outstanding_fn: Optional[Callable[[], Dict[int, float]]] \
            = None
        self._pause_fn: Optional[Callable[[str], None]] = None
        self._resume_fn: Optional[Callable[[str], None]] = None
        self._acks: Dict[int, List] = {}       # token -> [Event, payload]
        self._ack_lock = threading.Lock()
        self._token = itertools.count(1)
        self._step = itertools.count(1)        # migration ckpt steps
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.channel = _ElasticChannel(self.comm)
        self.channel.on("stats", self._on_stats)
        self.channel.on("ack", self._on_ack)

    # ------------------------------------------------------------ wiring
    def _place(self, tenants) -> None:
        for i, t in enumerate(sorted(tenants)):
            if self.serving_ranks:
                self.placement[t] = self.serving_ranks[
                    i % len(self.serving_ranks)]

    def set_router(self, outstanding_fn: Callable[[], Dict[int, float]],
                   pause_fn: Callable[[str], None],
                   resume_fn: Callable[[str], None]) -> None:
        """Routing integration: ``outstanding_fn() -> {rank: backlog}``
        (requests routed but not yet completed, per serving rank);
        ``pause_fn(tenant)`` / ``resume_fn(tenant)`` bracket a tenant
        migration — paused traffic queues at the router and flushes to
        the new owner on resume (the measured migration pause)."""
        self._outstanding_fn = outstanding_fn
        self._pause_fn = pause_fn
        self._resume_fn = resume_fn

    def record_latency(self, latency_s: float) -> None:
        self._lat.append(float(latency_s))

    def owner_of(self, tenant: str) -> Optional[int]:
        return self.placement.get(tenant)

    def draining_ranks(self) -> List[int]:
        return sorted(self.draining)

    # ------------------------------------------------------- AM handlers
    def _on_stats(self, src: int, msg: Dict) -> None:
        with self._hb_lock:
            self._hb[src] = {"t": time.monotonic(),
                             "backlog": float(msg.get("backlog", 0.0)),
                             "tenants": msg.get("tenants", [])}

    def _on_ack(self, src: int, msg: Dict) -> None:
        with self._ack_lock:
            slot = self._acks.get(msg.get("token"))
        if slot is not None:
            slot[1] = msg
            slot[0].set()

    def _new_ack(self) -> Tuple[int, List]:
        token = next(self._token)
        slot = [threading.Event(), None]
        with self._ack_lock:
            self._acks[token] = slot
        return token, slot

    def _wait_ack(self, token: int, slot: List, timeout: float,
                  what: str) -> Dict:
        try:
            if not slot[0].wait(timeout):
                raise TimeoutError(f"elastic: no ack for {what} within "
                                   f"{timeout:.1f}s")
            msg = slot[1]
            if msg.get("error"):
                raise RuntimeError(f"elastic: {what} failed on the "
                                   f"remote rank: {msg['error']}")
            return msg
        finally:
            with self._ack_lock:
                self._acks.pop(token, None)

    # ----------------------------------------------------------- signals
    def signals(self) -> Signals:
        sig = Signals(serving_ranks=len(self.serving_ranks))
        per: Dict[int, float] = {r: 0.0 for r in self.serving_ranks}
        with self._hb_lock:
            for r, hb in self._hb.items():
                if r in per:
                    per[r] = hb["backlog"]
        if self._outstanding_fn is not None:
            for r, v in (self._outstanding_fn() or {}).items():
                # router-side view dominates: it also counts requests
                # a saturated worker has not even received yet
                per[r] = max(per.get(r, 0.0), float(v))
        sig.per_rank = per
        sig.backlog = sum(per.values())
        rt = self.runtime
        if rt is not None:
            st = rt.stats
            sig.parks = int(st.get("parked", 0))
            sig.rejections = int(st.get("rejected", 0))
            sig.shed = int(st.get("shed", 0))
        lats = list(self._lat)
        sig.p99_s = _pctl(lats, 0.99)
        sig.deadline_s = self.deadline_s
        return sig

    # ------------------------------------------------------ control loop
    def start(self) -> "ElasticController":
        if self.mode == "off" or self._thread is not None:
            return self
        t = threading.Thread(target=self._loop,
                             name="parsec-autoscaler", daemon=True)
        self._thread = t
        t.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5.0)
            self._thread = None

    def _loop(self) -> None:
        poll = float(mca_param.get("serving.autoscale_poll_s", 0.25))
        while not self._stop.wait(poll):
            try:
                self.step()
            except Exception as exc:  # noqa: BLE001 — loop must survive
                warning("elastic", "autoscaler step raised: %s", exc)
                import traceback
                traceback.print_exc()

    def step(self) -> Dict:
        """One control iteration (also callable directly — tests and
        deterministic harnesses drive it without the thread)."""
        now = time.monotonic()
        sig = self.signals()
        if self.mode == "act":
            # repair pass: a tenant left UNPLACED (a migration's adopt
            # leg failed, or a drain carried leftovers) is re-placed
            # from its durable shard step — without this, only the
            # next scale-UP would ever restore its traffic. Reuses
            # this poll's signal set (signals() walks the router's
            # outstanding map under its lock — no second pass).
            self.repair_placement(sig)
        desired, reason = self.policy.decide(sig, now)
        self.desired = desired
        current = len(self.serving_ranks)
        decision = {"t": now, "from": current, "to": desired,
                    "reason": reason, "mode": self.mode,
                    "backlog": round(sig.backlog, 1), "acted": False,
                    "ok": None}
        if desired != current:
            if self.mode == "act":
                decision["acted"] = True
                try:
                    if desired > current:
                        self.grow_one()
                    else:
                        self.shrink_one(sig)
                    decision["ok"] = True
                except Exception as exc:  # noqa: BLE001 — abandoned op
                    decision["ok"] = False
                    decision["error"] = str(exc)[:200]
                    warning("elastic", "scale %d -> %d abandoned: %s",
                            current, desired, exc)
                self.policy.note_act(time.monotonic())
            else:
                debug_verbose(2, "elastic",
                              "advise: would scale %d -> %d (%s)",
                              current, desired, reason)
        if decision["acted"]:
            self.decisions.append(decision)
            del self.decisions[:-256]
        elif reason not in ("steady", "cooldown"):
            # advise-mode would-acts and at-cap pressure: a separate
            # bounded log, so chatter can never push the (rare, load-
            # bearing) acted entries out of the operator's view
            self.advisories.append(decision)
            del self.advisories[:-32]
        self.last_decision = decision
        return decision

    # --------------------------------------------------------- scale up
    def _next_slot(self) -> int:
        """Reuse the lowest drained/dead slot first (keeps the world
        dense — a joiner wires up to every live in-range peer), else
        extend the world by one."""
        ws = self.comm.world_status()
        gone = sorted(set(ws["departed"]) | set(ws["dead"]))
        for r in gone:
            if r != self.ctx.my_rank:
                return r
        return int(ws["world"])

    def grow_one(self) -> int:
        """Admit one fresh serving rank: spawn → wait for the socket
        engine's admission → rebalance tenants onto it. A joiner
        stalled past ``comm.rejoin_timeout`` is abandoned (raises
        TimeoutError; the loop records the failure and continues)."""
        if self.spawn_rank is None:
            raise RuntimeError("scale-up needs a spawn_rank callback")
        new_rank = self._next_slot()
        ws = self.comm.world_status()
        world = max(int(ws["world"]), new_rank + 1)
        # controller FIRST in the joiner's wireup order: an abandoned
        # joiner is denied here before it can touch any other peer
        me = self.ctx.my_rank
        live = [me] + [r for r in ws["live"] if r != me]
        self._allow_join_everywhere(new_rank, live)
        self.spawn_rank(new_rank, world, live)
        try:
            self.comm.wait_rejoin(new_rank)
        except TimeoutError:
            admitted_late = False
            if hasattr(self.comm, "abandon_join"):
                # two-sided abandonment: a late arrival of the stalled
                # joiner is DENIED at the handshake — it must not be
                # silently admitted into quorums the controller will
                # never route work to. Propagated to every live peer
                # too (the joiner wires to the controller first, but
                # belt-and-braces against reordered transports). The
                # joiner may have squeaked in between our timeout and
                # the abandon mark — re-check once; an admitted rank
                # is a SUCCESS, not a zombie.
                self.comm.abandon_join(new_rank)
                for r in self.comm.world_status()["live"]:
                    if r != self.ctx.my_rank:
                        self.channel.send(r, "abandon_join",
                                          rank=new_rank)
                try:
                    self.comm.wait_rejoin(new_rank, timeout=0.05)
                    admitted_late = True
                    self._allow_join_everywhere(new_rank)
                except TimeoutError:
                    pass
            if not admitted_late:
                self.failed_joins += 1
                raise
        # readiness handshake: socket admission happens in the
        # joiner's engine constructor, BEFORE its ElasticWorker (and
        # hence its AMTag.ELASTIC handler) exists — migrating tenants
        # into that window would silently drop the adopt op and park
        # the tenant's routing for the whole migrate timeout. The
        # worker heartbeats immediately on construction; wait for it.
        self._wait_agent(new_rank)
        self.serving_ranks = sorted(set(self.serving_ranks) |
                                    {new_rank})
        self.rebalance()
        return new_rank

    def _allow_join_everywhere(self, rank: int, live=None) -> None:
        """Re-arm a joiner id on THIS engine and every live peer — an
        earlier abandonment was broadcast, so re-arming only locally
        would leave the fresh joiner denied by every worker it wires
        to after the controller."""
        if not hasattr(self.comm, "allow_join"):
            return
        self.comm.allow_join(rank)
        if live is None:
            live = self.comm.world_status()["live"]
        for r in live:
            if r != self.ctx.my_rank:
                self.channel.send(r, "allow_join", rank=rank)

    def _wait_agent(self, rank: int,
                    timeout: Optional[float] = None) -> None:
        """Block until ``rank``'s worker agent has heartbeat (its
        control-plane handler is registered); raises TimeoutError so a
        joined-but-agentless rank is a recorded failed decision, not a
        silent 30 s routing outage per migrated tenant."""
        if timeout is None:
            timeout = float(mca_param.get("serving.migrate_timeout_s",
                                          30.0))
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._hb_lock:
                if rank in self._hb:
                    return
            time.sleep(0.01)
        raise TimeoutError(
            f"rank {rank} joined the mesh but its elastic worker "
            f"agent sent no heartbeat within {timeout:.1f}s")

    def repair_placement(self, sig: Optional[Signals] = None) -> int:
        """Re-place every unplaced tenant (adopt-leg failure / drain
        leftovers) onto the least-loaded serving rank, adopting from
        its last durable shard step. Returns tenants re-placed; a
        still-failing adopt is logged and retried next step."""
        unplaced = sorted(t for t, r in self.placement.items()
                          if r is None)
        if not unplaced:
            return 0
        ranks = sorted(set(self.serving_ranks) - self.draining)
        if not ranks:
            return 0
        per = (sig if sig is not None else self.signals()).per_rank
        n = 0
        for t in unplaced:
            dst = min(ranks, key=lambda r: (per.get(r, 0.0), r))
            try:
                self.migrate_tenant(t, dst)
                n += 1
            except Exception as exc:  # noqa: BLE001 — retry next step
                warning("elastic", "re-placing tenant %s on rank %d "
                        "failed (will retry): %s", t, dst, exc)
        return n

    def rebalance(self) -> int:
        """Recompute the tenant → rank placement round-robin over the
        CURRENT serving ranks and migrate every tenant whose owner
        changed (the newcomer-onboarding path after a grow; also the
        repair path after a shrink). Returns migrations performed."""
        ranks = sorted(set(self.serving_ranks) - self.draining)
        if not ranks:
            return 0
        n = 0
        for i, t in enumerate(sorted(self.placement)):
            dst = ranks[i % len(ranks)]
            if self.placement[t] != dst:
                self.migrate_tenant(t, dst)
                n += 1
        return n

    # ------------------------------------------------------- scale down
    def shrink_one(self, sig: Optional[Signals] = None) -> int:
        """Quiesce → checkpoint-cut → drain one victim rank: migrate
        its tenants off, then send ``drain`` and wait for the ack; the
        victim leaves with an orderly BYE (peers record DEPARTED — the
        whole point is that a drained rank is never a failure)."""
        candidates = [r for r in self.serving_ranks
                      if r not in self.draining]
        if len(candidates) <= self.policy.min_ranks:
            raise RuntimeError("shrink refused: at min_ranks")
        per = (sig.per_rank if sig is not None else
               self.signals().per_rank)
        # least-loaded victim; highest id on ties (drained high slots
        # are reused first on the next grow, keeping the world dense)
        victim = max(candidates,
                     key=lambda r: (-per.get(r, 0.0), r))
        self.draining.add(victim)
        try:
            remaining = [r for r in self.serving_ranks
                         if r != victim and r not in self.draining]
            owned = sorted(t for t, r in self.placement.items()
                           if r == victim)
            if owned and not remaining:
                # scale-to-zero with live tenants: refuse with a clear
                # error instead of crashing the control loop every
                # poll (min_ranks=0 is a registered knob value)
                raise RuntimeError(
                    f"shrink refused: rank {victim} hosts tenants "
                    f"{owned} and no serving rank remains to adopt "
                    "them (raise serving.autoscale_min_ranks)")
            for i, t in enumerate(owned):
                self.migrate_tenant(t, remaining[i % len(remaining)])
            token, slot = self._new_ack()
            # the drain carries a checkpoint step so any LEFTOVER
            # tenant (normally all migrated off above) still exits
            # through the checkpoint vehicle, never lost
            step = next(self._step)
            self.channel.send(victim, "drain", token=token, step=step)
            ack = self._wait_ack(
                token, slot,
                float(mca_param.get("serving.drain_timeout_s", 30.0)),
                f"drain of rank {victim}")
            for t, s in (ack.get("steps") or {}).items():
                self.placement[t] = None
                self.shard_steps[t] = s
            self.serving_ranks = [r for r in self.serving_ranks
                                  if r != victim]
        finally:
            self.draining.discard(victim)
        return victim

    # -------------------------------------------------- tenant migration
    def migrate_tenant(self, tenant: str, dst: int) -> float:
        """Move one tenant's serving state from its current owner to
        ``dst`` through the checkpoint vehicle: pause routing → owner
        drains the tenant's in-flight work and saves its shard as a
        single-rank checkpoint step → ``dst`` restores the step and
        starts serving → resume routing. Returns the pause in ms (one
        ``migration_pauses_ms`` sample). Also the hot-spot
        isolation primitive — callable directly, not only from
        scale events."""
        src = self.placement.get(tenant)
        if src == dst:
            return 0.0
        timeout = float(mca_param.get("serving.migrate_timeout_s", 30.0))
        t0 = time.perf_counter()
        if self._pause_fn is not None:
            self._pause_fn(tenant)
        try:
            step = next(self._step)
            if src is not None:
                token, slot = self._new_ack()
                self.channel.send(src, "drop_tenant", tenant=tenant,
                                  step=step, token=token)
                ack = self._wait_ack(token, slot, timeout,
                                     f"drop of tenant {tenant} on "
                                     f"rank {src}")
                step = ack.get("step", step)
                # the drop leg committed: src no longer serves the
                # tenant, the shard lives in checkpoint ``step``. From
                # here the tenant is UNPLACED until an adopt succeeds —
                # a failed adopt must not leave routing pointed at src
                # (whose worker would bounce forever) nor a later
                # retry re-dropping a shard src no longer holds.
                self.placement[tenant] = None
                self.shard_steps[tenant] = step
            else:
                # unplaced tenant: adopt from its last durable shard
                # step (None = genuinely fresh)
                step = self.shard_steps.get(tenant)
            token, slot = self._new_ack()
            self.channel.send(dst, "adopt_tenant", tenant=tenant,
                              step=step, token=token)
            self._wait_ack(token, slot, timeout,
                           f"adopt of tenant {tenant} on rank {dst}")
            self.placement[tenant] = dst
        finally:
            if self._resume_fn is not None:
                self._resume_fn(tenant)
        pause_ms = (time.perf_counter() - t0) * 1e3
        self.migration_pauses_ms.append(pause_ms)
        debug_verbose(2, "elastic", "tenant %s: rank %s -> %d in %.1fms",
                      tenant, src, dst, pause_ms)
        return pause_ms

    def shutdown_workers(self) -> None:
        """Orderly end-of-life: every serving rank exits WITHOUT
        migration (the harness is tearing the whole mesh down)."""
        for r in list(self.serving_ranks):
            try:
                self.channel.send(r, "shutdown")
            except Exception:  # noqa: BLE001 — teardown is best-effort
                pass

    # ------------------------------------------------------------ status
    def status(self) -> Dict:
        """The statusz/report ``autoscaler`` block."""
        now = time.monotonic()
        last = None
        if self.last_decision is not None:
            d = self.last_decision
            last = {"age_s": round(now - d["t"], 2),
                    "from": d["from"], "to": d["to"],
                    "reason": d["reason"], "acted": d["acted"],
                    "ok": d["ok"]}
        return {"mode": self.mode,
                "desired": self.desired,
                "serving_ranks": list(self.serving_ranks),
                "draining": self.draining_ranks(),
                "placement": dict(self.placement),
                "cooldown_remaining_s": round(
                    self.policy.cooldown_remaining(now), 3),
                "last_decision": last,
                "decisions": len(self.decisions),
                "advisories": len(self.advisories),
                "failed_joins": self.failed_joins,
                "migrations": len(self.migration_pauses_ms),
                "migration_pause_p99_ms": (
                    round(_pctl(self.migration_pauses_ms, 0.99), 3)
                    if self.migration_pauses_ms else None)}


# ---------------------------------------------------------------------------
# worker agent
# ---------------------------------------------------------------------------

class ElasticWorker:
    """Serving-rank agent: heartbeats + the drain/migrate protocol.

    The workload plugs in through three callbacks:

    - ``on_adopt(tenant, step)`` — start serving ``tenant``; ``step``
      is the migration checkpoint to restore its shard from (None for
      a fresh tenant).
    - ``on_drop(tenant, step)`` — stop serving ``tenant``: drain its
      in-flight work, save its shard as checkpoint ``step``, release
      its resources.
    - ``on_request(src, msg)`` — serve one routed request (runs on the
      dedicated request thread, so a blocking admission park never
      delays the control plane).

    ``backlog_fn()`` feeds the heartbeat (queued + in-flight requests
    on this rank). AM handlers only enqueue; the service thread does
    the blocking work — a drain mid-checkpoint cannot stall the comm
    thread."""

    def __init__(self, ctx, controller_rank: int = 0,
                 on_adopt: Optional[Callable] = None,
                 on_drop: Optional[Callable] = None,
                 on_request: Optional[Callable] = None,
                 backlog_fn: Optional[Callable[[], float]] = None):
        self.ctx = ctx
        self.comm = ctx.comm
        self.controller_rank = controller_rank
        self.on_adopt = on_adopt
        self.on_drop = on_drop
        self.on_request = on_request
        self.backlog_fn = backlog_fn
        self.tenants: List[str] = []
        self._ops: "queue.Queue[Tuple[int, Dict]]" = queue.Queue()
        self._reqs: "queue.Queue[Tuple[int, Dict]]" = queue.Queue()
        self.drained = threading.Event()
        self._stop = threading.Event()
        self.channel = _ElasticChannel(self.comm)
        for op in ("adopt_tenant", "drop_tenant", "drain", "shutdown"):
            self.channel.on(op, self._enqueue_op)
        self.channel.on("req", self._enqueue_req)
        self.channel.on("abandon_join", self._on_abandon_join)
        self.channel.on("allow_join", self._on_allow_join)
        self._svc = threading.Thread(target=self._service_main,
                                     name="parsec-elastic-worker",
                                     daemon=True)
        self._req_thread = threading.Thread(
            target=self._request_main, name="parsec-elastic-req",
            daemon=True)
        self._svc.start()
        self._req_thread.start()

    # ---------------------------------------------------------- plumbing
    def _enqueue_op(self, src: int, msg: Dict) -> None:
        self._ops.put((src, msg))

    def _enqueue_req(self, src: int, msg: Dict) -> None:
        self._reqs.put((src, msg))

    def _on_abandon_join(self, src: int, msg: Dict) -> None:
        # comm-thread handler: a set add is GIL-atomic, no enqueue
        # needed — the controller abandoned a stalled joiner and every
        # peer must deny its late arrival
        if hasattr(self.comm, "abandon_join"):
            self.comm.abandon_join(msg["rank"])

    def _on_allow_join(self, src: int, msg: Dict) -> None:
        # the controller is reusing a previously-abandoned slot for a
        # FRESH spawn: re-arm it here too (set discard, GIL-atomic)
        if hasattr(self.comm, "allow_join"):
            self.comm.allow_join(msg["rank"])

    def _ack(self, src: int, msg: Dict, **kw) -> None:
        token = msg.get("token")
        if token is not None:
            self.channel.send(src, "ack", token=token, **kw)

    def send_controller(self, op: str, **kw) -> None:
        self.channel.send(self.controller_rank, op, **kw)

    # ------------------------------------------------------------ threads
    def _request_main(self) -> None:
        while not self._stop.is_set():
            try:
                src, msg = self._reqs.get(timeout=0.1)
            except queue.Empty:
                continue
            if self.on_request is None:
                continue
            try:
                self.on_request(src, msg)
            except Exception as exc:  # noqa: BLE001 — keep serving
                warning("elastic", "request handler raised: %s", exc)

    def _service_main(self) -> None:
        poll = float(mca_param.get("serving.autoscale_poll_s", 0.25))
        last_hb = 0.0
        while not self._stop.is_set():
            now = time.monotonic()
            if now - last_hb >= poll:
                last_hb = now
                backlog = 0.0
                if self.backlog_fn is not None:
                    try:
                        backlog = float(self.backlog_fn())
                    except Exception:  # noqa: BLE001 — heartbeat only
                        pass
                try:
                    self.send_controller("stats", rank=self.comm.rank,
                                         backlog=backlog,
                                         tenants=list(self.tenants))
                except Exception:  # noqa: BLE001 — mesh tearing down
                    pass
            try:
                src, msg = self._ops.get(timeout=poll)
            except queue.Empty:
                continue
            op = msg.get("op")
            try:
                if op == "adopt_tenant":
                    t = msg["tenant"]
                    if self.on_adopt is not None:
                        self.on_adopt(t, msg.get("step"))
                    if t not in self.tenants:
                        self.tenants.append(t)
                    self._ack(src, msg)
                elif op == "drop_tenant":
                    t = msg["tenant"]
                    step = msg.get("step")
                    if self.on_drop is not None:
                        step = self.on_drop(t, step)
                    if t in self.tenants:
                        self.tenants.remove(t)
                    self._ack(src, msg, step=step)
                elif op == "drain":
                    # quiesce → checkpoint-cut → leave: leftover
                    # tenants (normally migrated off already) are
                    # dropped through the same checkpoint vehicle so
                    # nothing is lost even on a direct drain (they all
                    # share the drain's step — one step dir holds one
                    # file per collection)
                    steps = {}
                    for t in list(self.tenants):
                        if self.on_drop is not None:
                            steps[t] = self.on_drop(t, msg.get("step"))
                        self.tenants.remove(t)
                    self._ack(src, msg, steps=steps)
                    self.drained.set()
                elif op == "shutdown":
                    self._ack(src, msg)
                    self.drained.set()
            except Exception as exc:  # noqa: BLE001 — ack the failure
                warning("elastic", "worker op %r raised: %s", op, exc)
                import traceback
                traceback.print_exc()
                self._ack(src, msg, error=str(exc)[:200])

    def wait_drained(self, timeout: Optional[float] = None) -> bool:
        """Block until this rank was told to drain/shutdown. The
        caller then finalizes its context — the engine's orderly BYE
        is what moves this rank to DEPARTED on every peer."""
        return self.drained.wait(timeout)

    def stop(self) -> None:
        self._stop.set()
        self._svc.join(timeout=5.0)
        self._req_thread.join(timeout=5.0)
