"""Multi-process comm engine over TCP sockets (MPI-funnelled analog).

Reference: parsec_mpi_funnelled.c (1,228 LoC) + remote_dep_mpi.c (2,207
LoC). The reference funnels all MPI calls through one dedicated comm
thread consuming a command dequeue (dep_cmd_item_t: ACTIVATE, MEMCPY,
RELEASE, CTL; remote_dep.h:261-272), aggregates activations per peer,
sends small payloads eagerly inline with the activation message and large
ones through a rendezvous GET/PUT with registered-memory handles
(remote_dep_mpi.c:1963-2118).

This engine reproduces that architecture over localhost TCP for real
multi-process runs (the reference's tests run 2-8 MPI ranks on one node —
SURVEY §4; DCN between TPU hosts is the production transport this models):

- full-mesh wireup: rank r listens on ``base_port + r``; higher ranks
  connect to lower ranks and identify themselves;
- ONE comm thread per rank owns every socket (funnelled); worker threads
  only enqueue commands. ``comm.thread_multiple=1`` is the
  MPI_THREAD_MULTIPLE analog (parsec_param_comm_thread_multiple): worker
  threads write frames to the peer socket directly under per-peer send
  locks, receives/handlers stay on the comm thread;
- per-peer aggregation: all ACTIVATE commands drained in one progress
  iteration and bound for the same peer ship as one frame, ordered by
  priority (remote_dep_mpi.c:1089-1139);
- eager vs rendezvous by ``comm.eager_limit``: large values stay in the
  sender's registered-memory table; the receiver answers the activation
  with a GET carrying its own handle; the sender PUTs the payload
  (remote_dep_wire_get_t analog, remote_dep.h:50-56);
- termdet waves (fourcounter) and user triggers ride dedicated AM tags
  with rank 0 as wave coordinator.
"""

from __future__ import annotations

import itertools
import pickle
import queue
import selectors
import socket
import struct
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from .engine import AMTag, CommEngine
from .collectives import BcastTopology, bcast_live_children
from . import device_plane
from ..utils import mca_param
from ..utils.debug import debug_verbose, warning

mca_param.register("comm.eager_limit", 256 * 1024,
                   help="payloads <= this many bytes ship inline with the "
                        "activation (parsec_param_eager_limit analog)")
mca_param.register("comm.aggregate", True,
                   help="coalesce same-peer activations into one frame "
                        "(parsec_param_enable_aggregate analog)")
mca_param.register("comm.stage_recv", "auto",
                   help="stage received array payloads to the device on "
                        "the comm thread: auto (accelerator backends "
                        "only) | 1 | 0")
mca_param.register("comm.wireup_timeout_s", 30.0,
                   help="seconds to wait for the full mesh to connect")
mca_param.register("comm.rdv_push", 1,
                   help="above-eager-limit payloads stream as pushed "
                        "segment frames right behind their activation "
                        "(the GET leg's round trip is elided; TCP "
                        "backpressure replaces receiver pacing); 0 = "
                        "classic registered-memory GET/PUT rendezvous "
                        "(remote_dep_mpi.c:1963-2118)")
mca_param.register("comm.rejoin", 0,
                   help="accept a replacement rank for a dead peer: on "
                        "death detection this rank re-opens its wireup "
                        "listener and a process started with "
                        "SocketCommEngine(..., rejoin=True) can adopt "
                        "the dead rank's slot (ULFM-style shrink/"
                        "respawn); 0 = a dead rank stays dead")
mca_param.register("comm.rejoin_timeout", 60.0,
                   help="seconds wait_rejoin blocks for a replacement "
                        "rank before raising (the survivor-side "
                        "rendezvous bound before recovery replay)")
mca_param.register("comm.elastic", 0,
                   help="elastic mesh mode (serving autoscale): every "
                        "rank keeps its wireup listener open for the "
                        "life of the engine, FRESH ranks beyond the "
                        "original world size are admitted (the peer "
                        "table, termdet waves, barriers and recovery "
                        "allgathers grow to the enlarged live set), and "
                        "an orderly BYE (drain) removes a rank from the "
                        "live set WITHOUT the failure path; 0 = the "
                        "static mesh (rejoin still replaces dead ranks "
                        "under comm.rejoin)")
mca_param.register("comm.thread_multiple", 0,
                   help="MPI_THREAD_MULTIPLE analog (parsec_param_comm_"
                        "thread_multiple, remote_dep.h:166): worker "
                        "threads write frames to the peer socket "
                        "directly (per-peer send locks keep the byte "
                        "stream framed) instead of funnelling through "
                        "the comm-thread command queue; receives and AM "
                        "handlers stay on the comm thread. Direct sends "
                        "skip per-peer activation aggregation. "
                        "0 = funnelled (the reference default)")

_HDR = struct.Struct("!Q")     # frame length prefix
_U32 = struct.Struct("!I")     # pickle-section length prefix
_WAKE_PEER = -1                # selector data tag of the self-pipe
_LISTEN_PEER = -2              # selector data tag of the rejoin listener


class _WaveState:
    """Coordinator-side state of one in-flight termdet wave (the
    coordinator is the lowest LIVE rank — rank 0 unless it died)."""

    def __init__(self, name: str, wave_id: int, live):
        self.name = name
        self.wave_id = wave_id
        self.live = set(live)
        self.pending = len(self.live)
        self.replied: set = set()
        self.sent = 0
        self.received = 0
        self.all_idle = True


class SocketCommEngine(CommEngine):
    """parsec_comm_engine_t implementation over localhost TCP."""

    def __init__(self, rank: int, nb_ranks: int, base_port: int = 27450,
                 host: str = "127.0.0.1", rejoin: bool = False,
                 join_peers: Optional[List[int]] = None):
        super().__init__(rank, nb_ranks)
        self.host = host
        self.base_port = base_port
        # elastic capacity: the world size this engine was BUILT with
        # (the statusz "configured" row) — self.nb_ranks may grow as
        # fresh ranks are admitted (comm.elastic); departed ranks left
        # via an orderly drain (BYE), distinct from failures
        self._nb_ranks0 = nb_ranks
        self._departed: set = set()
        # elastic join: the LIVE peer set a fresh/replacement rank
        # wires up to (None = every other rank in range(nb_ranks) — the
        # static-mesh rejoin default). On an elastic mesh some slots in
        # that range may be drained-and-empty; connecting to them would
        # wedge the joiner until the wireup deadline. CALLER ORDER is
        # preserved: the controller puts itself first, so a joiner it
        # has ABANDONED sticks in the controller's deny-retry loop and
        # never partially joins the other peers (world-size divergence)
        self._join_peers = ([int(p) for p in join_peers]
                            if join_peers is not None else None)
        self._socks: Dict[int, socket.socket] = {}
        self._rxbuf: Dict[int, bytearray] = {}
        self._txbuf: Dict[int, bytearray] = {}   # guarded by _send_locks
        # per-peer send locks: the comm thread and (under
        # comm.thread_multiple) worker threads serialize frame writes so
        # the byte stream never interleaves mid-frame
        self._send_locks: Dict[int, threading.Lock] = {}
        self._stats_lock = threading.Lock()
        self._cmd_q: "queue.Queue[Tuple]" = queue.Queue()
        self._mem: Dict[int, Any] = {}
        self._mem_next = 0
        self._mem_lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # failure detection: the reference gets job-kill semantics from
        # MPI's default error handler + parsec_abort (runtime.h:33-37);
        # here a dead peer is detected at the socket (zero-byte recv /
        # send error), recorded, and every dependent wait is failed
        # instead of left to time out
        self._dead_peers: set = set()
        self._bye_peers: set = set()       # peers that announced shutdown
        self._peer_failure: Optional[BaseException] = None
        self._barrier_waiting = False
        self._listener: Optional[socket.socket] = None
        self._sel = selectors.DefaultSelector()
        self._context = None
        self._parked: Dict[str, List[tuple]] = {}
        self._pending_gets: Dict[int, Tuple] = {}    # my recv handle -> state
        # segmented payload streams (comm-thread-only state):
        # (src_rank, sid) -> reassembly dict; sender-side sid counter
        self._rx_streams: Dict[Tuple[int, int], Dict] = {}
        self._sid_next = itertools.count(1)
        # mid-large-frame receive: peer -> [frame bytearray, filled]
        # (bytes land straight in the frame via recv_into — the staging
        # rxbuf never holds more than the small-frame working set)
        self._rxlarge: Dict[int, List] = {}
        self._termdet_monitors: Dict[str, object] = {}
        # wave coordination (lowest live rank)
        self._waves: Dict[str, _WaveState] = {}
        self._wave_next_id = 0
        self._barrier_release = threading.Event()
        # coordinator-side barrier entries, keyed by GENERATION (= the
        # entrant's observed death count): entries abandoned when a
        # peer death failed their barrier stay in their own bucket and
        # can never release a post-recovery barrier early
        self._barrier_counts: Dict[int, int] = {}
        self._barrier_gen = 0                    # this rank's last entry
        # fault recovery: rejoin listener + per-rank admit events, and
        # the RECOVER-tag allgather state (comm-thread-only dicts)
        self._rejoin_listener: Optional[socket.socket] = None
        self._rejoin_evts: Dict[int, threading.Event] = {}
        self._rejoin_lock = threading.Lock()
        # ABANDONED joiner ids (wait_rejoin timed out and the caller
        # gave up on the slot): a late arrival is denied instead of
        # silently admitted into a mesh whose controller no longer
        # routes to it — admitting it would inflate every barrier
        # quorum with a rank that never participates
        self._abandoned: set = set()
        self._recover_state: Dict[str, Dict] = {}
        self._recover_futs: Dict[str, object] = {}
        self._silenced = False
        self.tag_register(AMTag.RECOVER, self._on_recover)
        # deterministic failure injection (comm.fault_inject)
        from .faultinject import FaultInjector
        self.fault = FaultInjector.from_mca(rank)
        if self.fault is not None:
            self.fault.attach(self)
        # clock-offset pingpong (distributed-trace alignment): replies
        # run on the comm thread; initiators park on a Future
        self._clock_futs: Dict[int, object] = {}
        self._clock_next = itertools.count(1)
        self._clock_cache: Dict[int, Tuple[float, float]] = {}
        self.tag_register(AMTag.CLOCK, self._on_clock)
        # control-plane tags usable without a Context
        self.tag_register(AMTag.BARRIER, self._on_barrier)
        self.tag_register(AMTag.TERMDET_FOURCOUNTER, self._on_termdet)
        self.tag_register(AMTag.TERMDET_USER_TRIGGER, self._on_trigger)
        self.tag_register(AMTag.BYE, self._on_bye)
        # frame-level wire counters only; payload-level activation
        # counters live in the base ``stats`` dict (record_msg)
        self._stats = {"frames_sent": 0, "frames_recv": 0, "bytes_sent": 0,
                       "bytes_recv": 0, "gets": 0, "puts": 0,
                       "segs_sent": 0, "segs_recv": 0}
        # self-pipe: workers posting commands interrupt the comm thread's
        # selector block so sends don't wait out the poll timeout (the
        # reference relies on MPI progress being driven by the same
        # thread that dequeues — here the selector needs an explicit kick)
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._wake_w.setblocking(False)
        self._sel.register(self._wake_r, selectors.EVENT_READ, _WAKE_PEER)
        if nb_ranks > 1:
            if rejoin:
                self._wireup_rejoin()
            else:
                self._wireup()

    def _post_cmd(self, cmd: Tuple) -> None:
        """Enqueue a command for the comm thread and kick its selector —
        unless the CALLER is the comm thread: it drains the queue at the
        top of its next iteration before selecting again, so the kick
        would be a wasted syscall plus a token to drain per handler-
        originated send (two per rendezvous leg on the round-5 path)."""
        self._cmd_q.put(cmd)
        if threading.get_ident() == getattr(self, "_comm_tid", None):
            return
        try:
            self._wake_w.send(b"x")
        except (BlockingIOError, OSError):
            pass      # pipe full = wakeup already pending

    # ------------------------------------------------------------- wireup
    def _wireup(self) -> None:
        timeout = float(mca_param.get("comm.wireup_timeout_s", 30.0))
        deadline = time.monotonic() + timeout
        lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        lst.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        lst.bind((self.host, self.base_port + self.rank))
        lst.listen(self.nb_ranks)
        self._listener = lst
        # connect to every lower rank, retrying until its listener is up
        for peer in range(self.rank):
            while True:
                try:
                    s = socket.create_connection(
                        (self.host, self.base_port + peer), timeout=1.0)
                    break
                except OSError:
                    if time.monotonic() > deadline:
                        raise TimeoutError(
                            f"rank {self.rank}: wireup to {peer} timed out")
                    time.sleep(0.02)
            s.sendall(struct.pack("!I", self.rank))
            self._register_peer(peer, s)
        # accept every higher rank
        lst.settimeout(max(0.1, deadline - time.monotonic()))
        for _ in range(self.rank + 1, self.nb_ranks):
            s, _addr = lst.accept()
            hdr = self._recv_exact(s, 4)
            peer = struct.unpack("!I", hdr)[0]
            self._register_peer(peer, s)
        lst.close()
        self._listener = None
        debug_verbose(3, "comm", "rank %d: mesh up (%d peers)",
                      self.rank, len(self._socks))

    def _wireup_rejoin(self) -> None:
        """Replacement-rank wireup: adopt a dead rank's slot by
        connecting OUT to every other rank (their rejoin listeners
        reopen on death detection — comm.rejoin); retried until the
        wireup deadline, since survivors open their listeners only once
        they detect the death."""
        if self.fault is not None:
            # slowjoin injection: the handshake stalls HERE, before the
            # first connect — peers past comm.rejoin_timeout abandon us
            self.fault.on_join_handshake()
        timeout = float(mca_param.get("comm.wireup_timeout_s", 30.0))
        deadline = time.monotonic() + timeout
        peers = self._join_peers if self._join_peers is not None \
            else range(self.nb_ranks)
        for peer in peers:
            if peer == self.rank:
                continue
            while True:
                s = None
                try:
                    s = socket.create_connection(
                        (self.host, self.base_port + peer), timeout=2.0)
                    s.settimeout(2.0)
                    s.sendall(struct.pack("!I", self.rank))
                    # explicit admit/deny: a TCP connect alone is NOT
                    # admission — the peer may refuse (it has not
                    # detected our predecessor's death yet, or the rank
                    # id is still live there); retry until admitted
                    if self._recv_exact(s, 1) == b"\x01":
                        break
                    raise ConnectionRefusedError("rejoin denied")
                except OSError:
                    if s is not None:
                        try:
                            s.close()
                        except OSError:
                            pass
                    if time.monotonic() > deadline:
                        raise TimeoutError(
                            f"rank {self.rank}: rejoin to {peer} timed "
                            f"out (is comm.rejoin enabled there?)")
                    time.sleep(0.05)
            self._register_peer(peer, s)
        if self._join_peers is not None:
            # in-range slots we were told NOT to join are drained-and-
            # empty: record them departed so this rank's live set (and
            # hence barrier quorums / termdet waves) agrees with the
            # rest of the mesh; a later joiner reusing such a slot is
            # admitted through the normal rejoin path
            absent = set(range(self.nb_ranks)) \
                - set(self._join_peers) - {self.rank}
            self._dead_peers.update(absent)
            self._departed.update(absent)
        debug_verbose(2, "comm", "rank %d: rejoined mesh (%d peers)",
                      self.rank, len(self._socks))

    def _open_rejoin_listener(self) -> None:
        """Re-open this rank's wireup port so a replacement for a dead
        peer can connect (comm thread; idempotent)."""
        if self._rejoin_listener is not None:
            return
        try:
            lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            lst.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            lst.bind((self.host, self.base_port + self.rank))
            lst.listen(self.nb_ranks)
            lst.setblocking(False)
        except OSError as exc:
            warning("comm", "rank %d: cannot open rejoin listener: %s",
                    self.rank, exc)
            return
        self._rejoin_listener = lst
        self._sel.register(lst, selectors.EVENT_READ, _LISTEN_PEER)
        debug_verbose(2, "comm", "rank %d: rejoin listener open",
                      self.rank)

    def _close_rejoin_listener(self) -> None:
        lst = self._rejoin_listener
        if lst is None:
            return
        self._rejoin_listener = None
        try:
            self._sel.unregister(lst)
        except (KeyError, ValueError):
            pass
        try:
            lst.close()
        except OSError:
            pass

    def _elastic_enabled(self) -> bool:
        return str(mca_param.cached_get("comm.elastic", 0)).lower() \
            not in ("0", "off", "false")

    def _accept_rejoin(self, lst: socket.socket) -> None:
        """Admit a replacement or FRESH rank (comm thread): it
        identifies itself with its rank id. A currently-dead (or
        drained) id is a rejoin — the slot is adopted; under
        ``comm.elastic`` an id at or beyond the current world size is a
        GROW — the peer table, live set, and every collective quorum
        extend to the enlarged world. A live id is denied."""
        elastic = self._elastic_enabled()
        while True:
            try:
                s, _addr = lst.accept()
            except (BlockingIOError, OSError):
                return
            try:
                s.settimeout(2.0)
                peer = struct.unpack("!I", self._recv_exact(s, 4))[0]
            except (OSError, struct.error) as exc:
                warning("comm", "rank %d: bad rejoin handshake: %s",
                        self.rank, exc)
                s.close()
                continue
            if peer in self._abandoned:
                # the controller gave up on this joiner (wait_rejoin
                # timed out — e.g. a slowjoin stall): deny, so the
                # late arrival cannot skew quorums; its own wireup
                # deadline ends it
                warning("comm", "rank %d: abandoned joiner rank %d "
                        "denied", self.rank, peer)
                try:
                    s.sendall(b"\x00")
                except OSError:
                    pass
                s.close()
                continue
            grow = elastic and peer >= self.nb_ranks
            if not grow and peer not in self._dead_peers:
                # deny explicitly (the replacement retries — e.g. we
                # have not detected its predecessor's death yet)
                warning("comm", "rank %d: rejoin for live rank %d "
                        "refused", self.rank, peer)
                try:
                    s.sendall(b"\x00")
                except OSError:
                    pass
                s.close()
                continue
            try:
                s.sendall(b"\x01")      # admit BEFORE going non-blocking
            except OSError as exc:
                warning("comm", "rank %d: rejoin admit failed: %s",
                        self.rank, exc)
                s.close()
                continue
            self._register_peer(peer, s)
            self._sel.register(s, selectors.EVENT_READ, peer)
            if grow:
                # fresh rank beyond the original world: _live_ranks,
                # barrier quorums, termdet waves and the RECOVER
                # allgather all range over nb_ranks — one assignment
                # (comm thread, like every handler) grows them all
                self.nb_ranks = max(self.nb_ranks, peer + 1)
            self._dead_peers.discard(peer)
            self._bye_peers.discard(peer)
            self._departed.discard(peer)
            # the quorum landscape changed (grow: new generation;
            # rejoin: live set restored) — pre-admit generations whose
            # entrants are all in must release now, not at timeout
            self._maybe_release_barrier()
            if not self._dead_peers:
                # mesh whole again: new taskpools may launch. Elastic
                # meshes keep the listener open for the next joiner.
                self._peer_failure = None
                if not elastic:
                    self._close_rejoin_listener()
            with self._rejoin_lock:
                evt = self._rejoin_evts.setdefault(peer,
                                                   threading.Event())
            evt.set()
            warning("comm", "rank %d: rank %d %s the mesh (world %d)",
                    self.rank, peer, "grew" if grow else "rejoined",
                    self.nb_ranks)

    def wait_rejoin(self, rank: int,
                    timeout: Optional[float] = None) -> bool:
        """Block until a replacement for dead ``rank`` — or, on an
        elastic mesh, a FRESH joiner adopting that id — has been
        admitted (the survivor/autoscaler-side rendezvous).
        ``timeout`` defaults to the ``comm.rejoin_timeout`` MCA knob;
        expiry raises a :class:`TimeoutError` naming the knob so a
        too-slow (or slowjoin-stalled) joiner is ABANDONED with a
        diagnosable error instead of a bare False propagating into a
        confusing replay failure or a wedged autoscaler loop."""
        if timeout is None:
            timeout = float(mca_param.get("comm.rejoin_timeout", 60.0))
        with self._rejoin_lock:
            evt = self._rejoin_evts.setdefault(rank, threading.Event())
        if not evt.wait(timeout):
            raise TimeoutError(
                f"rank {self.rank}: no replacement/joiner for rank "
                f"{rank} within {timeout:.1f}s — raise the "
                "comm.rejoin_timeout MCA knob if the respawner needs "
                "longer")
        return True

    def abandon_join(self, rank: int) -> None:
        """Give up on an expected joiner (after a wait_rejoin timeout):
        a late arrival under this id is DENIED at the handshake. The
        id can be re-armed with :meth:`allow_join` before a fresh
        spawn reuses it. Set-membership writes are GIL-atomic; the
        accept path reads on the comm thread."""
        self._abandoned.add(int(rank))

    def allow_join(self, rank: int) -> None:
        """Re-arm a previously-abandoned joiner id (the controller is
        about to spawn a fresh process for it)."""
        self._abandoned.discard(int(rank))

    def acknowledge_failure(self) -> None:
        self._peer_failure = None

    def go_silent(self, why: str) -> None:
        """Drop-mode fault injection: stop all outbound traffic and
        tear down the peer sockets so peers detect a crash — but keep
        the process alive (the in-suite failure harness)."""
        self._silenced = True
        self._post_cmd(("go_silent", why))

    @staticmethod
    def _recv_exact(s: socket.socket, n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            chunk = s.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("peer closed during wireup")
            buf += chunk
        return buf

    def _register_peer(self, peer: int, s: socket.socket) -> None:
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.setblocking(False)
        self._socks[peer] = s
        self._rxbuf[peer] = bytearray()
        self._txbuf[peer] = bytearray()
        self._send_locks[peer] = threading.Lock()

    # ----------------------------------------------------------- lifecycle
    def enable(self) -> None:
        super().enable()
        if self.nb_ranks > 1 and self._thread is None:
            if not self._socks:
                # disable() closed the peer mesh; restarting the comm
                # thread with zero registered sockets would leave this
                # rank silently deaf — fail fast (engines are created
                # per run; re-wireup needs a fresh engine)
                raise RuntimeError(
                    "socket engine re-enabled after disable() closed "
                    "the peer mesh; create a new engine instead")
            if self._wake_r.fileno() < 0:     # re-enable after disable()
                self._wake_r, self._wake_w = socket.socketpair()
                self._wake_r.setblocking(False)
                self._wake_w.setblocking(False)
                self._sel.register(self._wake_r, selectors.EVENT_READ,
                                   _WAKE_PEER)
            self._stop.clear()
            for peer, s in self._socks.items():
                self._sel.register(s, selectors.EVENT_READ, peer)
            t = threading.Thread(target=self._comm_main,
                                 name=f"parsec-comm-{self.rank}", daemon=True)
            self._thread = t
            t.start()
            if self._elastic_enabled():
                # elastic mesh: the wireup listener stays open for the
                # life of the engine so fresh ranks can join at any
                # time (opened ON the comm thread — listener + selector
                # state are comm-thread-only by construction)
                self._post_cmd(("listen",))

    def disable(self) -> None:
        super().disable()
        if self._thread is not None and not self._stop.is_set():
            # orderly goodbye (MPI_Finalize analog): peers seeing our
            # FIN after this frame treat the close as shutdown, not
            # failure. Queued before _stop so the comm thread's exit
            # drain flushes it.
            for peer in self._socks:
                if peer != self.rank and peer not in self._dead_peers:
                    self._post_cmd(("am", AMTag.BYE, peer, {}))
        self._stop.set()
        try:
            self._wake_w.send(b"x")   # kick the selector out of its block
        except (BlockingIOError, OSError):
            pass
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        self._close_rejoin_listener()     # after the join: comm-thread state
        for s in self._socks.values():
            # unregister BEFORE closing: a stale selector entry whose fd
            # number gets reused by a later socket would break re-enable
            # (register raises) or misattribute readiness events
            try:
                self._sel.unregister(s)
            except (KeyError, ValueError):
                pass
            try:
                s.close()
            except OSError:
                pass
        self._socks.clear()
        # release the wakeup pair — engines are created per run, and
        # leaked fd pairs add up in long-lived parents (harness loops)
        try:
            self._sel.unregister(self._wake_r)
        except (KeyError, ValueError):
            pass
        for s in (self._wake_r, self._wake_w):
            try:
                s.close()
            except OSError:
                pass

    # --------------------------------------------- comm thread (funnelled)
    def _comm_main(self) -> None:
        """remote_dep_dequeue_main analog: the only thread touching
        sockets. Each iteration drains the command queue (with per-peer
        aggregation) then progresses receives."""
        from ..utils import binding
        self._comm_tid = threading.get_ident()
        binding.bind_comm_thread()        # remote_dep_bind_thread analog
        while not self._stop.is_set():
            queued = self._drain_commands()
            flushed = self._flush_sends()
            # the selector IS the idle wait: peers' data and the
            # command self-pipe both wake it immediately, so a longer
            # block costs no latency (only bounds _stop polling) —
            # UNLESS outbound bytes are stuck behind a full kernel
            # buffer: the selector only watches reads, so keep the
            # retry cadence short until the tx drains
            if queued or flushed:
                block = 0.0
            elif any(self._txbuf.values()):
                block = 0.0005
            else:
                block = 0.01
            self._progress_recv(block)
        # drain: flush whatever is still queued so peers aren't cut off
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            self._drain_commands()
            if not self._flush_sends() and \
                    not any(self._txbuf.values()) and self._cmd_q.empty():
                break

    def _drain_commands(self) -> int:
        aggregate = bool(mca_param.cached_get("comm.aggregate", True))
        per_peer: Dict[int, List[Dict]] = {}
        other: List[Tuple] = []
        n = 0
        while True:
            try:
                cmd = self._cmd_q.get_nowait()
            except queue.Empty:
                break
            n += 1
            kind = cmd[0]
            if kind == "activate":
                _, dst, msg = cmd
                if dst == self.rank:
                    self._dispatch(AMTag.ACTIVATE, self.rank, [msg])
                    continue
                per_peer.setdefault(dst, []).append(msg)
            elif kind == "self":       # ("self", tag, msg)
                self._dispatch(cmd[1], self.rank, cmd[2])
            elif kind == "deliver":    # ("deliver", tp) — drain parked
                tp = cmd[1]            # activations on the comm thread
                for (src, msg) in self._parked.pop(tp.name, []):
                    self._deliver_activation(tp, src, msg)
            elif kind == "peer_dead":  # ("peer_dead", peer, why) — posted
                self._mark_peer_dead(cmd[1], cmd[2])  # by worker threads
            elif kind == "listen":     # elastic: (re)open the wireup
                self._open_rejoin_listener()          # listener
            elif kind == "go_silent":  # drop-mode fault injection: the
                # victim "crashes" from the peers' view — every peer
                # socket torn down, no BYE, local pools aborted through
                # the same peer-death sweep the survivors run
                for peer in [p for p in list(self._socks)
                             if p != self.rank]:
                    self._mark_peer_dead(peer, cmd[1])
            else:                      # ("am", tag, dst, msg)
                other.append(cmd)
        for dst, msgs in per_peer.items():
            msgs.sort(key=lambda m: -m.get("priority", 0))
            if aggregate:
                self._send_frame(dst, AMTag.ACTIVATE, msgs)
            else:
                for m in msgs:
                    self._send_frame(dst, AMTag.ACTIVATE, [m])
        for (_, tag, dst, msg) in other:
            self._send_frame(dst, tag, msg)
        return n

    def _encode_parts(self, tag: int, msg: Any) -> Tuple[List[Any], int]:
        """Serialize one frame as scatter-gather parts. Wire format
        (unchanged from the round-5 single-buffer encoder): ``!Q
        total_len``, ``!I pickle_len``, the protocol-5 pickle, then each
        out-of-band buffer as ``!Q len`` + raw bytes (the reference's
        datatype pack path, parsec_comm_engine.h:113-183). Control bytes
        land in one small bytearray; each contiguous array payload stays
        a ZERO-COPY memoryview over the producer's buffer — the send
        paths hand the list to ``sendmsg``, so a rendezvous-sized PUT
        pays no Python-side payload copy on the happy path (the round-5
        encoder copied the payload into the frame AND the frame into
        txbuf: two full copies per large frame). Returns
        ``(parts, frame_nbytes)``."""
        bufs: List[pickle.PickleBuffer] = []
        payload = pickle.dumps((int(tag), self.rank, msg),
                               protocol=5, buffer_callback=bufs.append)
        raws = [b.raw() for b in bufs]
        total = _U32.size + len(payload) + sum(
            _HDR.size + r.nbytes for r in raws)
        head = bytearray()
        head += _HDR.pack(total)
        head += _U32.pack(len(payload))
        head += payload
        parts: List[Any] = [head]
        for r in raws:
            parts.append(_HDR.pack(r.nbytes))
            parts.append(r)
        return parts, _HDR.size + total

    def _write_parts_locked(self, dst: int, s: socket.socket,
                            parts: List[Any]) -> Optional[OSError]:
        """Write frame parts to the peer socket as far as the kernel
        accepts (send lock held, socket non-blocking); any unsent
        remainder is queued on txbuf for ``_flush_sends`` — txbuf bytes
        always precede new frames, so framing stays intact. Returns the
        OSError of a failed send (the caller handles peer teardown
        OUTSIDE the lock — _mark_peer_dead takes it), else None."""
        views = [memoryview(p) for p in parts]
        i = 0
        while i < len(views):
            try:
                sent = s.sendmsg(views[i:i + 64])    # IOV_MAX headroom
            except BlockingIOError:
                break
            except OSError as exc:
                return exc
            if not sent:
                break
            while i < len(views) and sent >= views[i].nbytes:
                sent -= views[i].nbytes
                i += 1
            if sent:
                views[i] = views[i][sent:]
        if i < len(views):
            buf = self._txbuf[dst]
            for v in views[i:]:
                buf += v
        return None

    def _count_sent(self, frame_bytes: int) -> None:
        with self._stats_lock:
            self._stats["frames_sent"] += 1
            self._stats["bytes_sent"] += frame_bytes

    def _send_frame(self, dst: int, tag: int, msg: Any) -> None:
        """Send one frame from the COMM THREAD: write straight to the
        socket when nothing is queued (the common case — saves a full
        frame copy into txbuf plus one flush iteration of latency;
        round 5 queued unconditionally, which cost the rendezvous PUT
        path an extra 1 MB copy AND a loop turnaround per leg), else
        append behind the queued bytes. Non-blocking sends prevent the
        head-of-line deadlock of two ranks pushing large frames at each
        other with full TCP buffers; no wait ever happens under the
        per-peer lock (unsent remainders go to txbuf)."""
        if self.fault is not None and self.fault.on_frame_sent():
            return                    # injected silence (drop mode)
        if dst in self._dead_peers:
            debug_verbose(3, "comm", "rank %d: dropping frame for dead "
                          "peer %d", self.rank, dst)
            return
        parts, nbytes = self._encode_parts(tag, msg)
        s = self._socks.get(dst)
        failed: Optional[OSError] = None
        with self._send_locks[dst]:
            buf = self._txbuf[dst]
            if buf or s is None:
                for p in parts:
                    buf += p
            else:
                failed = self._write_parts_locked(dst, s, parts)
        self._count_sent(nbytes)
        if failed is not None:
            self._mark_peer_dead(dst, f"send failed: {failed}")

    def _direct_send(self, dst: int, tag: int, msg: Any) -> None:
        """comm.thread_multiple send path: write the frame to the peer
        socket from the CALLING thread. The per-peer lock keeps frames
        whole and is NEVER held across a wait: when the kernel buffer
        fills mid-frame the unsent remainder goes onto txbuf for the
        comm thread (framing stays intact — txbuf bytes always precede
        new frames). Waiting under the lock would stall the comm
        thread's _send_frame/_flush_sends; with two ranks symmetrically
        direct-sending large frames, both receive loops would stop
        draining and the ranks deadlock."""
        if self.fault is not None and self.fault.on_frame_sent():
            return                # injected silence (drop mode)
        if dst in self._dead_peers:
            return                # drop before paying the encode
        parts, nbytes = self._encode_parts(tag, msg)
        lock = self._send_locks[dst]
        s = self._socks.get(dst)
        queued = False
        failed: Optional[OSError] = None
        with lock:
            if dst in self._dead_peers or s is None:
                return            # drop, like the funnelled path
            pending = self._txbuf[dst]
            if pending:
                for p in parts:   # keep ordering behind queued bytes
                    pending += p
                queued = True
            else:
                # scatter-gather write; on a mid-frame send failure the
                # byte stream to this peer is desynchronized beyond
                # repair — tear the peer down (on the comm thread) so
                # later sends drop cleanly instead of framing garbage
                # after a partial frame
                failed = self._write_parts_locked(dst, s, parts)
                queued = bool(self._txbuf[dst])
        self._count_sent(nbytes)
        if failed is not None:
            self._post_cmd(("peer_dead", dst,
                            f"direct send failed: {failed}"))
        elif queued:                  # kick the comm thread to flush
            try:
                self._wake_w.send(b"x")
            except (BlockingIOError, OSError):
                pass

    def _flush_sends(self) -> int:
        """Push queued outbound bytes as far as the kernel accepts.
        Per-peer try-lock: under comm.thread_multiple a worker may be
        mid-direct-send; skipping the peer this iteration is cheaper
        than stalling the receive loop."""
        n = 0
        dead: List[Tuple[int, OSError]] = []
        for dst, buf in self._txbuf.items():
            if not buf or dst in self._dead_peers:
                continue
            lock = self._send_locks[dst]
            if not lock.acquire(blocking=False):
                continue
            try:
                try:
                    sent = self._socks[dst].send(buf)
                except BlockingIOError:
                    continue
                except OSError as exc:
                    # broken pipe / reset: retrying forever would pin
                    # these bytes and hide the failure — mark the peer
                    # (outside the send lock: _mark_peer_dead takes it)
                    dead.append((dst, exc))
                    continue
                if sent:
                    del buf[:sent]
                    n += sent
            finally:
                lock.release()
        for dst, exc in dead:
            self._mark_peer_dead(dst, f"send failed: {exc}")
        return n

    def _progress_recv(self, block_s: float) -> int:
        events = self._sel.select(timeout=block_s)
        n = 0
        for key, _mask in events:
            peer = key.data
            s = key.fileobj
            if peer == _WAKE_PEER:
                try:
                    s.recv(4096)      # drain wakeup tokens
                except (BlockingIOError, OSError):
                    pass
                continue
            if peer == _LISTEN_PEER:
                self._accept_rejoin(s)
                continue
            n += self._recv_ready(peer, s)
        return n

    _LARGE_FRAME = 32 * 1024

    def _recv_ready(self, peer: int, s: socket.socket) -> int:
        """Drain ``peer``'s readable socket completely. Small frames
        parse out of the staging rxbuf; a frame ≥ ``_LARGE_FRAME``
        switches to ``recv_into`` a preallocated frame buffer, so each
        payload byte is copied exactly once (kernel → frame) instead of
        the round-5 append-to-rxbuf + slice-out pair (two extra full
        copies per 1 MB frame), and the whole remainder arrives without
        one selector round trip per kernel-buffer chunk."""
        n = 0
        buf = self._rxbuf[peer]
        while True:
            large = self._rxlarge.get(peer)
            if large is not None:
                frame, filled = large
                try:
                    m = s.recv_into(memoryview(frame)[filled:])
                except BlockingIOError:
                    return n
                except OSError as exc:
                    self._peer_closed(peer, s, f"recv failed: {exc}")
                    return n
                if not m:
                    self._peer_closed(peer, s, "connection closed by peer")
                    return n
                filled += m
                if filled < len(frame):
                    large[1] = filled
                    continue          # keep draining; EAGAIN exits
                del self._rxlarge[peer]
                self._deliver_frame(frame)
                n += 1
                continue
            try:
                chunk = s.recv(1 << 18)
            except BlockingIOError:
                return n
            except OSError as exc:
                self._peer_closed(peer, s, f"recv failed: {exc}")
                return n
            if not chunk:
                self._peer_closed(peer, s, "connection closed by peer")
                return n
            buf += chunk
            while len(buf) >= _HDR.size:
                (ln,) = _HDR.unpack_from(buf, 0)
                if _HDR.size + ln <= len(buf):
                    # slicing a bytearray yields a (writable) bytearray —
                    # arrays reconstructed over the out-of-band views may
                    # be updated in place by bodies
                    frame = buf[_HDR.size:_HDR.size + ln]
                    del buf[:_HDR.size + ln]
                    self._deliver_frame(frame)
                    n += 1
                    continue
                if ln >= self._LARGE_FRAME:
                    frame = bytearray(ln)
                    have = len(buf) - _HDR.size
                    frame[:have] = memoryview(buf)[_HDR.size:]
                    del buf[:]
                    self._rxlarge[peer] = [frame, have]
                break

    def _deliver_frame(self, frame: bytearray) -> None:
        """Parse one complete frame and dispatch its AM."""
        (plen,) = _U32.unpack_from(frame, 0)
        off = _U32.size
        payload = frame[off:off + plen]
        off += plen
        # out-of-band buffers: zero-copy views into ``frame`` for
        # payloads that dominate the frame; smaller ones are copied out
        # so a retained array doesn't pin an entire aggregated
        # multi-payload frame in memory
        views: List[Any] = []
        ln = len(frame)
        while off < ln:
            (bl,) = _HDR.unpack_from(frame, off)
            off += _HDR.size
            if 2 * bl >= ln:
                views.append(memoryview(frame)[off:off + bl])
            else:
                views.append(bytearray(frame[off:off + bl]))
            off += bl
        tag, src, msg = pickle.loads(payload, buffers=views)
        self._stats["frames_recv"] += 1
        self._stats["bytes_recv"] += _HDR.size + ln
        self._dispatch(tag, src, msg)

    def _peer_closed(self, peer: int, s: socket.socket, why: str) -> None:
        """A peer's socket went away (comm thread). During orderly
        shutdown (_stop set: disable() is closing the mesh) just stop
        watching the fd; otherwise this is a failure — detect it."""
        try:
            self._sel.unregister(s)
        except (KeyError, ValueError):
            pass
        if self._stop.is_set():
            return      # orderly: we're stopping ourselves
        # BYE'd peers route through _mark_peer_dead too: its orderly
        # branch skips job-kill but still fails anything in flight
        # toward the departed peer (a silent drop would convert those
        # waits into timeouts)
        self._mark_peer_dead(peer, why)

    def _sweep_peer_inflight(self, peer: int, exc: BaseException) -> List:
        """Fail everything in flight that involves ``peer``: rendezvous
        GETs awaiting its PUT (both entry shapes carry the peer at
        index 2; "get"-kind callers see the error in the handle slot
        and their callback fires) and one-sided tile fetches targeting
        it. Returns the doomed _pending_gets entries so the caller can
        abort the taskpools of "activation"-kind ones."""
        doomed: List[Tuple] = []
        with self._mem_lock:
            for h, st in list(self._pending_gets.items()):
                if st[2] == peer:
                    doomed.append((h, self._pending_gets.pop(h)))
        for h, st in doomed:
            if st[0] == "get":
                with self._mem_lock:
                    self._mem[h] = exc
                st[1]()
        # segment streams fed by the dead peer can never complete —
        # their activations are in flight exactly like a pending GET
        # (comm-thread state, same thread as this sweep)
        self._rxlarge.pop(peer, None)
        for sid, state in list(self._rx_streams.items()):
            if state["src"] == peer:
                del self._rx_streams[sid]
                if state["tp"] is not None:
                    doomed.append(
                        (None, ("activation", state["tp"], peer,
                                state["msg"])))
                else:
                    # activation is PARKED (taskpool unknown): poison
                    # the parked msg so a later registration aborts the
                    # pool loudly instead of releasing its deps with a
                    # silent None payload
                    state["msg"]["failed"] = str(exc)
        with self._fetch_lock:
            for req, fut in list(self._fetch_futures.items()):
                if getattr(fut, "owner", None) == peer:
                    del self._fetch_futures[req]
                    fut.set(("error", str(exc)))
        return doomed

    def _on_bye(self, src: int, msg: Dict) -> None:
        # TCP delivers the BYE bytes before the FIN, so by the time the
        # zero-byte recv arrives the peer is already recorded here
        self._bye_peers.add(src)

    def _mark_peer_dead(self, peer: int, why: str) -> None:
        """Failure detection (comm thread only). The reference's MPI
        engine aborts the job on peer failure (default MPI error
        handler + parsec_abort, runtime.h:33-37); a silent unregister
        here would turn every dependent wait into a timeout. Record
        the death, fail every in-flight rendezvous/fetch/barrier that
        involves the peer, and abort active taskpools with a
        diagnostic naming it."""
        if peer in self._dead_peers or peer == self.rank:
            return
        self._dead_peers.add(peer)
        with self._rejoin_lock:
            # this slot may be re-admitted later (rejoin or elastic
            # slot reuse): a stale SET event from a previous admission
            # would make the next wait_rejoin return before the new
            # joiner actually connected
            self._rejoin_evts.pop(peer, None)
        s = self._socks.get(peer)
        if s is not None:
            try:
                self._sel.unregister(s)
            except (KeyError, ValueError):
                pass
            try:
                s.close()
            except OSError:
                pass
        lock = self._send_locks.get(peer)
        if lock is not None:
            with lock:
                self._txbuf[peer].clear()
        if peer in self._bye_peers:
            # the peer announced orderly shutdown: a send failing
            # against its closing socket (EPIPE on a late termdet ack)
            # is teardown, not death — no job-kill. On an elastic mesh
            # this IS the scale-down drain: the rank leaves the live
            # set but is recorded DEPARTED, never a failure
            # (_peer_failure stays None, no taskpool abort sweep, no
            # quarantine downstream). Anything still IN FLIGHT toward
            # that peer can never complete and must fail promptly (not
            # time out): sweep it with an orderly-shutdown diagnostic
            # and abort only the taskpools those entries belong to
            # (barriers stay untouched — see below).
            self._departed.add(peer)
            exc = ConnectionError(
                f"rank {self.rank}: peer rank {peer} shut down with "
                f"requests in flight ({why})")
            doomed = self._sweep_peer_inflight(peer, exc)
            if doomed:
                warning("comm", "%s — failing %d pending request(s)",
                        exc, len(doomed))
                for tp in {st[1] for (_h, st) in doomed
                           if st[0] == "activation"}:
                    tp.abort(exc)
            else:
                debug_verbose(2, "comm", "rank %d: post-BYE teardown "
                              "for peer %d (%s)", self.rank, peer, why)
            # in-flight termdet waves this rank coordinates can never
            # hear from the departed peer — shrink them to the live set
            # (same fail-safe as the death path: a partial wave can
            # only FAIL to terminate, never falsely terminate)
            for name, ws in list(self._waves.items()):
                if peer in ws.live and peer not in ws.replied:
                    ws.live.discard(peer)
                    ws.pending -= 1
                    if ws.pending == 0:
                        self._finish_wave(name, ws)
            # the live quorum shrank: a barrier of the NEW generation
            # may already be complete (entrants that processed this
            # departure first) — re-check
            self._maybe_release_barrier()
            # barriers of the OLD generation are NOT failed here:
            # whether a departed peer strands one is not locally
            # decidable (an already-entered peer doesn't — rank 0
            # still releases). A peer that BYEs without entering a
            # barrier others wait in is a collective-ordering bug; the
            # 60 s barrier timeout names that case.
            return
        exc = ConnectionError(
            f"rank {self.rank}: peer rank {peer} died ({why})")
        doomed = self._sweep_peer_inflight(peer, exc)
        # elastic recovery: re-open the wireup listener so a
        # replacement rank can adopt the dead slot (comm.rejoin)
        if not self._silenced and str(mca_param.cached_get(
                "comm.rejoin", 0)).lower() not in ("0", "off", "false"):
            self._open_rejoin_listener()
        # in-flight termdet waves this rank coordinates can never hear
        # from the dead peer — shrink them to the live set (a partial
        # wave can only FAIL to terminate, never falsely terminate:
        # sent == received still has to hold globally)
        for name, ws in list(self._waves.items()):
            if peer in ws.live and peer not in ws.replied:
                ws.live.discard(peer)
                ws.pending -= 1
                if ws.pending == 0:
                    self._finish_wave(name, ws)
        # barrier entries of the now-failed generation are NOT
        # reclaimed: waiters wake locally (above) and re-enter under
        # the next generation; the stale per-generation count can never
        # release a later barrier (release/entry are generation-tagged).
        # But entries for the NEW generation may already be complete
        # (entrants that detected this death first) — re-check.
        self._maybe_release_barrier()
        #
        # recovery exchanges in flight: ABORT them everywhere — local
        # waiters now, remote ones via an error result. Completing with
        # a shrunken contributor set would hand ranks that have not yet
        # detected this death a success whose completed-set omits the
        # dead rank's record, and their replay plan would diverge from
        # the ranks that restart with the larger dead set.
        with self._rejoin_lock:
            rfuts = list(self._recover_futs.values())
            self._recover_futs.clear()
        for fut in rfuts:
            if not fut.is_ready():
                fut.set(("error", f"peer rank {peer} died mid-exchange"))
        for token, st in list(self._recover_state.items()):
            if st["want"] is not None and peer in st["want"]:
                del self._recover_state[token]
                for r in st["want"]:
                    if r != peer:
                        self.send_am(AMTag.RECOVER, r,
                                     {"op": "result", "token": token,
                                      "error": f"rank {peer} died "
                                               f"mid-exchange"})
        # release a barrier this rank is blocked in (the dead peer can
        # never enter it) — sync() re-raises _peer_failure
        self._peer_failure = exc
        if self._barrier_waiting:
            self._barrier_release.set()
        # abort active taskpools so ctx.wait raises instead of hanging.
        # Serving isolation (ROADMAP item 4): a pool whose rank_scope
        # excludes the dead peer cannot have tasks, tiles or edges on
        # it — it keeps running, so one tenant's dead rank is a
        # per-taskpool failure unit, not a context-wide fail-stop.
        # scope None (the default) preserves the pre-serving behavior:
        # every pool aborts.
        ctx = self._context
        pools = []
        spared = 0
        if ctx is not None:
            with ctx._lock:
                for tp in ctx._active_taskpools:
                    scope = getattr(tp, "rank_scope", None)
                    if scope is not None and peer not in scope:
                        spared += 1
                        continue
                    pools.append(tp)
        affected = bool(pools or doomed)
        if affected or self._barrier_waiting:
            warning("comm", "%s — aborting %d taskpool(s) (%d scoped "
                    "pool(s) unaffected), failing %d pending get(s)",
                    exc, len(pools), spared, len(doomed))
        else:
            # nothing in flight (e.g. teardown race before _stop is
            # set locally): record quietly
            debug_verbose(2, "comm", "rank %d: peer %d gone (%s), "
                          "nothing in flight", self.rank, peer, why)
        for tp in pools:
            tp.abort(exc)

    def _dispatch(self, tag: int, src: int, msg: Any) -> None:
        cb = self._am_callbacks.get(tag)
        if cb is None:
            warning("comm", "rank %d: no handler for AM tag %d",
                    self.rank, tag)
            return
        try:
            cb(src, msg)
        except Exception as exc:    # noqa: BLE001 — comm thread must survive
            warning("comm", "rank %d: AM handler %d raised: %s",
                    self.rank, tag, exc)
            import traceback
            traceback.print_exc()
            from ..utils import debug_history
            debug_history.dump_on_fatal(
                f"rank {self.rank} AM handler tag={tag} raised")

    # ------------------------------------------------------------ send API
    def _thread_multiple(self) -> bool:
        # Never take the direct (potentially blocking) path FROM the
        # comm thread itself: an AM handler blocking in a send while
        # the peer does the same would deadlock both receive loops —
        # exactly the head-of-line hazard the non-blocking txbuf design
        # exists to prevent. Handler-originated sends stay funnelled.
        return self._thread is not None and \
            threading.get_ident() != getattr(self, "_comm_tid", None) and \
            bool(int(mca_param.cached_get("comm.thread_multiple", 0)))

    def send_am(self, tag: int, dst_rank: int, msg: Any) -> None:
        if dst_rank == self.rank:
            # self-sends are queued too, so EVERY handler runs on the comm
            # thread — handler state (waves, barriers, pending gets) is
            # single-threaded by construction, like the funnelled reference
            if self._thread is not None:
                self._post_cmd(("self", tag, msg))
            else:
                self._dispatch(tag, self.rank, msg)
            return
        if self._thread_multiple():
            self._direct_send(dst_rank, tag, msg)
            return
        if tag in (AMTag.GET_DATA, AMTag.PUT_DATA) and \
                threading.get_ident() == getattr(self, "_comm_tid", None):
            # rendezvous fast path: GET requests and PUT replies
            # originate on the comm thread (the activation/GET
            # handlers), which owns the sockets — sending inline skips
            # a command-queue round trip per rendezvous leg (two legs
            # per large payload; part of the round-5 +20% rdv_1M p50
            # regression). Restricted to the rendezvous request/reply
            # tags: they are handle-addressed, so overtaking frames
            # still queued for this peer cannot break any ordering
            # contract (per-peer ACTIVATE ordering stays queue-driven).
            self._send_frame(dst_rank, tag, msg)
            return
        self._post_cmd(("am", tag, dst_rank, msg))

    # ----------------------------------------------------------- one-sided
    @staticmethod
    def wire_value(value: Any, _dev_seen: Optional[list] = None) -> Any:
        """Snapshot device-resident values (jax.Array) to host numpy at
        the comm boundary — the calling worker thread pays the D2H sync,
        not the comm thread, and the wire then ships raw array bytes.
        (Reference: datatype pack/unpack, parsec_comm_engine.h:113-183.)
        numpy arrays, scalars and containers pass through; device arrays
        start their D2H ASYNCHRONOUSLY before any is awaited and are
        memoized by identity, so shared references snapshot (and pickle)
        once — see :func:`~.device_plane.snapshot_host`.
        ``_dev_seen``: a one-element list set True when any device array
        was snapshotted — the sender-side tag that tells the receiver
        this payload belongs on the device (stage_recv_value)."""
        return device_plane.snapshot_host(value, _dev_seen)

    def mem_register(self, buffer: Any) -> int:
        with self._mem_lock:
            h = (self.rank << 48) | self._mem_next
            self._mem_next += 1
            self._mem[h] = self.wire_value(buffer)
            return h

    def mem_unregister(self, handle: int) -> None:
        with self._mem_lock:
            self._mem.pop(handle, None)

    def put(self, local_handle: int, remote_rank: int, remote_handle: int,
            on_local_done: Optional[Callable] = None,
            on_remote_done_tag: Optional[int] = None) -> None:
        value = self._mem.get(local_handle)
        self.send_am(AMTag.PUT_DATA, remote_rank,
                     {"handle": remote_handle, "value": value,
                      "done_tag": on_remote_done_tag})
        self._stats["puts"] += 1
        self.record_msg("sent", "put", remote_rank,
                        self.payload_bytes(value))
        if on_local_done is not None:
            on_local_done()

    def get(self, remote_rank: int, remote_handle: int, local_handle: int,
            on_done: Optional[Callable] = None) -> None:
        self._stats["gets"] += 1
        self.record_msg("sent", "get", remote_rank, 0)
        # register the completion BEFORE the request leaves: the reply may
        # be processed before this function returns (self-rank inline path)
        if on_done is not None:
            with self._mem_lock:
                self._pending_gets[local_handle] = \
                    ("get", on_done, remote_rank)
        self.send_am(AMTag.GET_DATA, remote_rank,
                     {"remote_handle": remote_handle,
                      "reply_handle": local_handle})

    # --------------------------------------------------- remote-dep service
    def remote_dep_activate(self, task, ref, target_rank: int) -> None:
        """parsec_remote_dep_activate analog: enqueue one activation for
        the comm thread; value rides inline below the eager limit, else
        through the registered-memory rendezvous."""
        self.remote_dep_activate_multi(task, target_rank, [ref])

    @staticmethod
    def _encode_value(value) -> Tuple[bytes, List[Any], List[int], int]:
        """Protocol-5 split of a wire value: ``(head, raws, sizes,
        total)`` — the pickled control head plus the out-of-band raw
        buffers that a segment stream carries (the reference's datatype
        pack path, parsec_comm_engine.h:113-183)."""
        bufs: List[pickle.PickleBuffer] = []
        head = pickle.dumps(value, protocol=5, buffer_callback=bufs.append)
        raws = [b.raw() for b in bufs]
        sizes = [r.nbytes for r in raws]
        return head, raws, sizes, sum(sizes)

    @staticmethod
    def _segments(raws, seg_bytes: int):
        """Yield per-segment lists of memoryview slices over the
        concatenated ``raws`` — a virtual split, no copies."""
        out: List[Any] = []
        used = 0
        for r in raws:
            mv = r if isinstance(r, memoryview) else memoryview(r)
            off = 0
            while off < mv.nbytes:
                take = min(seg_bytes - used, mv.nbytes - off)
                out.append(mv[off:off + take])
                used += take
                off += take
                if used == seg_bytes:
                    yield out
                    out, used = [], 0
        if out:
            yield out

    def _new_sid(self) -> int:
        # globally unique across ranks (forwarders keep the root's sid,
        # so a stream id must never collide with another sender's)
        return (self.rank << 32) | next(self._sid_next)

    def _attach_stream(self, msg: Dict, value) -> Optional[List[Any]]:
        """Above-eager payloads become a pushed segment stream: the
        activation carries the stream header, the raw bytes follow as
        DATA_SEG frames (``comm.segment_bytes`` granularity). Returns
        the raw buffers to stream, or None when the value packed small
        (inline) — mutates ``msg`` accordingly."""
        eager_limit = int(mca_param.cached_get("comm.eager_limit",
                                               256 * 1024))
        head, raws, sizes, total = self._encode_value(value)
        if total <= eager_limit:
            msg["value"] = value      # head-heavy or small: inline
            return None
        sid = self._new_sid()
        msg["stream"] = {"sid": sid, "head": head, "sizes": sizes,
                         "nbytes": total}
        msg["nbytes"] = total
        return raws

    def _send_stream(self, dsts, sid: int, raws) -> None:
        """Stream the raw buffers to every rank in ``dsts`` as DATA_SEG
        frames, breadth-first: segment k reaches every child before
        k+1 leaves, so a forwarding chain overlaps its receive of k+1
        with the children's receive of k (the pipelined-rendezvous
        overlap; remote_dep_mpi.c:1963-2118's GET/PUT legs collapse
        into the stream). ``raws`` is either a raw-buffer list or a
        :class:`~.device_plane.DeviceStreamSource`, whose segments are
        resolved from async D2H fetches just before they ship — the
        pipelined device staging (D2H of k overlaps the send of k−1)."""
        seg_b = max(4096, int(mca_param.cached_get("comm.segment_bytes",
                                                   128 * 1024)))
        direct = self._thread_multiple()
        seg_iter = raws.segments(seg_b) if hasattr(raws, "segments") \
            else self._segments(raws, seg_b)
        for seq, views in enumerate(seg_iter):
            data = [pickle.PickleBuffer(v) for v in views]
            msg = {"sid": sid, "seq": seq, "data": data}
            seg_nb = sum(v.nbytes for v in views)
            for dst in dsts:
                with self._stats_lock:
                    self._stats["segs_sent"] += 1
                self.record_msg("sent", "seg", dst, seg_nb)
                if direct and dst != self.rank:
                    self._direct_send(dst, AMTag.DATA_SEG, msg)
                else:
                    self._post_cmd(("am", AMTag.DATA_SEG, dst, msg))

    def remote_dep_activate_multi(self, task, target_rank: int,
                                  refs) -> None:
        """Packed multi-target activation: N deps of ONE produced value
        to one rank ship the payload ONCE (the reference's one-data-per-
        (dep, rank) aggregation, remote_dep.c) — a PANEL factor fanning
        out to a whole wave of remote consumers would otherwise
        re-serialize the same array per consumer."""
        tp = task.taskpool
        monitor = tp.monitor
        monitor.outgoing_message_start(target_rank)
        targets = self._targets_of(refs)
        msg = {"taskpool": tp.name, "targets": targets}
        from ..utils import debug_history
        if debug_history.enabled():   # DEBUG_MARK_CTL_MSG_ACTIVATE_SENT
            for t in targets:
                debug_history.mark("ACTIVATE_SENT to=%d %s.%s%r flow=%s",
                                   target_rank, tp.name, t["class"],
                                   t["locals"], t["flow"])
        # per-peer aggregation orders same-drain activations by priority
        # (remote_dep_mpi.c:1089-1139) — a packed msg ranks by its most
        # urgent target
        msg["priority"] = max(t["priority"] for t in targets)
        rdv_push = str(mca_param.cached_get("comm.rdv_push", 1)).lower() \
            not in ("0", "off", "false")
        eager_limit = int(mca_param.cached_get("comm.eager_limit", 256 * 1024))
        raws = None
        src = device_plane.make_stream_source(
            refs[0].value, eager_limit, self._encode_value) \
            if rdv_push else None
        if src is not None:
            # pipelined device stream (comm.device_pipeline): the head
            # pickles _DevSlot placeholders, the bytes follow as
            # DATA_SEG frames resolved from ASYNC per-segment D2H — no
            # whole-value host snapshot ever happens
            sid = self._new_sid()
            msg["stream"] = {"sid": sid, **src.header()}
            msg["nbytes"] = nbytes = src.total
            msg["dev"] = True
            raws = src
        else:
            dev_seen = [False]
            value = self.wire_value(refs[0].value, dev_seen)
            if dev_seen[0]:
                # receiver stages this payload back onto its device (the
                # consumer side of a device-resident dataflow edge)
                msg["dev"] = True
            nbytes = self.payload_bytes(value)
            if value is not None and nbytes > eager_limit:
                if rdv_push:
                    raws = self._attach_stream(msg, value)
                else:
                    msg["value_handle"] = self.mem_register(value)
                    msg["nbytes"] = nbytes
            else:
                msg["value"] = value
        self.record_msg("sent", "activate", target_rank, nbytes)
        self._span_sent(self._span_attach(tp, task, msg), target_rank,
                        nbytes)
        if target_rank != self.rank and self._thread_multiple():
            # THREAD_MULTIPLE: the worker ships the activation itself
            # (one [msg] frame — direct sends skip per-peer aggregation,
            # like the reference's non-funnelled path)
            self._direct_send(target_rank, AMTag.ACTIVATE, [msg])
        else:
            self._post_cmd(("activate", target_rank, msg))
        if raws is not None:
            self._send_stream((target_rank,), msg["stream"]["sid"], raws)
        monitor.outgoing_message_end(target_rank)

    def remote_dep_broadcast(self, task, rank_refs) -> None:
        """Tree-routed data-plane broadcast (remote_dep.c:334-413
        analog): ONE produced value with consumers on >=2 ranks travels
        each tree edge exactly once. The root computes the participant
        list, every node rebuilds the identical tree from it
        (bcast_children over comm.bcast_topology/comm.bcast_fanout; DTD
        taskpools pin star), forwards to its children before releasing
        locally, and dead children are reparented — the payload still
        reaches their live subtrees."""
        tp = task.taskpool
        monitor = tp.monitor
        msg, parts, topo, fanout = self._bcast_envelope(tp, rank_refs)
        first = next(iter(rank_refs.values()))[0]
        rdv_push = str(mca_param.cached_get("comm.rdv_push", 1)).lower() \
            not in ("0", "off", "false")
        eager_limit = int(mca_param.cached_get("comm.eager_limit",
                                               256 * 1024))
        src = device_plane.make_stream_source(
            first.value, eager_limit, self._encode_value) \
            if rdv_push else None
        if src is not None:
            # pipelined device stream down the tree: forwarding nodes
            # re-send the raw segments WITHOUT restaging (bytes only —
            # no D2H/H2D round trip per hop); only local consumption
            # stages
            sid = self._new_sid()
            msg["stream"] = {"sid": sid, **src.header()}
            msg["nbytes"] = src.total
            msg["dev"] = True
            nbytes = src.total
            raws = src
        else:
            dev_seen = [False]
            value = self.wire_value(first.value, dev_seen)
            if dev_seen[0]:
                msg["dev"] = True
            nbytes = self.payload_bytes(value)
            if nbytes > eager_limit and not rdv_push:
                # comm.rdv_push=0 selects the classic registered-memory
                # GET/PUT protocol, which cannot pipeline a payload down
                # the tree (each hop would have to re-register and serve
                # its own GETs) — honor the knob: one packed classic
                # activation per consumer rank, no tree
                for target_rank, refs in rank_refs.items():
                    self.remote_dep_activate_multi(task, target_rank,
                                                   refs)
                return
            if nbytes > eager_limit:
                raws = self._attach_stream(msg, value)
            else:
                # below-eager: inline, without _attach_stream's
                # throwaway trial serialization
                msg["value"] = value
                raws = None
        children = bcast_live_children(topo, parts, self.rank, fanout,
                                       self.peer_alive)
        from ..utils import debug_history
        if debug_history.enabled():
            debug_history.mark("BCAST_ROOT %s parts=%r topo=%s kids=%r "
                               "nbytes=%d", tp.name, parts, topo.value,
                               children, nbytes)
        ctx = self._context
        if ctx is not None and ctx.pins is not None:
            ctx.pins.bcast_fwd(tp.name, -1, children, nbytes)
        direct = self._thread_multiple()
        bsp = self._span_attach(tp, task, msg)
        for c in children:
            monitor.outgoing_message_start(c)
            # one entry per tree edge at the logical payload size — the
            # "bcast" kind's sent_bytes at the root IS its data-plane
            # egress (the bench guard reads exactly this)
            self.record_msg("sent", "bcast", c, nbytes)
            self._span_sent(bsp, c, nbytes)
            if direct and c != self.rank:
                self._direct_send(c, AMTag.ACTIVATE, [msg])
            else:
                self._post_cmd(("activate", c, msg))
        if raws is not None:
            self._send_stream(children, msg["stream"]["sid"], raws)
        for c in children:
            monitor.outgoing_message_end(c)

    def install_activate_handler(self, context) -> None:
        """Register the runtime AM handlers (ACTIVATE / GET / PUT) — the
        remote_dep_mpi_save_activate_cb + get/put callback set."""
        self._context = context
        self.tag_register(AMTag.ACTIVATE, self._on_activate)
        self.tag_register(AMTag.GET_DATA, self._on_get)
        self.tag_register(AMTag.PUT_DATA, self._on_put)
        self.tag_register(AMTag.DATA_SEG, self._on_data_seg)
        self.tag_register(AMTag.DTD_CONTROL, self._on_dtd_control)

    def _find_taskpool(self, name: str):
        ctx = self._context
        with ctx._lock:
            return next((t for t in ctx._active_taskpools
                         if t.name == name), None)

    def _on_activate(self, src: int, msgs: List[Dict]) -> None:
        ctx = self._context
        for msg in msgs:
            if "stream" in msg:
                # reassembly state must exist BEFORE the taskpool check:
                # the stream's DATA_SEG frames are right behind this
                # frame on the socket, taskpool registered or not
                self._open_rx_stream(src, msg)
            # lookup AND park under the context lock: otherwise the
            # taskpool can register between the miss and the park and the
            # activation is orphaned (local.py does the same)
            with ctx._lock:
                tp = next((t for t in ctx._active_taskpools
                           if t.name == msg["taskpool"]), None)
                if tp is None:
                    # unknown-taskpool parking (remote_dep_mpi.c:1857-1869)
                    self._parked.setdefault(msg["taskpool"], []).append(
                        (src, msg))
                    continue
            self._deliver_activation(tp, src, msg)

    # ------------------------------------------------ segmented streams
    def _open_rx_stream(self, src: int, msg: Dict) -> Dict:
        st = msg["stream"]
        state = {"sid": st["sid"], "buf": bytearray(st["nbytes"]),
                 "got": 0, "nbytes": st["nbytes"], "head": st["head"],
                 "sizes": st["sizes"], "msg": msg, "src": src,
                 "tp": None, "fwd": (), "dev": st.get("dev"),
                 # pipelined H2D: device-slot bytes are device_put as
                 # their segments arrive (overlapping the receive of
                 # the next segment); the host buf still fills in
                 # parallel — forwarders and fallbacks read it
                 "stager": device_plane.make_stager(
                     st, tagged=msg.get("dev", False)),
                 "fetch": None}
        self._rx_streams[st["sid"]] = state
        return state

    def _on_data_seg(self, src: int, msg: Dict) -> None:
        self._stats["segs_recv"] += 1
        seg_nb = sum(d.nbytes if isinstance(d, memoryview) else len(d)
                     for d in msg["data"])
        self.record_msg("recv", "seg", src, seg_nb)
        state = self._rx_streams.get(msg["sid"])
        if state is None:
            return            # stream swept (peer death) — drop
        fwd = state["fwd"]
        if fwd:
            # pipelined tree edge: re-send segment k downstream BEFORE
            # copying it in — children receive k while k+1 is in flight
            out = {"sid": msg["sid"], "seq": msg["seq"],
                   "data": [pickle.PickleBuffer(d) for d in msg["data"]]}
            for c in fwd:
                with self._stats_lock:
                    self._stats["segs_sent"] += 1
                self.record_msg("sent", "seg", c, seg_nb)
                self._send_frame(c, AMTag.DATA_SEG, out)
        buf, got = state["buf"], state["got"]
        stager = state.get("stager")
        if stager is not None:
            stager.feed(got, msg["data"])
        for d in msg["data"]:
            n = d.nbytes if isinstance(d, memoryview) else len(d)
            buf[got:got + n] = d
            got += n
        state["got"] = got
        if got >= state["nbytes"]:
            self._finish_stream(state)

    def _finish_stream(self, state: Dict) -> None:
        self._rx_streams.pop(state["sid"], None)
        mv = memoryview(state["buf"])
        views: List[Any] = []
        off = 0
        for sz in state["sizes"]:
            views.append(mv[off:off + sz])
            off += sz
        value = pickle.loads(state["head"], buffers=views)
        if state.get("dev"):
            # device-slot resolution: the stager's on-device assemblies
            # where segments staged cleanly, host views over the
            # reassembly buffer otherwise (bit-identical either way)
            slots = device_plane.resolve_dev_slots(
                state["buf"], sum(state["sizes"]), state["dev"],
                state.get("stager"))
            value = device_plane.substitute_slots(value, slots)
        if state.get("fetch") is not None:
            # segmented TILE_FETCH reply: resolve the requester's future
            with self._fetch_lock:
                fut = self._fetch_futures.pop(state["fetch"], None)
            if fut is not None and not fut.is_ready():
                fut.set(("ok", value))
            return
        msg = state["msg"]
        msg.pop("stream", None)
        tp = state["tp"]
        if tp is None:
            # activation is parked (unknown taskpool): stash the value
            # in the SAME parked msg — taskpool_registered delivers it
            msg["value"] = value
            return
        self._finish_activation(tp, state["src"], msg, value)

    def _bcast_forward(self, tp, src: int, msg: Dict,
                       state: Optional[Dict]) -> None:
        """Receiver-side tree hop: rebuild the identical tree from the
        participant list, reparent dead children, forward the
        activation (and, for streams, the bytes received so far — live
        segments follow in _on_data_seg) BEFORE local release."""
        b = msg["bcast"]
        children = bcast_live_children(
            BcastTopology(b["topo"]), b["parts"], self.rank,
            b.get("fanout", 0), self.peer_alive)
        if not children:
            return
        nbytes = msg.get("nbytes",
                         self.payload_bytes(msg.get("value")))
        from ..utils import debug_history
        if debug_history.enabled():
            debug_history.mark("BCAST_FWD %s from=%d kids=%r nbytes=%d",
                               tp.name, src, children, nbytes)
        ctx = self._context
        if ctx is not None and ctx.pins is not None:
            ctx.pins.bcast_fwd(tp.name, src, children, nbytes)
        monitor = tp.monitor
        for c in children:
            monitor.outgoing_message_start(c)
            self.record_msg("sent", "bcast", c, nbytes)
            # forwarded tree edges keep the ROOT-minted span id — each
            # edge still gets its own sent/recv pair for the wire share
            self._span_sent(msg.get("span"), c, nbytes)
            # forwarding runs on the comm thread, which owns the
            # sockets: write the frame directly (ordering with the
            # stream catch-up + live segments below is per-socket FIFO)
            self._send_frame(c, AMTag.ACTIVATE, [msg])
        if state is not None:
            got = state["got"]
            if got:
                # catch-up: bytes that landed before the taskpool was
                # known re-stream as one segment; live ones follow
                catch = {"sid": state["sid"], "seq": -1,
                         "data": [pickle.PickleBuffer(
                             memoryview(state["buf"])[:got])]}
                for c in children:
                    with self._stats_lock:
                        self._stats["segs_sent"] += 1
                    self.record_msg("sent", "seg", c, got)
                    self._send_frame(c, AMTag.DATA_SEG, catch)
            state["fwd"] = tuple(children)
        for c in children:
            monitor.outgoing_message_end(c)

    def _deliver_activation(self, tp, src: int, msg: Dict) -> None:
        from ..utils import debug_history
        if "failed" in msg:
            # the payload stream died (peer gone) while this activation
            # was parked — its deps can never be satisfied
            tp.abort(ConnectionError(
                f"rank {self.rank}: activation from rank {src} lost "
                f"its payload stream: {msg['failed']}"))
            return
        targets = self._msg_targets(msg)
        if debug_history.enabled():   # DEBUG_MARK_CTL_MSG_ACTIVATE_RECV
            for t in targets:
                debug_history.mark("ACTIVATE_RECV from=%d %s.%s%r "
                                   "flow=%s", src, tp.name, t["class"],
                                   tuple(t["locals"]), t["flow"])
        kind = "bcast" if "bcast" in msg else "activate"
        self.record_msg("recv", kind, src,
                        msg.get("nbytes",
                                self.payload_bytes(msg.get("value"))))
        tp.monitor.incoming_message_start(src)
        state = None
        if "stream" in msg:
            state = self._rx_streams.get(msg["stream"]["sid"])
        if "bcast" in msg:
            # forward down the tree BEFORE releasing locally
            self._bcast_forward(tp, src, msg, state)
        if state is not None:
            # stream still in flight: completion finishes the
            # activation (incoming_message_end fires there)
            state["tp"] = tp
            return
        if "value_handle" in msg:
            # classic rendezvous (comm.rdv_push=0): allocate the receive
            # slot, GET the payload, and finish the activation when it
            # lands (get_start analog)
            with self._mem_lock:
                h = (self.rank << 48) | self._mem_next
                self._mem_next += 1
                self._pending_gets[h] = ("activation", tp, src, dict(msg))
            self.send_am(AMTag.GET_DATA, src,
                         {"remote_handle": msg["value_handle"],
                          "reply_handle": h})
            self._stats["gets"] += 1
            self.record_msg("sent", "get", src, 0)
            return
        self._finish_activation(tp, src, msg, msg.get("value"))

    @staticmethod
    def stage_recv_value(value: Any, tagged: bool = False):
        """Stage received array payloads onto the accelerator on the
        comm thread (async device_put): the consumer's body then starts
        from device-resident operands instead of paying a synchronous
        H2D at dispatch — the receive half of the reference's
        registered-memory PUT landing in device-visible memory
        (remote_dep_mpi.c:1594-1729). Gated by ``comm.stage_recv``
        through the shared :func:`~.device_plane.should_stage` gate:
        ``auto`` stages only payloads the SENDER tagged device-resident
        (``tagged``) on an accelerator backend — a host-born payload
        gains nothing from a device round trip its consumer did not ask
        for; ``1`` forces, ``0`` disables. Values already
        staged per segment by the pipelined rx path arrive as jax
        arrays and pass through untouched."""
        import numpy as np
        if not device_plane.should_stage(tagged):
            return value
        import jax

        def stage(v):
            if isinstance(v, np.ndarray) and v.nbytes >= 4096:
                try:
                    return jax.device_put(v)
                except Exception:  # noqa: BLE001 — staging is best-effort
                    return v
            if isinstance(v, tuple):
                return tuple(stage(x) for x in v)
            if isinstance(v, list):
                return [stage(x) for x in v]
            if isinstance(v, dict):
                return {k: stage(x) for k, x in v.items()}
            return v

        return stage(value)

    def _finish_activation(self, tp, src: int, msg: Dict, value) -> None:
        from ..core.taskpool import SuccessorRef
        value = self.stage_recv_value(value, tagged=msg.get("dev", False))
        targets = self._msg_targets(msg)
        ready = []
        for t in targets:               # one payload, N dependent tasks
            tc = tp.get_task_class(t["class"])
            ref = SuccessorRef(task_class=tc, locals=tuple(t["locals"]),
                               flow_name=t["flow"], value=value,
                               dep_index=t["dep_index"],
                               priority=t["priority"])
            new_task = tp.activate_dep(ref)
            if new_task is not None:
                ready.append(new_task)
        if "span" in msg and self._trace is not None:
            self._span_recv(msg, src,
                            msg.get("nbytes",
                                    self.payload_bytes(value)), ready)
        if ready:
            self._context.schedule(None, ready)
        tp.monitor.incoming_message_end(src)

    def _on_get(self, src: int, msg: Dict) -> None:
        """Sender side of the rendezvous: peer asks for a registered
        payload (remote_dep_mpi_save_put_cb → put_start analog)."""
        self.record_msg("recv", "get", src, 0)
        value = self._mem.get(msg["remote_handle"])
        self.mem_unregister(msg["remote_handle"])
        self.send_am(AMTag.PUT_DATA, src,
                     {"handle": msg["reply_handle"], "value": value})
        self._stats["puts"] += 1
        self.record_msg("sent", "put", src, self.payload_bytes(value))

    def _on_put(self, src: int, msg: Dict) -> None:
        """Receiver side: payload landed (get_end_cb analog)."""
        self.record_msg("recv", "put", src,
                        self.payload_bytes(msg.get("value")))
        with self._mem_lock:
            st = self._pending_gets.pop(msg["handle"], None)
        if st is None:
            self._mem[msg["handle"]] = msg["value"]
            return
        if st[0] == "activation":
            _, tp, asrc, amsg = st
            self._finish_activation(tp, asrc, amsg, msg["value"])
        elif st[0] == "get":
            self._mem[msg["handle"]] = msg["value"]
            st[1]()
        if msg.get("done_tag") is not None:
            self.send_am(msg["done_tag"], src, msg["handle"])

    # ------------------------------------------ one-sided tile fetch
    def _on_tile_fetch(self, src: int, msg: Any) -> None:
        """Socket upgrade of the base tile-fetch service: replies above
        the eager limit stream as DATA_SEG frames — device tiles leave
        through the same pipelined per-segment async D2H as activation
        payloads instead of one blocking whole-tile snapshot, and a
        requester that asked for staging (``fetch_tiles(stage=True)``,
        the HBM remote stage-in) reassembles them with per-segment H2D
        straight into device memory."""
        if msg.get("reply"):
            st = msg.get("stream")
            if st is not None:
                state = self._open_rx_stream(src, msg)
                state["fetch"] = msg["req"]
                with self._fetch_lock:
                    if not self._fetch_stage.pop(msg["req"], False):
                        state["stager"] = None
                return
            if "error" in msg:
                # the owner may have failed AFTER a stream-header reply
                # (mid-stream send error): drop any rx stream opened
                # for this request, or its reassembly buffer would
                # outlive the failed future forever
                for sid, state in list(self._rx_streams.items()):
                    if state.get("fetch") == msg["req"]:
                        del self._rx_streams[sid]
            return super()._on_tile_fetch(src, msg)
        rdv_push = str(mca_param.cached_get("comm.rdv_push", 1)).lower() \
            not in ("0", "off", "false")
        src_obj = None
        if rdv_push:
            try:
                ident = (msg.get("scope", ""), msg["name"])
                ref = self._exposed_colls.get(ident)
                dc = ref() if ref is not None else None
                if dc is not None:
                    eager_limit = int(mca_param.cached_get(
                        "comm.eager_limit", 256 * 1024))
                    src_obj = device_plane.make_stream_source(
                        dc.data_of(tuple(msg["key"])), eager_limit,
                        self._encode_value)
            except Exception:  # noqa: BLE001 — the base serve path
                src_obj = None  # owns lookup-error shaping
        if src_obj is None:
            # small/host/error cases: the base protocol (lookup, error
            # shaping, inline np reply) stays single-sourced
            return super()._on_tile_fetch(src, msg)
        try:
            sid = self._new_sid()
            reply = {"reply": True, "req": msg["req"], "dev": True,
                     "stream": {"sid": sid, **src_obj.header()}}
            self.send_am(AMTag.TILE_FETCH, src, reply)
            self._send_stream((src,), sid, src_obj)
        except Exception as exc:  # noqa: BLE001 — cross the wire, not die
            # the requester drops its half-open rx stream on this reply
            self.send_am(AMTag.TILE_FETCH, src,
                         {"reply": True, "req": msg["req"],
                          "error": str(exc)[:500]})

    def _on_dtd_control(self, src: int, msg: Dict) -> None:
        """Route DTD control messages (flush writebacks/acks) to the
        owning taskpool (terminated pools included — flush runs after
        wait)."""
        tp = self._context.find_taskpool(msg["taskpool"], active_only=False)
        if tp is None or not hasattr(tp, "_on_dtd_control"):
            warning("comm", "rank %d: DTD control for unknown taskpool %s",
                    self.rank, msg["taskpool"])
            return
        tp._on_dtd_control(src, msg)

    def taskpool_registered(self, tp):
        if self._peer_failure is not None:
            # the mesh is already broken: a taskpool with remote deps
            # would wait forever on the dead peer — fail it up front,
            # UNLESS its rank_scope avoids every dead rank (serving:
            # rank-local tenant pools keep launching while a broken
            # tenant's ranks are down). False tells Context.add_taskpool
            # to stop (no startup tasks, no on_enqueue) so nothing
            # launches into the dead mesh and termination doesn't fire
            # a second time
            scope = getattr(tp, "rank_scope", None)
            if scope is None or scope & set(self._dead_peers):
                tp.abort(ConnectionError(str(self._peer_failure)))
                return False
        # deliver ON THE COMM THREAD: a parked activation may have a
        # segment stream mid-reassembly there — delivering inline from
        # this (user) thread would race _on_data_seg/_finish_stream
        # over the stream state (lost segments between the catch-up
        # forward and the fwd-list install, or an attach to a state the
        # comm thread just popped). All _rx_streams access stays
        # comm-thread-only by construction.
        if self._thread is None:
            # no comm thread (single-rank / pre-enable): nothing can be
            # racing, and a queued command would never drain
            for (src, msg) in self._parked.pop(tp.name, []):
                self._deliver_activation(tp, src, msg)
        else:
            self._post_cmd(("deliver", tp))
        return True

    # ---------------------------------------------------- termdet services
    def register_termdet(self, name: str, monitor) -> None:
        monitor._termdet_name = name
        self._termdet_monitors[name] = monitor

    def _live_ranks(self) -> List[int]:
        """Every rank not known dead (self included) — the participant
        set of waves, barriers and recovery exchanges after a failure.
        The full mesh when nothing died."""
        return [r for r in range(self.nb_ranks)
                if r == self.rank or r not in self._dead_peers]

    def _td_coordinator(self) -> int:
        """Wave/barrier coordinator: the lowest LIVE rank (rank 0
        unless it died — survivor-side continuation must not wedge on a
        dead coordinator)."""
        return self._live_ranks()[0]

    def start_termdet_wave(self, monitor) -> None:
        """Fourcounter wave, the lowest live rank coordinating (the
        reference builds the wave over its own AM tag,
        termdet/fourcounter)."""
        name = getattr(monitor, "_termdet_name", None)
        if name is None:
            monitor.wave_result(0, 1, False)
            return
        self.send_am(AMTag.TERMDET_FOURCOUNTER, self._td_coordinator(),
                     {"op": "request", "name": name})

    def _finish_wave(self, name: str, ws: _WaveState) -> None:
        if self._waves.get(name) is ws:
            del self._waves[name]
        for r in ws.live:
            self.send_am(AMTag.TERMDET_FOURCOUNTER, r,
                         {"op": "result", "name": name,
                          "sent": ws.sent, "received": ws.received,
                          "idle": ws.all_idle})

    def _on_termdet(self, src: int, msg: Dict) -> None:
        op = msg["op"]
        name = msg["name"]
        if op == "request":                      # coordinator: maybe launch
            if name in self._waves:
                return                           # wave already in flight
            self._wave_next_id += 1
            ws = _WaveState(name, self._wave_next_id, self._live_ranks())
            self._waves[name] = ws
            for r in sorted(ws.live):
                self.send_am(AMTag.TERMDET_FOURCOUNTER, r,
                             {"op": "query", "name": name,
                              "wave_id": ws.wave_id})
        elif op == "query":                      # participant: contribute
            mon = self._termdet_monitors.get(name)
            if mon is None:
                sent, received, idle = 0, 0, False
            else:
                sent, received, idle = mon.local_wave_contribution()
            self.send_am(AMTag.TERMDET_FOURCOUNTER, src,
                         {"op": "reply", "name": name,
                          "wave_id": msg["wave_id"], "sent": sent,
                          "received": received, "idle": idle})
        elif op == "reply":                      # coordinator: collect
            ws = self._waves.get(name)
            if ws is None or ws.wave_id != msg["wave_id"] or \
                    src in ws.replied:
                return
            ws.replied.add(src)
            ws.sent += msg["sent"]
            ws.received += msg["received"]
            ws.all_idle = ws.all_idle and msg["idle"]
            ws.pending -= 1
            if ws.pending == 0:
                self._finish_wave(name, ws)
        elif op == "result":                     # everyone: apply
            mon = self._termdet_monitors.get(name)
            if mon is not None:
                mon.wave_result(msg["sent"], msg["received"], msg["idle"])

    def broadcast_user_trigger(self, monitor) -> None:
        name = getattr(monitor, "_termdet_name", None)
        if name is None:
            return
        for r in range(self.nb_ranks):
            if r != self.rank:
                self.send_am(AMTag.TERMDET_USER_TRIGGER, r, {"name": name})

    def _on_trigger(self, src: int, msg: Dict) -> None:
        mon = self._termdet_monitors.get(msg["name"])
        if mon is not None:
            mon.trigger(propagate=False)

    # -------------------------------------------------------------- extras
    def sync(self) -> None:
        """Barrier over the control channel: rank 0 counts entries, then
        releases everyone. The handler is registered once (install time)
        and its state lives on the comm thread, so back-to-back barriers
        cannot drop a fast peer's early 'enter'."""
        if self.nb_ranks <= 1:
            return
        self._barrier_release.clear()
        # order matters: _barrier_waiting must be visible BEFORE the
        # failure check — a death landing between the check and the
        # flag would otherwise never release this wait
        self._barrier_waiting = True
        try:
            if self._peer_failure is not None:
                # a dead peer can never enter the barrier — fail fast
                raise ConnectionError(str(self._peer_failure))
            self._barrier_gen = self._barrier_generation()
            self.send_am(AMTag.BARRIER, self._td_coordinator(),
                         {"op": "enter", "gen": self._barrier_gen})
            released = self._barrier_release.wait(timeout=60.0)
            if self._peer_failure is not None:   # checked first: a peer
                raise ConnectionError(           # death IS the timeout's
                    str(self._peer_failure))     # usual cause
            if not released:
                raise TimeoutError(f"rank {self.rank}: barrier timed out")
        finally:
            self._barrier_waiting = False

    def _on_barrier(self, src: int, msg: Dict) -> None:
        # comm-thread only (all handlers are); the collector is the
        # lowest live rank and the quorum is the LIVE set of the
        # CURRENT generation — a shrunk mesh still synchronizes
        # (post-recovery collectives) while a pre-failure barrier's
        # abandoned entries stay quarantined in their own generation
        if msg["op"] == "enter":
            g = msg.get("gen", 0)
            self._barrier_counts[g] = self._barrier_counts.get(g, 0) + 1
            self._maybe_release_barrier()
        elif msg.get("gen", 0) == self._barrier_gen:
            self._barrier_release.set()

    def _barrier_generation(self):
        """Barrier/quorum generation: (deaths+departures, world size).
        A death, a drain, AND an elastic grow each change the live
        quorum — entries from before any of them stay quarantined in
        their own generation and can never release a post-rescale
        barrier early (or vice versa)."""
        return (len(self._dead_peers), self.nb_ranks)

    def _maybe_release_barrier(self) -> None:
        """Release ANY generation whose quorum is in (comm thread;
        re-checked when a death/departure/grow changes the live set).
        A generation ``(deaths, world)`` had live quorum
        ``world − deaths`` when it was current — checking every
        bucket against its OWN quorum releases a barrier whose
        entrants ALL entered before a grow was admitted (the common
        overlap: admission is a point event, barriers entered just
        before it would otherwise stall against the post-grow quorum
        until the 60 s timeout). KNOWN LIMIT: entrants split ACROSS
        the admission instant land in different buckets ((d, w) vs
        (d, w+1)) and neither reaches quorum — that barrier times out
        loudly and the caller retries; merging buckets here would risk
        a false early release against stale abandoned entries. The
        elastic controller therefore serializes rescales against its
        own collective ops. Releases are generation-tagged, so a
        stale bucket firing can never wake a waiter of a different
        generation."""
        for g, cnt in list(self._barrier_counts.items()):
            if not cnt:
                continue
            quorum = max(1, g[1] - g[0]) if isinstance(g, tuple) \
                else len(self._live_ranks())
            if cnt >= quorum:
                self._barrier_counts[g] = 0
                for r in self._live_ranks():
                    self.send_am(AMTag.BARRIER, r,
                                 {"op": "release", "gen": g})

    def peer_alive(self, rank: int) -> bool:
        return rank not in self._dead_peers

    # ------------------------------------------------ clock alignment
    def _on_clock(self, src: int, msg: Dict) -> None:
        """CLOCK AM handler (comm thread): answer pings with this
        process's perf_counter; route pongs to the waiting Future."""
        if msg.get("op") == "ping":
            self.send_am(AMTag.CLOCK, src,
                         {"op": "pong", "req": msg["req"],
                          "t_remote": time.perf_counter()})
            return
        fut = self._clock_futs.pop(msg["req"], None)
        if fut is not None and not fut.is_ready():
            fut.set(msg["t_remote"])

    def clock_offset_to(self, peer: int, samples: int = 7,
                        timeout: float = 5.0) -> Tuple[float, float]:
        """Pingpong clock handshake against ``peer``: returns
        ``(offset_s, rtt_s)`` where offset_s added to this process's
        ``perf_counter`` lands in the peer's domain. NTP-style midpoint
        estimate per sample (t_remote − (t_send + t_recv)/2), keeping
        the minimum-RTT sample — the one with the least asymmetric
        queueing. Cached per peer (the mesh's relative clock drift over
        a trace's lifetime is far below the RTT noise floor)."""
        if peer == self.rank or self.nb_ranks <= 1:
            return 0.0, 0.0
        cached = self._clock_cache.get(peer)
        if cached is not None:
            return cached
        if self._thread is None:
            # comm thread down (pre-enable / post-disable): a ping could
            # never be answered — dump traces BEFORE fini to get offsets
            raise RuntimeError("clock handshake needs the comm thread "
                               "(dump traces before disable/fini)")
        from ..core.future import Future
        best: Optional[Tuple[float, float]] = None
        for _ in range(max(samples, 1)):
            fut = Future()
            req = next(self._clock_next)
            self._clock_futs[req] = fut
            t0 = time.perf_counter()
            self.send_am(AMTag.CLOCK, peer, {"op": "ping", "req": req})
            try:
                t_remote = fut.get(timeout=timeout)
            finally:
                self._clock_futs.pop(req, None)
            t3 = time.perf_counter()
            rtt = t3 - t0
            off = t_remote - (t0 + t3) / 2.0
            if best is None or rtt < best[1]:
                best = (off, rtt)
        self._clock_cache[peer] = best
        return best

    def clock_meta(self, root: int = 0) -> Dict[str, float]:
        """Trace metadata block: the wire-measured offset to the root
        rank's perf_counter domain + the handshake RTT (the alignment
        error bound the multi-rank merge inherits)."""
        if self.rank == root or self.nb_ranks <= 1 or \
                not self.peer_alive(root):
            return {"clock_offset_s": 0.0, "clock_rtt_us": 0.0}
        off, rtt = self.clock_offset_to(root)
        return {"clock_offset_s": off,
                "clock_rtt_us": round(rtt * 1e6, 1)}

    # ------------------------------------------------- recovery exchange
    def recover_exchange(self, token: str, payload: Any, dead_ranks,
                         timeout: float = 60.0) -> Dict[int, Any]:
        """Allgather ``payload`` across the live rank set (everyone
        minus ``dead_ranks``): the completed-set exchange survivors run
        before planning a replay. All live ranks must call with the
        SAME token and dead set; the lowest live rank coordinates. A
        further peer death mid-exchange fails every waiter promptly —
        the caller restarts recovery with the larger dead set."""
        if self.nb_ranks <= 1:
            return {self.rank: payload}
        from ..core.future import Future
        dead = {int(r) for r in dead_ranks}
        live = [r for r in range(self.nb_ranks) if r not in dead]
        if self.rank not in live:
            raise RuntimeError(f"rank {self.rank} is in the dead set")
        fut = Future()
        with self._rejoin_lock:
            if token in self._recover_futs:
                raise RuntimeError(f"recovery exchange {token!r} "
                                   f"already in flight")
            self._recover_futs[token] = fut
        self.send_am(AMTag.RECOVER, live[0],
                     {"op": "contrib", "token": token,
                      "rank": self.rank, "want": live, "data": payload})
        try:
            status, value = fut.get(timeout=timeout)
        finally:
            with self._rejoin_lock:
                self._recover_futs.pop(token, None)
        if status != "ok":
            raise ConnectionError(
                f"recovery exchange {token!r} failed: {value}")
        return value

    def _on_recover(self, src: int, msg: Dict) -> None:
        # comm-thread only (all handlers are)
        token = msg["token"]
        if msg["op"] == "contrib":
            st = self._recover_state.setdefault(
                token, {"got": {}, "want": None})
            st["got"][msg["rank"]] = msg["data"]
            if st["want"] is None:
                st["want"] = set(msg["want"])
            self._maybe_finish_recover(token, st)
            return
        with self._rejoin_lock:
            fut = self._recover_futs.get(token)
        if fut is not None and not fut.is_ready():
            if "error" in msg:
                fut.set(("error", msg["error"]))
            else:
                fut.set(("ok", msg["data"]))

    def _maybe_finish_recover(self, token: str, st: Dict) -> None:
        want = st["want"]
        if want is None or not set(st["got"]) >= want:
            return
        del self._recover_state[token]
        data = {r: st["got"][r] for r in sorted(want)}
        for r in sorted(want):
            self.send_am(AMTag.RECOVER, r,
                         {"op": "result", "token": token, "data": data})

    def world_status(self) -> Dict[str, Any]:
        """Capacity view of the rank set (statusz + elastic
        controller): configured = the world size this engine was BUILT
        with, world = the current (possibly grown) size; departed =
        orderly drains (scale-down / BYE), dead = failures. Reads are
        GIL-snapshot views of comm-thread state — consistent enough
        for an operator surface."""
        departed = set(self._departed)
        dead = set(self._dead_peers) - departed
        return {"configured": self._nb_ranks0,
                "world": self.nb_ranks,
                "live": self._live_ranks(),
                "departed": sorted(departed),
                "dead": sorted(dead)}

    def wire_stats(self) -> Dict[str, int]:
        """Frame-level wire counters (header+payload bytes on the socket);
        payload-level activation counters live in the base ``stats`` dict
        shared with every engine (remote_dep.h:355-365 analog)."""
        return dict(self._stats)
