"""Device-direct data plane: pipelined accelerator staging for the wire.

The reference runtime pipelines accelerator staging against the network
(remote_dep_mpi.c overlaps GPU D2H segments with the MPI sends of the
segments already on host); the round-5 engine instead snapshotted the
WHOLE device value to host in one blocking D2H before the first byte hit
the wire (``SocketCommEngine.wire_value``) and restaged only after full
reassembly (``stage_recv_value``). This module closes that gap on three
fronts:

- **Pipelined sender staging** (:func:`make_stream_source`): a device
  payload above the eager limit ships as the existing ``DATA_SEG``
  stream, but its raw bytes are produced per segment from ASYNC device
  fetches (``copy_to_host_async`` issued for every segment up front, so
  D2H of segment k overlaps the wire send of k−1). The pickled stream
  head carries :class:`_DevSlot` placeholders instead of materialized
  arrays — identity-deduped, so a value referenced twice in a container
  crosses the wire once.
- **Pipelined receiver staging** (:class:`SegmentStager`): segments of a
  device-tagged stream are ``device_put`` as they arrive (H2D of
  segment k overlaps the receive of k+1) and assembled ON DEVICE at
  stream completion; the host byte buffer is still filled in parallel,
  so broadcast-forwarding nodes forward raw bytes without restaging and
  any unstageable slot falls back to the classic host path bit-exactly.
- **Same-mesh direct transfers** (:func:`direct_device_for`): when both
  endpoints of a dep sit on one JAX mesh (the loopback fabric — one
  process, per-rank devices of a registered comm mesh,
  ``compiled/spmd.py``), the tile moves as an XLA device-to-device
  ``device_put`` and only a control frame is accounted — the payload
  never touches host memory.

Knobs (both default to the new paths; ``0`` preserves the round-5
bit-exact behavior — the A/B baseline, same pattern as ``comm.rdv_push``):

- ``comm.device_pipeline = auto|0|1`` — segmented async D2H/H2D overlap.
- ``comm.device_direct = auto|0|1`` — same-mesh device-to-device routing;
  ``auto`` engages only when a comm mesh is registered
  (:func:`~parsec_tpu.compiled.spmd.register_comm_mesh`), ``1`` forces a
  round-robin map over the visible devices.

Nothing here initializes an accelerator backend: every entry point
no-ops unless ``jax`` is already imported by the process (the same
comm-thread rule ``stage_recv_value`` follows).
"""

from __future__ import annotations

import sys
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..utils import mca_param

mca_param.register("comm.device_pipeline", "auto",
                   help="segment device payloads on the comm.segment_"
                        "bytes lattice and overlap D2H of segment k "
                        "with the send of k-1 (async device_get per "
                        "segment), H2D of segment k with the receive "
                        "of k+1 (per-segment device_put): auto/1 = on, "
                        "0 = the round-5 whole-value snapshot/restage "
                        "path (bit-exact A/B baseline)")
mca_param.register("comm.device_direct", "auto",
                   help="route deps whose endpoints sit on one JAX "
                        "mesh as device-to-device transfers (payload "
                        "never touches the host; only a control frame "
                        "is accounted): auto = on when a comm mesh is "
                        "registered (compiled.spmd.register_comm_mesh),"
                        " 1 = force (round-robin over visible devices),"
                        " 0 = off")

# device-raw alignment in the stream layout: every device slot starts at
# a multiple of this, so per-segment H2D chunks stay element-aligned for
# every numeric itemsize (complex128 = 16 is the widest)
_ALIGN = 16
# element itemsizes the segment cutter understands; anything else falls
# back to the host snapshot path
_ITEMSIZES = (1, 2, 4, 8, 16)


def _off(mode: str) -> bool:
    return str(mode).lower() in ("0", "off", "false")


def pipeline_mode() -> str:
    """``comm.device_pipeline`` resolution: ``"off"`` | ``"auto"`` |
    ``"force"``. Auto and force both enable the device-stream wire
    format; they differ in the CUT strategy (see
    :meth:`DeviceStreamSource.segments`)."""
    mode = str(mca_param.cached_get("comm.device_pipeline",
                                    "auto")).lower()
    if _off(mode):
        return "off"
    return "auto" if mode == "auto" else "force"


def pipeline_enabled() -> bool:
    """``comm.device_pipeline`` gate (auto == on — the knob exists for
    the A/B baseline, not capability detection: the pipelined paths
    degrade to the classic ones wherever async staging cannot apply)."""
    return pipeline_mode() != "off"


def per_segment_fetch() -> bool:
    """Cut strategy of the sender-side device stream: per-SEGMENT
    device fetches overlap D2H with the wire, but each slice is an
    eager accelerator dispatch — pure overhead on the CPU backend,
    where "D2H" is a memcpy (measured: +~1 ms on the 64 KB hop). Auto
    therefore slices per segment only on real accelerators and falls
    back to ONE whole-array async copy on CPU (still async-started,
    still zero-snapshot wire format); ``comm.device_pipeline=1``
    forces per-segment cutting everywhere (the tests' determinism
    hook)."""
    mode = pipeline_mode()
    if mode == "force":
        return True
    jax = _jax()
    if jax is None:
        return False
    return jax.default_backend() != "cpu"


def _jax():
    """The jax module IFF the process already imported it. Host-only
    rank fleets pin the CPU platform (``benchenv.pin_wire_bench_env``),
    so a backend question asked from the comm thread cannot claim a
    chip there."""
    return sys.modules.get("jax")


def is_device_array(v: Any) -> bool:
    jax = _jax()
    return jax is not None and isinstance(v, jax.Array)


def has_device(value: Any) -> bool:
    """Does ``value`` contain any device-resident array (container-
    recursive)? False whenever jax is not loaded."""
    if is_device_array(value):
        return True
    if isinstance(value, (tuple, list)):
        return any(has_device(v) for v in value)
    if isinstance(value, dict):
        return any(has_device(v) for v in value.values())
    return False


def start_host_copy(arr: Any) -> None:
    """Kick off an async D2H for ``arr`` (best-effort): the later
    ``np.asarray`` blocks only for the remainder of the transfer."""
    try:
        arr.copy_to_host_async()
    except Exception:  # noqa: BLE001 — async start is an optimization
        pass


def snapshot_host(value: Any, _dev_seen: Optional[list] = None) -> Any:
    """The ``wire_value`` core: snapshot device-resident values to host
    numpy at the comm boundary, containers recursed, everything else
    passed through. Two upgrades over the round-5 walk: (1) every
    device array's D2H is STARTED asynchronously before any is awaited,
    so a container of N device tiles overlaps N transfers instead of
    serializing them; (2) device arrays are memoized by identity — a
    value referenced twice snapshots once and the wire (protocol-5
    pickle memo) then carries its bytes once."""
    devs: List[Any] = []
    seen: set = set()

    def collect(v):
        if is_device_array(v):
            if id(v) not in seen:
                seen.add(id(v))
                devs.append(v)
        elif isinstance(v, (tuple, list)):
            for x in v:
                collect(x)
        elif isinstance(v, dict):
            for x in v.values():
                collect(x)

    collect(value)
    for a in devs:
        start_host_copy(a)
    memo: Dict[int, np.ndarray] = {}

    def convert(v):
        if v is None or isinstance(
                v, (bool, int, float, complex, str, bytes, bytearray,
                    np.ndarray, np.generic)):
            return v
        if isinstance(v, tuple):
            return tuple(convert(x) for x in v)
        if isinstance(v, list):
            return [convert(x) for x in v]
        if isinstance(v, dict):
            return {k: convert(x) for k, x in v.items()}
        if hasattr(v, "__array__"):          # jax.Array et al.
            if _dev_seen is not None:
                _dev_seen[0] = True
            got = memo.get(id(v))
            if got is None:
                got = memo[id(v)] = np.asarray(v)
            return got
        return v

    return convert(value)


# ---------------------------------------------------------------------------
# sender side: container extraction + segmented async D2H stream source
# ---------------------------------------------------------------------------

class _DevSlot:
    """Pickled placeholder of one device array in a stream head: the
    array's bytes travel as aligned regions of the DATA_SEG stream
    (described by the stream header's ``dev`` metadata), never through
    the pickle."""

    __slots__ = ("i",)

    def __init__(self, i: int):
        self.i = i

    def __reduce__(self):
        return (_DevSlot, (self.i,))


def _streamable(arr) -> bool:
    """May ``arr`` be shipped as a segmented device stream slot? Needs
    a single addressable placement (a committed multi-device array
    would gather per slice) and a plain numeric itemsize."""
    try:
        if int(np.dtype(arr.dtype).itemsize) not in _ITEMSIZES:
            return False
        shards = getattr(arr, "sharding", None)
        if shards is not None and len(shards.device_set) > 1:
            return False
        return True
    except Exception:  # noqa: BLE001 — be conservative, fall back
        return False


def extract_device(value: Any) -> Tuple[Any, List[Any], bool]:
    """Split a wire value into ``(skeleton, dev_arrays, dev_seen)``:
    device arrays become identity-deduped :class:`_DevSlot` markers (so
    shared references reassemble shared), unstreamable device arrays
    are host-snapshotted in place (async-started first by the caller's
    snapshot pass), host leaves pass through untouched."""
    slots: Dict[int, _DevSlot] = {}
    arrs: List[Any] = []
    seen_dev = [False]
    memo: Dict[int, np.ndarray] = {}

    def walk(v):
        if is_device_array(v):
            seen_dev[0] = True
            if _streamable(v):
                slot = slots.get(id(v))
                if slot is None:
                    slot = slots[id(v)] = _DevSlot(len(arrs))
                    arrs.append(v)
                return slot
            got = memo.get(id(v))
            if got is None:
                start_host_copy(v)
                got = memo[id(v)] = np.asarray(v)
            return got
        if isinstance(v, tuple):
            return tuple(walk(x) for x in v)
        if isinstance(v, list):
            return [walk(x) for x in v]
        if isinstance(v, dict):
            return {k: walk(x) for k, x in v.items()}
        return v

    return walk(value), arrs, seen_dev[0]


def substitute_slots(skeleton: Any, values: List[Any]) -> Any:
    """Inverse of :func:`extract_device` on the receiver: replace each
    :class:`_DevSlot` with its reassembled value (index-shared slots
    resolve to the SAME object — the dedup round-trips)."""
    def walk(v):
        if isinstance(v, _DevSlot):
            return values[v.i]
        if isinstance(v, tuple):
            return tuple(walk(x) for x in v)
        if isinstance(v, list):
            return [walk(x) for x in v]
        if isinstance(v, dict):
            return {k: walk(x) for k, x in v.items()}
        return v

    return walk(skeleton)


class DeviceStreamSource:
    """Sender half of a pipelined device stream: owns the pickled
    skeleton head, the host raw buffers, and the device arrays whose
    bytes are produced per segment from async D2H fetches.

    Layout of the byte stream (``total`` bytes):
    ``[host raws, concatenated][pad→16][dev0][pad→16][dev1]...`` —
    device slots are 16-byte aligned so every per-segment chunk cut at
    an element boundary on the sender re-cuts at an element boundary on
    the receiver (:class:`SegmentStager`)."""

    def __init__(self, head: bytes, host_raws: List[Any],
                 host_sizes: List[int], arrs: List[Any],
                 metas: List[Dict], total: int):
        self.head = head
        self.host_raws = host_raws
        self.host_sizes = host_sizes
        self.arrs = arrs
        self.metas = metas
        self.total = total

    def header(self) -> Dict[str, Any]:
        """The ``msg["stream"]`` fields beyond sid (the caller mints
        the sid — it owns the engine's counter)."""
        return {"head": self.head, "sizes": list(self.host_sizes),
                "nbytes": self.total, "dev": self.metas}

    def segments(self, seg_bytes: int):
        """Yield per-segment buffer lists (the ``_send_stream``
        contract). Host raws stream first (zero-copy views); each
        device slot's bytes follow as element-aligned chunks resolved
        from ASYNC D2H fetches — all fetches are started before the
        first yield, so while segment k's bytes are on the wire the
        device is already pushing k+1..n to host (max(link, copy)
        instead of link + copy).

        Two cut strategies (:func:`per_segment_fetch`): on real
        accelerators each chunk is its OWN device slice + async copy
        (finest overlap granularity); on CPU one whole-array async
        copy is started per slot and the chunks are zero-copy views
        over its host buffer
        (the slicing dispatches would cost more than the memcpy they
        overlap)."""
        seg_bytes = max(int(seg_bytes), _ALIGN)
        per_seg = per_segment_fetch()
        # plan every chunk first so the async copies cover the tail of
        # the stream while its head is being sent
        plans: List[Tuple] = []  # ("buf",mv) | ("dev",slice) |
        #                          ("devw", arr, byte_off, nbytes)
        used = 0                 # bytes in the current segment

        def account(n):
            nonlocal used
            used = (used + n) % seg_bytes

        for r in self.host_raws:
            mv = r if isinstance(r, memoryview) else memoryview(r)
            mv = mv.cast("B") if mv.ndim != 1 or mv.itemsize != 1 else mv
            off = 0
            while off < mv.nbytes:
                take = min(seg_bytes - used, mv.nbytes - off)
                plans.append(("buf", mv[off:off + take]))
                account(take)
                off += take
        for arr, meta in zip(self.arrs, self.metas):
            if meta["pad"]:
                plans.append(("buf", memoryview(b"\x00" * meta["pad"])))
                account(meta["pad"])
            isz = int(np.dtype(arr.dtype).itemsize)
            if per_seg:
                flat = arr.reshape(-1)
                nelt = int(flat.shape[0]) if flat.shape else 1
            else:
                start_host_copy(arr)     # ONE async copy for the slot
                nelt = meta["nbytes"] // isz
            e = 0
            while e < nelt:
                room = seg_bytes - used
                take_e = min(max(room // isz, 1), nelt - e)
                if per_seg:
                    piece = flat[e:e + take_e]
                    start_host_copy(piece)
                    plans.append(("dev", piece))
                else:
                    plans.append(("devw", arr, e * isz, take_e * isz))
                account(take_e * isz)
                e += take_e
        # emit: group planned chunks into seg_bytes frames, resolving
        # device chunks (np.asarray blocks only until THAT chunk's —
        # or, whole-array mode, that SLOT's — async copy lands) just
        # before their segment ships
        out: List[Any] = []
        used = 0
        hosts: Dict[int, Any] = {}       # whole-array mode memo
        for plan in plans:
            kind = plan[0]
            if kind == "dev":
                obj = np.asarray(plan[1])
            elif kind == "devw":
                _k, arr, boff, bn = plan
                host = hosts.get(id(arr))
                if host is None:
                    host = hosts[id(arr)] = memoryview(
                        np.ascontiguousarray(np.asarray(arr))).cast("B")
                obj = host[boff:boff + bn]
            else:
                obj = plan[1]
            out.append(obj)
            used += obj.nbytes
            if used >= seg_bytes:
                yield out
                out, used = [], 0
        if out:
            yield out


def make_stream_source(value: Any, eager_limit: int,
                       encode) -> Optional[DeviceStreamSource]:
    """Build the pipelined stream source for a device-bearing wire
    value, or None when the classic path should run (pipeline off, no
    device content, or the whole payload fits under the eager limit —
    sub-eager device values still benefit from the async snapshot in
    :func:`snapshot_host`). ``encode`` is the engine's protocol-5
    splitter (``SocketCommEngine._encode_value``)."""
    if not pipeline_enabled() or _jax() is None:
        return None
    if not has_device(value):
        return None
    # cheap sub-eager gate BEFORE any extraction/pickling: the legacy
    # path sizes by the same payload_bytes measure, so the boundary
    # decision stays consistent — without this, every sub-eager device
    # tile paid a throwaway container walk + protocol-5 pickle (and a
    # discarded D2H for unstreamable arrays) on the hottest send path
    from .engine import CommEngine
    if CommEngine.payload_bytes(value) <= eager_limit:
        return None
    skeleton, arrs, _seen = extract_device(value)
    if not arrs:
        return None
    head, raws, sizes, host_total = encode(skeleton)
    total = host_total
    metas: List[Dict] = []
    for a in arrs:
        pad = (-total) % _ALIGN
        nb = int(a.nbytes)
        metas.append({"nbytes": nb, "pad": pad,
                      "dtype": str(np.dtype(a.dtype)),
                      "shape": tuple(int(s) for s in a.shape)})
        total += pad + nb
    if total <= eager_limit:
        return None
    return DeviceStreamSource(head, raws, sizes, arrs, metas, total)


# ---------------------------------------------------------------------------
# receiver side: per-segment H2D stager
# ---------------------------------------------------------------------------

# the accelerator the comm plane stages onto (set by the first real
# accelerator TPUDevice module — device/tpu.py): staging straight onto
# the chip that will run the consumer avoids a default-device bounce on
# multi-chip hosts. None = jax's default placement (uncommitted), which
# is also the only safe choice on CPU test meshes.
_STAGE_TARGET = None


def set_stage_target(dev) -> None:
    """Record the preferred comm-staging device (first accelerator
    module wins; device/tpu.py calls this)."""
    global _STAGE_TARGET
    if _STAGE_TARGET is None:
        _STAGE_TARGET = dev


def stage_target():
    return _STAGE_TARGET


def should_stage(tagged: bool) -> bool:
    """ONE staging gate for every receive path (``stage_recv_value``,
    the per-segment stager, the HBM fetch stage-in): ``comm.stage_recv``
    = 0 never, 1 always (if jax is loaded), auto only for sender-tagged
    device payloads on a non-CPU backend — a host-born payload gains
    nothing from a device round trip its consumer did not ask for."""
    mode = str(mca_param.cached_get("comm.stage_recv", "auto"))
    if _off(mode):
        return False
    if mode == "auto" and not tagged:
        return False
    jax = _jax()
    if jax is None:
        return False
    return not (mode == "auto" and jax.default_backend() == "cpu")


class SegmentStager:
    """Receiver half of the pipelined device stream: as each DATA_SEG
    lands, the bytes belonging to device slots are ``device_put``
    immediately (H2D of segment k overlaps the receive of k+1);
    :meth:`finish` assembles each slot ON DEVICE (one concatenate +
    reshape — pure data movement, bitwise). Chunks that arrive
    element-misaligned (a forwarder's merged catch-up segment) mark the
    slot for the classic host fallback — correctness never depends on
    staging succeeding."""

    def __init__(self, host_total: int, metas: List[Dict]):
        self.ranges: List[Tuple[int, int, Any, Tuple]] = []
        off = host_total
        for m in metas:
            off += m["pad"]
            self.ranges.append((off, off + m["nbytes"],
                                np.dtype(m["dtype"]), tuple(m["shape"])))
            off += m["nbytes"]
        self.chunks: List[List[Tuple[int, Any]]] = [[] for _ in metas]
        self.ok = [True] * len(metas)

    def feed(self, stream_off: int, views: List[Any]) -> None:
        jax = _jax()
        if jax is None:
            self.ok = [False] * len(self.ok)
            return
        pos = stream_off
        for v in views:
            mv = v if isinstance(v, memoryview) else memoryview(v)
            if mv.ndim != 1 or mv.itemsize != 1:
                mv = mv.cast("B")
            n = mv.nbytes
            for i, (a, b, dt, _shape) in enumerate(self.ranges):
                if not self.ok[i]:
                    continue
                lo, hi = max(pos, a), min(pos + n, b)
                if lo >= hi:
                    continue
                isz = dt.itemsize
                if (lo - a) % isz or (hi - lo) % isz:
                    # element-misaligned chunk (merged forwarder
                    # catch-up): host fallback for this slot
                    self.ok[i] = False
                    continue
                try:
                    host = np.frombuffer(mv[lo - pos:hi - pos], dtype=dt)
                    dev = jax.device_put(host, _STAGE_TARGET)
                    self.chunks[i].append(((lo - a) // isz, dev))
                except Exception:  # noqa: BLE001 — fall back, never die
                    self.ok[i] = False
            pos += n

    def finish(self) -> List[Optional[Any]]:
        """Per-slot device arrays (or None where the host fallback must
        serve the slot). Coverage is verified — a dropped/duplicated
        chunk falls back rather than reassembling garbage."""
        jax = _jax()
        out: List[Optional[Any]] = []
        for i, (a, b, dt, shape) in enumerate(self.ranges):
            if jax is None or not self.ok[i]:
                out.append(None)
                continue
            parts = sorted(self.chunks[i], key=lambda p: p[0])
            want = 0
            good = True
            for off, dev in parts:
                if off != want:
                    good = False
                    break
                want += int(dev.shape[0]) if dev.shape else 1
            if not good or want * dt.itemsize != b - a:
                out.append(None)
                continue
            try:
                import jax.numpy as jnp
                dev = parts[0][1] if len(parts) == 1 \
                    else jnp.concatenate([p[1] for p in parts])
                out.append(dev.reshape(shape))
            except Exception:  # noqa: BLE001 — fall back, never die
                out.append(None)
        return out


def make_stager(stream: Dict, tagged: bool) -> Optional[SegmentStager]:
    """A :class:`SegmentStager` for one rx stream, or None when the
    stream carries no device slots / staging is gated off (the host
    reassembly buffer then serves every slot)."""
    metas = stream.get("dev")
    if not metas or not pipeline_enabled() or not should_stage(tagged):
        return None
    return SegmentStager(sum(stream.get("sizes", ())), metas)


def resolve_dev_slots(buf: bytearray, host_total: int,
                      metas: List[Dict],
                      stager: Optional[SegmentStager]) -> List[Any]:
    """Final values of a stream's device slots: the stager's on-device
    assemblies where they exist, host views over the reassembly buffer
    otherwise (bit-identical either way — the device path is pure data
    movement)."""
    staged = stager.finish() if stager is not None \
        else [None] * len(metas)
    out: List[Any] = []
    off = host_total
    for m, dev in zip(metas, staged):
        off += m["pad"]
        if dev is not None:
            out.append(dev)
        else:
            dt = np.dtype(m["dtype"])
            host = np.frombuffer(memoryview(buf)[off:off + m["nbytes"]],
                                 dtype=dt).reshape(m["shape"])
            out.append(host)
        off += m["nbytes"]
    return out


# ---------------------------------------------------------------------------
# same-mesh device-direct routing (the ICI path)
# ---------------------------------------------------------------------------

def local_device(dev) -> bool:
    """Is ``dev`` addressable from THIS process? Only locally-
    addressable targets can receive a ``device_put`` (a multi-
    controller mesh ships through the wire); an unanswerable query is
    treated as NOT local — the wire path is always correct. ONE
    definition for routing (:func:`direct_device_for`) and detection
    (``compiled.spmd.same_mesh``) — two copies already diverged once in
    review."""
    jax = _jax()
    if jax is None or dev is None:
        return False
    try:
        return dev.process_index == jax.process_index()
    except Exception:  # noqa: BLE001 — conservative: use the wire
        return False


def direct_device_for(rank: int):
    """The device rank ``rank``'s tiles should land on when the
    device-direct path applies, else None (classic wire path). ``auto``
    engages only when a comm mesh is registered — detection, not hope;
    ``1`` forces a round-robin map over the visible devices (the
    single-process loopback fabric)."""
    mode = str(mca_param.cached_get("comm.device_direct", "auto")).lower()
    if _off(mode):
        return None
    jax = _jax()
    if jax is None:
        return None
    from ..compiled import spmd
    dev = spmd.comm_mesh_device(rank)
    if dev is None and mode != "auto":
        try:
            devs = jax.devices()
            dev = devs[rank % len(devs)]
        except Exception:  # noqa: BLE001
            return None
    return dev if local_device(dev) else None


def place_value(value: Any, dev) -> Any:
    """Move every device leaf of ``value`` onto ``dev`` (XLA
    device-to-device transfer — the ICI edge; host leaves untouched).
    Pure data movement: bitwise."""
    jax = _jax()

    def walk(v):
        if is_device_array(v):
            return jax.device_put(v, dev)
        if isinstance(v, tuple):
            return tuple(walk(x) for x in v)
        if isinstance(v, list):
            return [walk(x) for x in v]
        if isinstance(v, dict):
            return {k: walk(x) for k, x in v.items()}
        return v

    return walk(value)


def control_bytes(targets) -> int:
    """Wire accounting of a device-direct activation: the payload never
    crosses the wire, so the message costs its CONTROL frame — the
    packed target list plus the envelope. The bench's ICI row asserts
    exactly this stays orders of magnitude under the payload size."""
    import pickle
    try:
        return len(pickle.dumps(targets, protocol=5)) + 64
    except Exception:  # noqa: BLE001
        return 128
