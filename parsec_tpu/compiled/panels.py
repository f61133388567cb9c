"""Panel-fused executor: wavefront plans over a dense transposed array.

The tile-dict/stacked-store executors (wavefront.py) run each wave-group
as a gather → batched body → scatter. That is the right general shape,
but for dense one-matrix DAGs (POTRF/GEQRF-like) the data movement
dominates on TPU: every task's tiles are stacked (copied) before compute
and re-sliced after — measured ~3x the compute floor for tiled POTRF at
NT=8..16 — and batched (vmapped) matmuls themselves reach only ~92 TF/s
on a v5e chip where plain 2D matmuls of any aspect ratio reach ~166-177.

This executor is the next fusion level, the wave-granular analog of the
chore ``batch_hook`` (core.task.Chore): the *taskpool* registers a
``wave_fuser`` that lowers an ENTIRE wave's groups to a few dense-slice
operations against the matrix stored as ONE ``(N, M)`` HBM array holding
**Aᵀ** (row panel j of the store = block-column j of A). The transposed
layout makes every panel write a leading-dimension contiguous
dynamic-update-slice (in-place under jit), and panel reads are strided
slices XLA fuses into the matmuls. Measured effect for tiled POTRF on a
v5e chip: the left-looking fused form reaches 138 TF/s (PERF.md §6,
PR 40; ~107 before it) where the per-tile executors topped out at ~45.

Slot bookkeeping comes from the SAME :class:`~.wavefront.WavefrontPlan` —
planning, leveling, and hazard verification are unchanged; only the data
substrate changes. ``write_back`` honors the DAG's write-set: tiles no
task writes are never copied back, so collection-level semantics match
the tiled executors even if the substrate scribbles on cells the DAG
never reads.

Reference analog: the reference reaches peak by handing whole-tile
operations to vendor BLAS inside .jdf bodies and letting lookahead keep
the GPU busy (dplasma dpotrf + device_cuda_module.c pipeline). Here the
fusion brings whole *panels* to the MXU — the TPU-idiomatic equivalent —
while the PTG DAG still defines and validates the schedule.

A wave_fuser has signature::

    fuser(wave: List[WaveGroup], geom: PanelGeometry)
        -> Callable[[dict], dict] | None

taking/returning the executor state — a dict with one transposed dense
array per collection, keyed by collection name (``geom.name``); fusers
may stash extra carry entries (underscore-prefixed by convention, e.g. a
factored diagonal inverse consumed by the next wave). ``geom`` is always
the ``{name: PanelGeometry}`` dict; single-collection fusers unpack
their one entry. Return None to
reject a wave (the executor then refuses, naming it — no silent
fallback; a hybrid would reintroduce the copies this path avoids). A
returned function may carry static counts of what it emitted as a dict
``fn.account``; :meth:`PanelExecutor.lowering_report` sums them.

Compile-once serving (the segmented panel path)
-----------------------------------------------

Whole-DAG jit of the fused program is the fastest *runtime* form but
its compile time is linear in waves and specific to N — every new
problem size is a fresh multi-second lowering (PARITY compile-time
table). The **segmented** path restores PaRSEC's compile-per-task-class
economy: a taskpool may additionally register a ``panel_segment_fuser``
that lowers each wave to :class:`SegStep` descriptors — named *panel
kernels* over extracted panels whose shapes are rounded up to a small
**bucket lattice** (:func:`bucket_tiles`: exact up to 16 tiles, then
multiples of 2^(⌊log₂t⌋−3) → ≤12.5% padding per dim, O(16·log NT)
buckets; grids of ≤16 tiles never pad at all).
Padding is exact-by-construction: extraction zero-masks beyond the true
extent, write-back masks to the true extent (and shifts windows clamped
at the array edge), so padded lanes carry zeros through the math.

The heavy kernels are keyed by (kernel, NB, bucket shape, dtype, body
hooks/trace knobs) — **independent of N** — and enter the shared
in-process jit store and the persistent executor store
(``utils/compile_cache.py``): a new N at an already-served (NB, dtype)
re-uses every already-compiled bucket, and a second run (or second
process) pays zero XLA compiles. Only the thin extract/write programs
are keyed per state shape (they are slice+mask copies, cheap to
compile, and they persist too).

Over a mesh (the runtime's own partition)
-----------------------------------------

A taskpool may also register a ``mesh_wave_fuser``::

    mesh_fuser(wave, geoms, part: PanelPartition)
        -> Callable[[dict], dict] | None

which lowers a wave for ONE shard of the state, every collection's axis
0 split in ``part.shards`` equal contiguous parts over the mesh axis
``part.axis``. The returned function runs under ``jax.shard_map``: it
sees its shard alone, tells the chips apart by ``lax.axis_index`` and
writes its own collectives. That is owner-computes over the data
collection's distribution, decided by the runtime, where the
``wave_fuser``'s one-chip program handed to GSPMD with a
``PartitionSpec`` leaves the partition to be derived (and re-derived at
every slice that is not aligned to a shard).
:func:`~.spmd.compile_with_plan` asks :meth:`PanelExecutor.partitioned`
first; a taskpool without a mesh lowering goes through GSPMD as before
and :meth:`PanelExecutor.partition_report` says which it was.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

import numpy as np

from .wavefront import WavefrontPlan, plan_structure_fingerprint
from ..utils import compile_cache
from ..utils.debug import debug_verbose


@dataclass(frozen=True)
class PanelGeometry:
    """Transposed-dense layout geometry handed to wave fusers: the state
    array ``state[name]`` is ``(nb*nt, mb*mt)`` holding the collection
    transposed — tile (i, j) lives at ``D[cols(j), rows(i)]``
    transposed."""
    name: str
    mb: int
    nb: int
    mt: int
    nt: int

    def rows(self, i: int) -> slice:
        """Column range of D covering block-row i of A."""
        return slice(i * self.mb, (i + 1) * self.mb)

    def cols(self, j: int) -> slice:
        """Row range of D covering block-column j of A."""
        return slice(j * self.nb, (j + 1) * self.nb)


# A mesh lowering multiplies a row panel's update in column runs, and a
# chip that holds factored rows sends each run's partial sums to the
# panel's owner as it leaves the MXU: XLA:TPU starts a collective-permute
# and finishes it later (its all-reduce it does not), so a run travels
# under its sender's next product and a step exposes its last run alone.
# On a v5e a run's transfer takes about half its product's time (45 GB/s
# a link against 170 TF/s over a chip's 16 row panels), so each run is
# half the one before it, down to a last of at most SEND_TAIL_BYTES. What
# keeps the runs few is the program's text, which a chip holds in HBM
# beside its shard: about 1.1 MiB a run and 0.26 MiB a send. A panel
# nobody sends — every panel of the one-chip program, and on a mesh the
# first chip's — goes in the fewest equal runs of at most
# PANEL_CHUNK_BYTES, and not only to keep a step's product from being one
# temporary. At N = 40960 on one v5e (PERF.md §6, PR 40) the one-chip
# program's products run at 173 TF/s in runs of 96 MiB, at 171 as one
# product a step and at 170 in runs of 48 MiB (84 runs against 54: more
# text too), where the product fused with its subtraction over the whole
# row panel ran at about 128. The runs need their barrier: without it
# XLA:TPU puts a run's product behind the write of the run before it and
# copies the state (6.25 GiB there: the compile fails).
PANEL_CHUNK_BYTES = 96 << 20
SEND_TAIL_BYTES = 28 << 20
_NO_MESH_LOWERING = "taskpool registers no mesh_wave_fuser"


class PanelPartition:
    """The split a ``mesh_wave_fuser`` lowers for — every collection's
    axis 0 (the row panels of the transposed store) in ``shards`` equal
    contiguous parts over mesh axis ``axis`` — and the lowering's own
    account of what it emitted: static counts, no run needed."""

    def __init__(self, axis: str, shards: int):
        self.axis = axis
        self.shards = shards
        self.chunk_bytes = PANEL_CHUNK_BYTES
        self.tail_bytes = SEND_TAIL_BYTES
        self.sends = 0
        self.hideable_sends = 0         # with a product of their sender after
        self.sent_bytes = [0] * shards  # payload each chip hands in
        self.widest_send = 0            # the most bytes one send carries
        self.ops = [0] * shards         # operations lowered per chip

    def owner(self, geom: PanelGeometry, j: int) -> Tuple[int, int]:
        """``(shard, local row panel)`` holding row panel ``j``."""
        return divmod(j, geom.nt // self.shards)

    def chunks(self, lo: int, hi: int, tile_bytes: int, owner: int
               ) -> List[Tuple[int, int]]:
        """Tile range ``[lo, hi)`` of a row panel of ``owner``'s in runs
        of whole tiles. A panel somebody sends goes in runs each about
        half the one before it, as many as bring the last within
        ``tail_bytes``; a panel nobody sends, in the fewest equal runs
        within ``chunk_bytes``."""
        tiles = hi - lo
        if self.senders(owner):
            # the least n with tiles ≤ tail · (2ⁿ − 1), a tile a run at most
            tail = max(1, self.tail_bytes // tile_bytes)
            n = min(tiles, (-(-tiles // tail)).bit_length())
            shares = [2 ** i for i in range(n)]     # the last run's first
        else:
            n = -(-tiles // max(1, self.chunk_bytes // tile_bytes))
            shares = [1] * n
        cuts = [hi]
        for i in range(n - 1):
            size = round((cuts[-1] - lo) * shares[i] / sum(shares[i:]))
            cuts.append(cuts[-1] - max(1, size))
        cuts.append(lo)
        return list(zip(cuts[::-1], cuts[-2::-1]))

    def senders(self, owner: int) -> range:
        """The chips that send ``owner`` a partial of its row panel: the
        rows are contiguous and factored in order, so the chips before
        it hold factored rows to contract and the ones after it none."""
        return range(owner)

    def count_send(self, sender: int, nbytes: int, last: bool) -> None:
        """One chunk from ``sender`` to the step's owner; ``last``: no
        product of the sender follows it in its step."""
        self.sends += 1
        self.hideable_sends += not last
        self.sent_bytes[sender] += nbytes
        self.widest_send = max(self.widest_send, nbytes)


# ---------------------------------------------------------------------------
# bucket lattice (segmented panel path)
# ---------------------------------------------------------------------------

def bucket_tiles(t: int, cap: int) -> int:
    """Round a tile count up to the bucket lattice, capped at ``cap``
    (the dimension's grid extent — buckets never exceed the store).

    Lattice: exact for t ≤ 16, then multiples of 2^(⌊log₂t⌋−3)
    ({18,20,...,32, 36,40,...,64, 72,...} — ≤16 points per octave) —
    padding overhead ≤ 12.5% per dimension, O(16·log NT) distinct
    buckets, and the lattice points are absolute (N-independent) so a
    smaller problem at the same NB lands entirely on already-compiled
    buckets (modulo its own cap point)."""
    if t >= cap:
        return cap
    q = 1 << max(0, t.bit_length() - 1 - 3)
    return min(((t + q - 1) // q) * q, cap)


@dataclass(frozen=True, eq=False)
class SegRead:
    """One kernel input: a masked bucketed window of a state array
    (``src="state"``), a carry produced by an earlier step
    (``src="carry"``), or a lowering-time constant (``src="const"``).
    Offsets/extents are element units; ``rows_b/cols_b`` are the
    bucketed extents actually extracted (≥ true, zero-masked)."""
    src: str
    name: str
    r0: int = 0
    c0: int = 0
    rows: int = 0
    cols: int = 0
    rows_b: int = 0
    cols_b: int = 0
    value: Any = None          # src="const" payload (host array/scalar)


@dataclass(frozen=True, eq=False)
class SegWrite:
    """One kernel output destination: a masked window of a state array
    (only ``[r0:r0+rows, c0:c0+cols]`` is written, whatever the padded
    value shape) or a named carry."""
    dst: str
    name: str
    r0: int = 0
    c0: int = 0
    rows: int = 0
    cols: int = 0


@dataclass(frozen=True, eq=False)
class SegStep:
    """One dispatch of a registered panel kernel: gather ``reads``,
    call the kernel, route outputs to ``writes`` (position-matched).
    ``static`` is extra kernel-builder config baked into the cache
    key (must be canonical primitives)."""
    kernel: str
    reads: Tuple[SegRead, ...]
    writes: Tuple[SegWrite, ...]
    static: Tuple = field(default=())


_PANEL_KERNELS: Dict[str, Callable] = {}


def register_panel_kernel(name: str):
    """Register a panel-kernel builder: ``builder(in_sds, static) ->
    pure fn(*arrays) -> array | tuple``. ``in_sds`` are the (bucketed)
    input ShapeDtypeStructs. Builders may read trace-affecting MCA
    knobs at build time — register those via
    :func:`~..utils.compile_cache.register_trace_knob` so the cache key
    covers them."""
    def deco(builder):
        _PANEL_KERNELS[name] = builder
        return builder
    return deco


def _build_extract(rows_b: int, cols_b: int, clamp_r: bool,
                   clamp_c: bool):
    """Masked bucketed window read: ``(D, r0, c0, rows, cols) ->
    (rows_b, cols_b)`` with zeros beyond the true extent. When the
    window can run off the array edge (static ``clamp_*`` decided at
    lowering from the descriptor), the slice start is clamped and the
    payload rolled back into place — dynamic_slice would otherwise
    silently shift the window."""
    def ext(D, r0, c0, rows, cols):
        import jax.numpy as jnp
        from jax import lax
        ra, ca = r0, c0
        if clamp_r:
            ra = jnp.minimum(r0, D.shape[0] - rows_b)
        if clamp_c:
            ca = jnp.minimum(c0, D.shape[1] - cols_b)
        raw = lax.dynamic_slice(D, (ra, ca), (rows_b, cols_b))
        if clamp_r:
            raw = jnp.roll(raw, -(r0 - ra), axis=0)
        if clamp_c:
            raw = jnp.roll(raw, -(c0 - ca), axis=1)
        rmask = jnp.arange(rows_b) < rows
        cmask = jnp.arange(cols_b) < cols
        return jnp.where(rmask[:, None] & cmask[None, :], raw,
                         jnp.zeros((), D.dtype))
    return ext


def _build_write(rows_b: int, cols_b: int, clamp_r: bool, clamp_c: bool):
    """Masked bucketed window write: only ``[r0:r0+rows, c0:c0+cols]``
    of D changes; padded lanes of V are discarded. D is donated — the
    update is in-place under XLA aliasing."""
    def wr(D, V, r0, c0, rows, cols):
        import jax.numpy as jnp
        from jax import lax
        ra, ca = r0, c0
        if clamp_r:
            ra = jnp.minimum(r0, D.shape[0] - rows_b)
        if clamp_c:
            ca = jnp.minimum(c0, D.shape[1] - cols_b)
        ro, co = r0 - ra, c0 - ca
        cur = lax.dynamic_slice(D, (ra, ca), (rows_b, cols_b))
        Vr = V.astype(D.dtype)
        if clamp_r:
            Vr = jnp.roll(Vr, ro, axis=0)
        if clamp_c:
            Vr = jnp.roll(Vr, co, axis=1)
        ri = jnp.arange(rows_b)
        ci = jnp.arange(cols_b)
        rmask = (ri >= ro) & (ri < ro + rows)
        cmask = (ci >= co) & (ci < co + cols)
        blended = jnp.where(rmask[:, None] & cmask[None, :], Vr, cur)
        return lax.dynamic_update_slice(D, blended, (ra, ca))
    return wr


class PanelExecutor:
    """Execute a :class:`WavefrontPlan` over transposed dense storage.

    Requirements (checked): the taskpool registered ``wave_fuser`` and
    every collection is a tiled matrix. :meth:`run_state` is a pure
    jittable function ``state -> state``
    (state = ``{collection name: transposed dense array, ...carries}``).
    """

    def __init__(self, plan: WavefrontPlan):
        import jax
        self.jax = jax
        self.plan = plan
        from ..dsl.ptg import taskpool_has_ranged_flows
        if taskpool_has_ranged_flows(plan.taskpool):
            # a plan made by hand: plan_taskpool refuses such a pool
            raise ValueError(
                f"taskpool {plan.taskpool.name!r} has a ranged data flow "
                f"(gather/scatter): a wave fuser lowers one panel slice a "
                f"flow; a list of tiles a flow is the host runtime's")
        fuser = getattr(plan.taskpool, "wave_fuser", None)
        if fuser is None:
            raise ValueError(
                f"taskpool {plan.taskpool.name!r} registers no wave_fuser; "
                "use the tile-dict/stacked executors instead")
        if getattr(plan, "has_reshapes", False):
            raise ValueError(
                f"taskpool {plan.taskpool.name!r} declares dep "
                "[type=...] reshape specs; wave fusers lower raw panel "
                "slices — use the tile-dict executors (which apply "
                "specs at gather) or the host runtime")
        self.geoms = {
            name: PanelGeometry(name=name, mb=dc.mb, nb=dc.nb,
                                mt=dc.mt, nt=dc.nt)
            for name, dc in plan.collections.items()}
        # fusers always receive the {name: PanelGeometry} dict —
        # uniform, no type sniffing (single-collection fusers unpack
        # their one entry)
        geom_arg = self.geoms
        self.geom = geom_arg
        # lower every wave up front — planning errors surface at build
        # time, not mid-trace. The run size is a module constant a
        # lowering reads, which a function's fingerprint does not cover:
        # the stored program's key carries what it was when we lowered
        self._chunk_bytes = PANEL_CHUNK_BYTES
        self._wave_fns: List[Callable] = []
        for w, wave in enumerate(plan.waves):
            fn = fuser(wave, geom_arg)
            if fn is None:
                names = [(g.tc.name, len(g.tasks)) for g in wave]
                raise ValueError(
                    f"wave {w} not fusable by {plan.taskpool.name!r}: "
                    f"{names}")
            self._wave_fns.append(fn)
        # DAG write-set per collection: (i, j) block coords any task writes
        self._written: Dict[str, Set[Tuple[int, int]]] = {
            name: set() for name in self.geoms}
        invmaps = {name: {s: k for k, s in plan.slot_maps[name].items()}
                   for name in self.geoms}
        for wave in plan.waves:
            for grp in wave:
                for (name, slots) in grp.out_slots:
                    for s in slots:
                        self._written[name].add(
                            tuple(invmaps[name][int(s)]))
        debug_verbose(3, "panels", "lowered %s: %d waves onto %d "
                      "transposed dense arrays", plan.taskpool.name,
                      len(self._wave_fns), len(self.geoms))
        # segmented (compile-once) path, lowered lazily on first use
        self._segment_fuser = getattr(plan.taskpool,
                                      "panel_segment_fuser", None)
        self._seg_steps: Optional[List[SegStep]] = None
        self._jitted = None
        # over a mesh: who partitioned the program last, and how
        self._mesh_fuser = getattr(plan.taskpool, "mesh_wave_fuser", None)
        self._partition: Dict[str, Any] = self._gspmd(_NO_MESH_LOWERING) \
            if self._mesh_fuser is None else {"branch": None}

    @property
    def supports_segments(self) -> bool:
        return self._segment_fuser is not None

    # -- whole-DAG jit (shared + persistent) ------------------------------
    # jit caches by FUNCTION OBJECT: a fresh jax.jit(self.run_state) per
    # executor used to re-trace (and re-lower, and re-XLA) the whole
    # program for every rebuild of an identical plan. The monolith now
    # routes through the shared keyed store: equal (plan structure,
    # fuser code, shapes, trace knobs) → one trace per process and a
    # serialized executable across processes.
    @property
    def jitted(self) -> Callable:
        if self._jitted is None:
            key = self.monolith_cache_key()
            if key is None:      # unstable fingerprint: per-instance jit
                self._jitted = self.jax.jit(self.run_state,
                                            donate_argnums=0)
            else:
                self._jitted = compile_cache.cached_jit(
                    self.run_state, key=key,
                    example_args=(self.state_shapes(),),
                    donate_argnums=0)
        return self._jitted

    def state_shapes(self) -> Dict[str, Any]:
        """Abstract (ShapeDtypeStruct) state as :meth:`make_state`
        builds it — the AOT lowering input."""
        import jax
        return {name: jax.ShapeDtypeStruct(
            (g.nb * g.nt, g.mb * g.mt),
            np.dtype(self.plan.collections[name].dtype))
            for name, g in self.geoms.items()}

    def monolith_cache_key(self) -> Optional[Tuple]:
        """Semantic cache key of the whole-DAG fused program, or None
        when some ingredient has no stable fingerprint."""
        fuser = getattr(self.plan.taskpool, "wave_fuser", None)
        f_ok, f_fp = compile_cache.function_fingerprint(fuser)
        p_ok, p_fp = plan_structure_fingerprint(self.plan)
        if not (f_ok and p_ok):
            return None
        shapes = tuple(sorted(
            (name, tuple(s.shape), str(s.dtype))
            for name, s in self.state_shapes().items()))
        return ("panel_monolith", f_fp, p_fp, shapes, self._chunk_bytes)

    def lowering_report(self) -> Dict[str, int]:
        """The one-chip lowering's own account of what it emitted, summed
        over the waves: static counts, no run needed, as
        :meth:`partition_report` gives a mesh lowering's. A wave's
        function carries its share as ``fn.account``
        (``build_potrf_left``: ``update_runs``, the column runs its
        UPDATE waves multiply in, and ``update_ops``, their
        operations)."""
        report: Dict[str, int] = {}
        for fn in self._wave_fns:
            for what, n in getattr(fn, "account", {}).items():
                report[what] = report.get(what, 0) + n
        return report

    # -- pure dense execution --------------------------------------------
    def run_state(self, state: Dict[str, Any]) -> Dict[str, Any]:
        state = dict(state)
        for fn in self._wave_fns:
            state = fn(state)
        # fuser carries (factored inverses etc.) are wave-transient —
        # only the collection arrays survive
        return {name: state[name] for name in self.geoms}

    # -- over a mesh: the runtime's partition -----------------------------
    @staticmethod
    def _gspmd(reason: str) -> Dict[str, Any]:
        return {"branch": "gspmd", "reason": reason}

    def partition_report(self) -> Dict[str, Any]:
        """Who partitioned this program the last time it was compiled
        over a mesh: ``branch`` is ``runtime`` (the taskpool's mesh
        lowering under ``shard_map``; with the sends to a row panel's
        owner a step, the bytes the busiest chip hands to them, the
        share of them that program order lets a product hide — those
        that are not their sender's last in their row panel — and the
        busiest chip's share of the operations, as the lowering counted
        them) or ``gspmd`` (the one-chip program and a
        ``PartitionSpec``; with the ``reason``). ``branch`` is None
        until a mesh compile has asked."""
        return dict(self._partition)

    def _split_axis(self, mesh, in_shardings, out_shardings
                    ) -> Tuple[Optional[str], str]:
        """``(axis, "")`` when the call asks for the one split a mesh
        lowering is written for, else ``(None, why not)``."""
        from jax.sharding import NamedSharding, PartitionSpec
        if len(mesh.axis_names) != 1:
            return None, f"mesh has {len(mesh.axis_names)} axes"
        axis = mesh.axis_names[0]

        def rows_split(sh) -> bool:
            if isinstance(sh, NamedSharding):
                if sh.mesh != mesh:
                    return False
                sh = sh.spec
            return isinstance(sh, PartitionSpec) and \
                tuple(sh)[:1] == (axis,) and not any(tuple(sh)[1:])

        if not (isinstance(in_shardings, (tuple, list))
                and len(in_shardings) == 1):
            return None, "in_shardings is not one state"
        for sh in (in_shardings[0], out_shardings):
            if not (isinstance(sh, dict) and set(sh) == set(self.geoms)
                    and all(rows_split(v) for v in sh.values())):
                return None, (f"in/out shardings are not P({axis!r}) on "
                              "axis 0 of every collection")
        n = mesh.devices.size
        odd = {name: g.nt for name, g in self.geoms.items() if g.nt % n}
        if odd:
            return None, f"{n} shards do not divide nt={odd}"
        return axis, ""

    def partitioned(self, mesh, in_shardings, out_shardings):
        """``(fn, key)``: the taskpool's mesh lowering of every wave as
        one ``shard_map``ped ``state -> state`` over ``mesh`` and what
        a store key must carry of it (None: no stable fingerprint, do
        not share). Returns None, and :meth:`partition_report` says
        why, when the taskpool registers no mesh lowering, when the
        call is not a one-axis mesh with the same ``P(axis)`` on every
        collection's axis 0 going in and coming out and a size that
        divides ``nt``, or when the lowering declines a wave — the
        caller then hands :meth:`run_state` to GSPMD."""
        from jax.sharding import PartitionSpec as P
        axis, why = (None, _NO_MESH_LOWERING) if self._mesh_fuser is None \
            else self._split_axis(mesh, in_shardings, out_shardings)
        if not why:
            part = PanelPartition(axis, int(mesh.devices.size))
            lowered = [self._mesh_fuser(wave, self.geoms, part)
                       for wave in self.plan.waves]
            if None in lowered:
                why = (f"wave {lowered.index(None)} declined by the "
                       "mesh_wave_fuser")
        if why:
            self._partition = self._gspmd(why)
            debug_verbose(2, "panels", "%s over a mesh is GSPMD's to "
                          "partition: %s", self.plan.taskpool.name, why)
            return None

        def run_shard(state):
            state = dict(state)
            for fn in lowered:
                state = fn(state)
            return {name: state[name] for name in self.geoms}

        specs = {name: P(axis) for name in self.geoms}
        # the lowering tells chips apart and sends across them itself;
        # nothing of it is replicated for shard_map to check
        mapped = self.jax.shard_map(run_shard, mesh=mesh, in_specs=(specs,),
                                    out_specs=specs, check_vma=False)
        self._partition = {
            "branch": "runtime", "axis": axis, "shards": part.shards,
            "busiest_chip_ops_share": max(part.ops) / max(sum(part.ops), 1),
            "sends_per_step": part.sends,
            "sent_bytes_per_step_busiest_chip": max(part.sent_bytes),
            "send_chunk_bytes": part.widest_send,
            "sends_with_a_product_behind_them_share":
                part.hideable_sends / max(part.sends, 1),
            # the names a reader of the all-reduce form knew
            "collectives_per_step": part.sends,
            "reduced_bytes_per_step_and_chip": max(part.sent_bytes)}
        debug_verbose(2, "panels", "%s over %d chips, the runtime's "
                      "partition: %s", self.plan.taskpool.name,
                      part.shards, self._partition)
        ok, fp = compile_cache.function_fingerprint(self._mesh_fuser)
        return mapped, (("mesh_wave_fuser", fp, part.chunk_bytes,
                         part.tail_bytes) if ok else None)

    # -- host-driven convenience -----------------------------------------
    def make_state(self) -> Dict[str, Any]:
        """Collection tiles → transposed dense state, one array per
        collection."""
        import jax.numpy as jnp
        state = {}
        for name, g in self.geoms.items():
            dc = self.plan.collections[name]
            rows = []
            for j in range(g.nt):
                rows.append(jnp.concatenate(
                    [jnp.asarray(dc.data_of((i, j))).T
                     for i in range(g.mt)], axis=1))
            state[name] = jnp.concatenate(rows, axis=0)
        return state

    def write_back(self, state: Dict[str, Any]) -> None:
        """Write ONLY the DAG's write-set back to the collections —
        substrate scribbles outside it stay invisible at the collection
        level."""
        for name, g in self.geoms.items():
            if not self._written[name]:
                continue
            dc = self.plan.collections[name]
            host = np.asarray(state[name])
            for (i, j) in sorted(self._written[name]):
                dc.write_tile((i, j), host[g.cols(j), g.rows(i)].T)

    # -- segmented execution (compile-once serving) -----------------------

    def segments(self) -> List[SegStep]:
        """Lower every wave through the taskpool's
        ``panel_segment_fuser`` (lazily, cached). Raises when the
        taskpool registers none or a wave is rejected — no silent
        fallback to the linear-in-waves monolith."""
        if self._seg_steps is not None:
            return self._seg_steps
        if self._segment_fuser is None:
            raise ValueError(
                f"taskpool {self.plan.taskpool.name!r} registers no "
                "panel_segment_fuser; use the whole-DAG fused form "
                "(run/jitted) or the tile-dict segmented executor")
        steps: List[SegStep] = []
        for w, wave in enumerate(self.plan.waves):
            lowered = self._segment_fuser(wave, self.geoms)
            if lowered is None:
                names = [(g.tc.name, len(g.tasks)) for g in wave]
                raise ValueError(
                    f"wave {w} not segment-fusable by "
                    f"{self.plan.taskpool.name!r}: {names}")
            steps.extend(lowered)
        self._seg_steps = steps
        debug_verbose(3, "panels", "segment-lowered %s: %d waves -> %d "
                      "steps", self.plan.taskpool.name,
                      len(self.plan.waves), len(steps))
        return steps

    @staticmethod
    def _window_fn(D_sds, val_sds, rd_or_wr, tag):
        """Shared-cache entry for one extract/write program. Keyed by
        (state shape, bucket shape, clamp flags) — these are the only
        per-N programs of the segmented path (thin slice+mask copies);
        the heavy kernels are N-independent."""
        import jax
        clamp_r = rd_or_wr.r0 + val_sds.shape[0] > D_sds.shape[0]
        clamp_c = rd_or_wr.c0 + val_sds.shape[1] > D_sds.shape[1]
        i32 = jax.ShapeDtypeStruct((), np.int32)
        key = (tag, tuple(D_sds.shape), str(D_sds.dtype),
               tuple(val_sds.shape), clamp_r, clamp_c)
        if tag == "panel_write":
            fn = _build_write(*val_sds.shape, clamp_r, clamp_c)
            ex = (D_sds, val_sds, i32, i32, i32, i32)
            return compile_cache.cached_jit(fn, key=key, example_args=ex,
                                            donate_argnums=0)
        fn = _build_extract(*val_sds.shape, clamp_r, clamp_c)
        ex = (D_sds, i32, i32, i32, i32)
        return compile_cache.cached_jit(fn, key=key, example_args=ex)

    def _kernel_fn(self, step: SegStep, in_sds: Tuple) -> Callable:
        builder = _PANEL_KERNELS.get(step.kernel)
        if builder is None:
            raise KeyError(f"unregistered panel kernel {step.kernel!r}")
        sig = tuple((tuple(s.shape), str(s.dtype)) for s in in_sds)
        key = ("panel_kernel", step.kernel, sig, step.static)
        return compile_cache.cached_jit(builder(in_sds, step.static),
                                        key=key, example_args=in_sds)

    def _seg_walk(self, state, dispatch: bool):
        """Shared walker for :meth:`run_state_segmented` (dispatch=True,
        state = device arrays) and :meth:`prepare_segments`
        (dispatch=False, state = ShapeDtypeStructs — resolves/compiles
        every program without running, propagating carry shapes with
        eval_shape). One walker so warm-up and execution can never
        resolve different cache keys."""
        import jax
        state = dict(state)
        carries: Dict[str, Any] = {}
        i4 = (np.int32(0),) * 4
        for step in self.segments():
            ins = []
            for rd in step.reads:
                if rd.src == "carry":
                    ins.append(carries[rd.name])
                elif rd.src == "const":
                    v = np.asarray(rd.value)
                    ins.append(jax.ShapeDtypeStruct(v.shape, v.dtype)
                               if not dispatch else v)
                else:
                    D = state[rd.name]
                    D_sds = jax.ShapeDtypeStruct(D.shape, D.dtype)
                    v_sds = jax.ShapeDtypeStruct(
                        (rd.rows_b, rd.cols_b), D.dtype)
                    fn = self._window_fn(D_sds, v_sds, rd, "panel_extract")
                    if dispatch:
                        ins.append(fn(D, np.int32(rd.r0), np.int32(rd.c0),
                                      np.int32(rd.rows), np.int32(rd.cols)))
                    else:
                        ins.append(v_sds)
            in_sds = tuple(
                x if isinstance(x, jax.ShapeDtypeStruct) else
                jax.ShapeDtypeStruct(x.shape, x.dtype) for x in ins)
            kfn = self._kernel_fn(step, in_sds)
            if dispatch:
                outs = kfn(*ins)
            else:
                builder = _PANEL_KERNELS[step.kernel]
                outs = jax.eval_shape(builder(in_sds, step.static),
                                      *in_sds)
            if not isinstance(outs, (tuple, list)):
                outs = (outs,)
            if len(outs) != len(step.writes):
                raise ValueError(
                    f"panel kernel {step.kernel!r} returned {len(outs)} "
                    f"outputs for {len(step.writes)} writes")
            for wr, val in zip(step.writes, outs):
                if wr.dst == "carry":
                    carries[wr.name] = val
                    continue
                D = state[wr.name]
                D_sds = jax.ShapeDtypeStruct(D.shape, D.dtype)
                v_sds = jax.ShapeDtypeStruct(val.shape, val.dtype)
                fn = self._window_fn(D_sds, v_sds, wr, "panel_write")
                if dispatch:
                    state[wr.name] = fn(D, val, np.int32(wr.r0),
                                        np.int32(wr.c0), np.int32(wr.rows),
                                        np.int32(wr.cols))
                else:
                    state[wr.name] = D_sds     # shape unchanged
        return {name: state[name] for name in self.geoms}

    def run_state_segmented(self, state: Dict[str, Any]
                            ) -> Dict[str, Any]:
        """state → state through cached per-(kernel, bucket) programs
        dispatched wave-by-wave. Same collection-level results as
        :meth:`run_state`; compile cost bounded by distinct buckets
        (not waves) and shared across N, executors, and — with the
        persistent store — processes. JAX async dispatch pipelines the
        per-step calls."""
        return self._seg_walk(state, dispatch=True)

    def prepare_segments(self) -> int:
        """Resolve (compile or load) every program the segmented run
        will dispatch, without touching data — the serving warm-up.
        Returns the number of distinct cached programs in the walk."""
        n0 = compile_cache.jit_store_size()
        self._seg_walk(self.state_shapes(), dispatch=False)
        return compile_cache.jit_store_size() - n0

    # -- host-driven run --------------------------------------------------

    def run(self, jit: bool = True, segmented: bool = False) -> float:
        t0 = time.perf_counter()
        state = self.make_state()
        if segmented:
            out = self.run_state_segmented(state)
        else:
            fn = self.jitted if jit else self.run_state
            out = fn(state)
        for v in out.values():
            v.block_until_ready()
        dt = time.perf_counter() - t0
        self.write_back(out)
        return dt
