"""SPMD distributed execution of wavefront plans over a device mesh.

Replaces the reference's remote-dep machinery (remote_dep.c /
remote_dep_mpi.c: activation AMs + rendezvous PUT/GET over MPI) for the
compiled path. The TPU-first recipe ("How to Scale Your Model"): pick a
``jax.sharding.Mesh``, annotate the stacked tile stores with a
``NamedSharding`` over the tile-slot dimension, and jit the store-passing
wavefront program over the mesh — XLA's SPMD partitioner inserts the
collectives (all-gathers / collective-permutes riding ICI) that the
reference implements by hand as activation trees + one-sided transfers.

Owner-computes refinement: distributed collections emit rank-grouped
slot orders (TiledMatrix.tile_index), so sharding the slot axis places
each tile's slot on (or near) its owner device and the partitioner's
collectives carry only true dataflow.

Preferential-pjit front end (compile-once serving)
--------------------------------------------------

:func:`compile_with_plan` is the single compilation entry for mesh
programs (the Titanax ``compile_step_with_plan`` helper shape):
explicit in/out shardings → a pjit-compiled program; a mesh without
shardings → a ``shard_map`` data-parallel fallback (the function must
then be shard-local — per-slot independent); neither → plain jit.
Whatever the branch, the product enters the same shared jit store and
persistent executor cache as the single-chip executors
(``utils/compile_cache.py``), keyed by mesh axes/devices + sharding
specs on top of the caller's key — so a serving process re-lowers a
mesh program exactly once per (program, mesh, sharding, shapes) and a
second process pays only deserialization.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from ..utils import compile_cache
from .panels import PanelExecutor


def make_mesh(n_devices: Optional[int] = None, axis: str = "tiles"):
    """A 1D mesh over the first ``n_devices`` visible devices."""
    import jax
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return jax.sharding.Mesh(np.asarray(devs), (axis,))


def shard_stores(stores: Dict[str, Any], mesh, axis: str = "tiles"):
    """Place each stacked store sharded over its slot dimension (padding
    the slot count up to a multiple of the mesh size)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.devices.size
    sharding = NamedSharding(mesh, P(axis))
    out = {}
    for name, arr in stores.items():
        pad = (-arr.shape[0]) % n
        if pad:
            arr = jnp.concatenate(
                [arr, jnp.zeros((pad,) + arr.shape[1:], arr.dtype)], axis=0)
        out[name] = jax.device_put(arr, sharding)
    return out


# ---------------------------------------------------------------------------
# comm-mesh registry: the same-mesh detection of the device-direct data
# plane (comm.device_direct). When the runtime's comm ranks map onto the
# devices of ONE JAX mesh (the loopback fabric: one process, per-rank
# chips; a single-controller pod slice the same way), a dep between two
# ranks is an intra-mesh edge — the tile can move as an XLA device-to-
# device transfer (jax.device_put onto the consumer's device, riding
# ICI on real hardware) and only a control frame needs the wire.
# ---------------------------------------------------------------------------

_COMM_MESH = None


def register_comm_mesh(mesh, rank_devices=None) -> None:
    """Declare that comm rank ``r`` computes on ``rank_devices[r]``
    (default: the mesh's devices in flat order, round-robin). The
    device-direct path (``comm.device_direct=auto``) engages only once
    a mesh is registered — detection, not hope."""
    global _COMM_MESH
    devs = list(rank_devices) if rank_devices is not None \
        else list(mesh.devices.flat)
    _COMM_MESH = (mesh, devs)


def unregister_comm_mesh() -> None:
    global _COMM_MESH
    _COMM_MESH = None


def comm_mesh():
    """The registered ``(mesh, rank_devices)`` pair, or None."""
    return _COMM_MESH


def comm_mesh_device(rank: int):
    """The device comm rank ``rank`` computes on under the registered
    comm mesh, or None when no mesh is registered."""
    if _COMM_MESH is None:
        return None
    devs = _COMM_MESH[1]
    return devs[rank % len(devs)] if devs else None


def same_mesh(src_rank: int, dst_rank: int) -> bool:
    """Do both endpoints of a dep sit on one registered mesh whose
    devices this process can address (the device-direct eligibility
    test)? Multi-controller placements (a device owned by another
    process) route through the wire instead. Shares the locality
    predicate with the routing path (``device_plane.local_device``) so
    detection can never drift from what routing actually does."""
    from ..comm.device_plane import local_device
    return local_device(comm_mesh_device(src_rank)) and \
        local_device(comm_mesh_device(dst_rank))


def mesh_of_value(value):
    """The mesh a sharded value lives on (NamedSharding), or None —
    the collection-sharding detection hook: a runtime that stores its
    tiles mesh-sharded can register that mesh as the comm mesh."""
    sh = getattr(value, "sharding", None)
    mesh = getattr(sh, "mesh", None)
    return mesh


# ---------------------------------------------------------------------------
# preferential-pjit compilation helper
# ---------------------------------------------------------------------------

def _mesh_repr(mesh) -> Tuple:
    if mesh is None:
        return ()
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


def _sharding_repr(s) -> Any:
    """Canonical key form of a sharding pytree (NamedShardings /
    PartitionSpecs / None leaves, possibly nested in dicts/tuples)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    def leaf(x):
        if x is None:
            return "none"
        if isinstance(x, NamedSharding):
            return ("named", _mesh_repr(x.mesh), tuple(repr(p)
                                                       for p in x.spec))
        if isinstance(x, PartitionSpec):
            return ("pspec", tuple(repr(p) for p in x))
        return repr(x)

    return jax.tree_util.tree_map(
        leaf, s, is_leaf=lambda x: x is None or
        isinstance(x, (NamedSharding, PartitionSpec)))


def compile_with_plan(fn: Callable, *, mesh=None, in_shardings=None,
                      out_shardings=None, in_specs=None, out_specs=None,
                      donate_argnums=(), example_args: Tuple = None,
                      key: Tuple = (), fn_key=None) -> Callable:
    """Compile ``fn`` for a device mesh, preferring ``pjit`` when the
    caller knows its shardings (SNIPPETS [2], Titanax
    ``compile_step_with_plan``):

    - ``in_shardings`` AND ``out_shardings`` given → pjit (``jax.jit``
      with shardings): XLA partitions the program, inserting the
      collectives true dataflow needs. Giving only one of the two is an
      error — a half-specified contract silently replicates the other
      side. One program is not left to XLA: when ``fn`` is a
      :class:`~.panels.PanelExecutor`'s ``run_state`` whose taskpool
      registers a mesh lowering and the call is the split it is written
      for (:meth:`~.panels.PanelExecutor.partitioned`), the jitted
      function is the runtime's own partition under ``shard_map``, with
      the same shardings and donation; the executor's
      ``partition_report()`` says which of the two was built.
    - no shardings but a ``mesh`` → ``shard_map`` fallback for pure
      data-parallel map-style execution over ``in_specs``/``out_specs``
      (default: shard the leading axis of every argument over the
      mesh's first axis). ``fn`` must be shard-local.
    - neither → plain jit.

    Every branch enters the shared jit store / persistent executor
    cache keyed by (``fn``'s identity, caller key, branch, mesh,
    sharding specs) — a rebuilt front end for an already-served program
    never re-traces, and a second process deserializes instead of
    compiling. ``fn``'s identity defaults to its code fingerprint;
    pass ``fn_key`` when ``fn`` is a bound method / closure whose
    *instance state* shapes the trace (the fingerprint cannot see it)
    and the caller can name that state (e.g. a plan fingerprint).
    Functions that are neither stably fingerprintable nor covered by a
    caller ``fn_key`` are compiled directly and NOT cached — silent
    cross-function sharing (or pinning a per-request object graph in
    the never-evicted store) is worse than a re-trace.
    """
    import jax

    have_in = in_shardings is not None
    have_out = out_shardings is not None
    if have_in != have_out:
        raise ValueError(
            "compile_with_plan requires BOTH in_shardings and "
            "out_shardings when using pjit; pass neither to use the "
            "shard_map fallback")
    if fn_key is None:
        ok, fp = compile_cache.function_fingerprint(fn)
        if ok and getattr(fn, "__self__", None) is None:
            fn_key = ("fp", fp)
    shareable = fn_key is not None
    if have_in:
        wrapper = lambda f: jax.jit(               # noqa: E731
            f, in_shardings=in_shardings, out_shardings=out_shardings,
            donate_argnums=donate_argnums)
        branch = ("pjit",)
        # the program's owner before GSPMD: a PanelExecutor whose
        # taskpool registers a mesh lowering writes the per-chip program
        # and its collectives itself, and the key says so (the caller's
        # fn_key names the one-chip program alone)
        owner = getattr(fn, "__self__", None)
        if mesh is not None and isinstance(owner, PanelExecutor) \
                and fn.__func__ is PanelExecutor.run_state:
            own = owner.partitioned(mesh, in_shardings, out_shardings)
            if own is not None:
                fn, lowering_key = own
                shareable = shareable and lowering_key is not None
                branch = ("runtime_partition", lowering_key)
        if not shareable:
            return wrapper(fn)
        full_key = branch + (fn_key, key, _mesh_repr(mesh),
                             _sharding_repr(in_shardings),
                             _sharding_repr(out_shardings),
                             tuple(donate_argnums)
                             if not isinstance(donate_argnums, int)
                             else donate_argnums)
        return compile_cache.cached_jit(
            fn, key=full_key, example_args=example_args,
            jit_wrapper=wrapper)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        axis = mesh.axis_names[0]
        ispec = in_specs if in_specs is not None else P(axis)
        ospec = out_specs if out_specs is not None else P(axis)
        mapped = jax.shard_map(fn, mesh=mesh, in_specs=ispec,
                               out_specs=ospec)
        if not shareable:
            return jax.jit(mapped, donate_argnums=donate_argnums)
        full_key = ("shard_map", fn_key, key, _mesh_repr(mesh),
                    _sharding_repr(ispec), _sharding_repr(ospec))
        return compile_cache.cached_jit(
            mapped, key=full_key, example_args=example_args,
            donate_argnums=donate_argnums)
    if not shareable:
        return jax.jit(fn, donate_argnums=donate_argnums)
    return compile_cache.cached_jit(
        fn, key=("jit", fn_key, key), example_args=example_args,
        donate_argnums=donate_argnums)


def run_sharded(executor, mesh=None, n_devices: Optional[int] = None,
                axis: str = "tiles") -> Dict[str, Any]:
    """Execute the plan with mesh-sharded stores: one pjit-compiled XLA
    program for the whole DAG, collectives inserted by the partitioner.

    Goes through :func:`compile_with_plan` with explicit in/out
    ``NamedSharding``s (the preferential-pjit path), so the program
    lands in the shared/persistent executor cache keyed by (plan, mesh,
    shardings, shapes) and is reused across runs and processes.

    Returns the (unsharded, unpadded) result stores and writes tiles back
    to the plan's collections.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if mesh is None:
        mesh = make_mesh(n_devices, axis)
    stores = executor.make_stores()
    orig_sizes = {k: v.shape[0] for k, v in stores.items()}
    sharded = shard_stores(stores, mesh, axis)

    sharding = NamedSharding(mesh, P(axis))
    shardings = {name: sharding for name in sharded}
    sds = {name: jax.ShapeDtypeStruct(v.shape, v.dtype)
           for name, v in sharded.items()}
    from .wavefront import plan_structure_fingerprint
    ok, plan_fp = plan_structure_fingerprint(executor.plan)
    fps = sorted({executor._body_fp(grp.tc) or "unstable"
                  for wave in executor.plan.waves for grp in wave})
    stable = ok and "unstable" not in fps
    # run_arrays is a bound method: its trace depends on the plan, so
    # the fn identity is the plan+body fingerprint — and when THAT is
    # unstable, fn_key stays None and compile_with_plan compiles
    # without caching (a cached entry would pin the executor and its
    # tile data in the never-evicted store under a one-shot id key)
    fn = compile_with_plan(
        executor.run_arrays, mesh=mesh, in_shardings=(shardings,),
        out_shardings=shardings,
        example_args=(sds,) if stable else None,
        fn_key=("run_sharded", plan_fp, tuple(fps)) if stable else None)
    out = fn(sharded)
    for v in out.values():
        v.block_until_ready()
    clipped = {k: v[:orig_sizes[k]] for k, v in out.items()}
    for name, dc in executor.plan.collections.items():
        if dc.scratch:
            continue      # intra-DAG temporaries: no host write-back
        dc.from_stacked(clipped[name][:-1], executor.plan.slot_maps[name])
    return clipped
