"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The compiled XLA twins of the streaming-attention task DAG
(``parsec_tpu.algorithms.transformer``). Where the runtime form ships the
online-softmax state between tasks through activations (the reference's
chain-dataflow pattern, SURVEY §5 "long-context"), these shard the
sequence over a ``jax.sharding.Mesh`` axis and move KV blocks with XLA
collectives riding ICI:

- :func:`ring_attention` — each device holds one Q/K/V sequence block;
  KV blocks rotate around the ring with ``lax.ppermute`` while every
  device folds the visiting block into its online-softmax state
  (`Ring Attention with Blockwise Transformers`, Liu et al. 2023 —
  PAPERS.md). Peak memory per device is O(block²) independent of the
  full sequence length; the permute overlaps with the block compute.
- :func:`ulysses_attention` — all-to-all re-shard: scatter heads /
  gather sequence (`DeepSpeed-Ulysses`, Jacobs et al. 2023), dense
  per-head attention locally, inverse all-to-all back to
  sequence-sharded. One collective pair instead of N-1 permutes; needs
  n_heads divisible by the mesh axis size.

Both are pure jittable functions of sequence-sharded operands: drop them
under ``pjit``/``shard_map`` with the rest of a model and XLA fuses and
overlaps the collectives.
"""

from __future__ import annotations

import math
from typing import Optional


_MASKED = -1e30      # finite "minus infinity": keeps exp() NaN-free when
                     # an entire row is masked (fully-future KV blocks)


def _online_softmax_step(q_blk, k_cur, v_cur, acc, m, l, scale,
                         qpos=None, kpos=None):
    """One online-softmax fold. ``qpos``/``kpos``: global sequence
    positions of the query/key rows — when given, causal masking
    (key position ≤ query position) is applied."""
    import jax.numpy as jnp
    from ..ops.tile_kernels import matmul_precision

    s = jnp.matmul(q_blk, jnp.swapaxes(k_cur, -1, -2),
                   preferred_element_type=jnp.float32,
                   precision=matmul_precision()) * scale
    allowed = None
    if qpos is not None:
        allowed = qpos[:, None] >= kpos[None, :]
        s = jnp.where(allowed, s, _MASKED)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    if allowed is not None:
        # a fully-masked row would give m_new == _MASKED and p == 1 for
        # every masked entry (uniform attention over forbidden keys);
        # zeroing masked p makes the helper safe standalone even though
        # callers currently fold the resident diagonal block first and
        # skip fully-future blocks
        p = jnp.where(allowed, p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.matmul(
        p, v_cur, preferred_element_type=jnp.float32,
        precision=matmul_precision())
    return acc_new, m_new, l_new


def ring_attention(q, k, v, mesh, axis: str = "seq",
                   kv_chunk: Optional[int] = None,
                   causal: bool = False, impl: str = "xla"):
    """Multi-head attention with the sequence sharded over mesh ``axis``.

    ``q/k/v``: float arrays of shape ``(S, H, dh)`` (sequence-major) laid
    out ``PartitionSpec(axis)`` over ``mesh``. Returns the attention
    output in the same layout.

    ``kv_chunk``: fold each visiting KV block in chunks of this many
    keys (flash-attention-style inner loop) — peak score memory drops
    from O(Sb²) to O(Sb·kv_chunk) per head, which is what lets a single
    chip run long blocks. Must divide the per-device block length.

    ``causal``: apply causal masking over GLOBAL sequence positions —
    each device masks the visiting KV block against its query block's
    position range, so fully-future blocks contribute nothing while the
    ring still rotates uniformly.

    ``impl``: local-block computation. ``"xla"`` — the jnp online-
    softmax fold (works everywhere). ``"flash"`` — the pallas flash
    kernel (ops.flash_attention) per visiting KV block, partial results
    combined with the (o, lse) state merge; measured ~6× the xla fold
    at S=16384 on a v5e chip (the S×S score round-trips through HBM are
    what the kernel eliminates). ``kv_chunk`` maps to the kernel's key
    block size. The ppermute ring is identical in both modes.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P
    if impl not in ("xla", "flash"):
        raise ValueError(f"ring_attention impl must be xla|flash: {impl!r}")

    n = mesh.shape[axis]
    scale = 1.0 / math.sqrt(q.shape[-1])
    perm = [(i, (i + 1) % n) for i in range(n)]

    if impl == "flash":
        from ..ops.flash_attention import (flash_attention,
                                           merge_attention_states)

        def block_flash(q_blk, k_blk, v_blk):
            my = lax.axis_index(axis)
            bk = min(kv_chunk, k_blk.shape[0]) if kv_chunk else 0

            def fold(k_cur, v_cur, diag):
                o, lse = flash_attention(
                    q_blk, k_cur, v_cur, causal=diag, scale=scale,
                    block_k=bk, return_lse=True)
                return o.astype(jnp.float32), lse

            # resident block first: with causal masking it is the
            # diagonal block (kernel-level causal mask); visiting blocks
            # are either fully past (unmasked) or fully future (skipped)
            o_c, lse_c = fold(k_blk, v_blk, causal)

            def step(carry, t):
                k_cur, v_cur, o_c, lse_c = carry
                k_cur = lax.ppermute(k_cur, axis, perm)
                v_cur = lax.ppermute(v_cur, axis, perm)
                kv_owner = (my - t - 1) % n

                def do_fold(op):
                    k_, v_, o1, l1 = op
                    o2, l2 = fold(k_, v_, False)
                    return merge_attention_states(o1, l1, o2, l2)

                if causal:
                    o_c, lse_c = lax.cond(
                        kv_owner < my, do_fold,
                        lambda op: (op[2], op[3]),
                        (k_cur, v_cur, o_c, lse_c))
                else:
                    o_c, lse_c = do_fold((k_cur, v_cur, o_c, lse_c))
                return (k_cur, v_cur, o_c, lse_c), None

            (k_f, v_f, o_c, lse_c), _ = lax.scan(
                step, (k_blk, v_blk, o_c, lse_c), jnp.arange(n - 1))
            return o_c.astype(q_blk.dtype)

        # check_vma=False: pallas_call's out_shape carries no varying-
        # across-mesh annotation, which the shard_map vma checker
        # rejects; the kernel is per-device-local so the check adds
        # nothing here
        return jax.shard_map(block_flash, mesh=mesh,
                             in_specs=(P(axis), P(axis), P(axis)),
                             out_specs=P(axis), check_vma=False)(q, k, v)

    def block(q_blk, k_blk, v_blk):
        # [Sb, H, dh] → head-major [H, Sb, dh] for batched matmuls
        qh = jnp.swapaxes(q_blk, 0, 1).astype(jnp.float32)
        kh = jnp.swapaxes(k_blk, 0, 1).astype(jnp.float32)
        vh = jnp.swapaxes(v_blk, 0, 1).astype(jnp.float32)
        Sb = qh.shape[1]
        my = lax.axis_index(axis)
        qpos = my * Sb + jnp.arange(Sb) if causal else None

        def fold_block(k_cur, v_cur, acc, m, l, kv_owner):
            # positions are always threaded; masking is keyed on qpos
            # (None in non-causal mode) so XLA DCEs the unused kpos
            kpos = kv_owner * Sb + jnp.arange(Sb)
            if kv_chunk is None or kv_chunk >= Sb:
                return _online_softmax_step(qh, k_cur, v_cur, acc, m, l,
                                            scale, qpos, kpos)
            if Sb % kv_chunk:
                raise ValueError(
                    f"kv_chunk={kv_chunk} must divide block length {Sb}")
            nch = Sb // kv_chunk
            # chunk axis leads so scan consumes chunks directly as xs
            kc = jnp.moveaxis(
                k_cur.reshape(k_cur.shape[0], nch, kv_chunk, -1), 1, 0)
            vc = jnp.moveaxis(
                v_cur.reshape(v_cur.shape[0], nch, kv_chunk, -1), 1, 0)

            def chunk_step(carry, xs):
                acc, m, l = carry
                kcur, vcur, kp = xs
                acc, m, l = _online_softmax_step(
                    qh, kcur, vcur, acc, m, l, scale, qpos, kp)
                return (acc, m, l), None

            (acc, m, l), _ = lax.scan(
                chunk_step, (acc, m, l),
                (kc, vc, kpos.reshape(nch, kv_chunk)))
            return acc, m, l

        def step(carry, t):
            # permute first, fold second: the local block is folded
            # before the loop, so exactly n-1 rotations happen — no
            # wasted final ppermute (XLA can't peel a scan iteration)
            k_cur, v_cur, acc, m, l = carry
            k_cur = lax.ppermute(k_cur, axis, perm)
            v_cur = lax.ppermute(v_cur, axis, perm)
            # after t+1 rotations, the resident block came from rank
            # (my - t - 1) mod n — its global positions drive the mask
            kv_owner = (my - t - 1) % n
            if causal:
                # fully-future blocks contribute nothing: skip their fold
                # (local compute only — the ppermute above stays uniform
                # across devices, so the ring itself is unaffected)
                acc, m, l = lax.cond(
                    kv_owner <= my,
                    lambda op: fold_block(*op),
                    lambda op: (op[2], op[3], op[4]),
                    (k_cur, v_cur, acc, m, l, kv_owner))
            else:
                acc, m, l = fold_block(k_cur, v_cur, acc, m, l, kv_owner)
            return (k_cur, v_cur, acc, m, l), None

        # fold the resident block, then rotate n-1 times; the init state
        # derives from qh so it carries the same varying manual axes as
        # the loop outputs (shard_map typing)
        acc0, m0, l0 = fold_block(
            kh, vh, qh * 0.0, qh[..., 0] * 0.0 - jnp.inf,
            qh[..., 0] * 0.0, my)
        (k_f, v_f, acc, m, l), _ = lax.scan(
            step, (kh, vh, acc0, m0, l0), jnp.arange(n - 1))
        out = acc / l[..., None]
        return jnp.swapaxes(out, 0, 1).astype(q_blk.dtype)

    fn = jax.shard_map(block, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis)),
                       out_specs=P(axis))
    return fn(q, k, v)


def ulysses_attention(q, k, v, mesh, axis: str = "seq"):
    """All-to-all sequence parallelism: re-shard (S/n, H, dh) →
    (S, H/n, dh), dense per-head attention locally, inverse all-to-all.
    ``H`` must be divisible by the mesh axis size."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    H = q.shape[1]
    if H % n:
        raise ValueError(f"n_heads={H} not divisible by mesh axis size {n}")
    scale = 1.0 / math.sqrt(q.shape[-1])

    def block(q_blk, k_blk, v_blk):
        # scatter heads, gather sequence: [Sb, H, dh] → [Sb·n, H/n, dh]
        def fwd(x):
            x = lax.all_to_all(x, axis, split_axis=1, concat_axis=0,
                               tiled=True)
            return jnp.swapaxes(x, 0, 1).astype(jnp.float32)  # [H/n, S, dh]

        from ..ops.tile_kernels import matmul_precision
        qh, kh, vh = fwd(q_blk), fwd(k_blk), fwd(v_blk)
        s = jnp.matmul(qh, jnp.swapaxes(kh, -1, -2),
                       preferred_element_type=jnp.float32,
                       precision=matmul_precision()) * scale
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.matmul(p, vh, preferred_element_type=jnp.float32,
                         precision=matmul_precision())
        # inverse: gather heads, scatter sequence
        out = jnp.swapaxes(out, 0, 1)                         # [S, H/n, dh]
        out = lax.all_to_all(out, axis, split_axis=0, concat_axis=1,
                             tiled=True)
        return out.astype(q_blk.dtype)

    fn = jax.shard_map(block, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis)),
                       out_specs=P(axis))
    return fn(q, k, v)


def dense_attention(q, k, v, causal: bool = False):
    """Unsharded reference: softmax(QKᵀ/√dh)·V per head; q/k/v (S, H, dh)."""
    import jax
    import jax.numpy as jnp

    S = q.shape[0]
    from ..ops.tile_kernels import matmul_precision
    scale = 1.0 / math.sqrt(q.shape[-1])
    qh = jnp.swapaxes(q, 0, 1).astype(jnp.float32)
    kh = jnp.swapaxes(k, 0, 1).astype(jnp.float32)
    vh = jnp.swapaxes(v, 0, 1).astype(jnp.float32)
    s = jnp.matmul(qh, jnp.swapaxes(kh, -1, -2),
                   preferred_element_type=jnp.float32,
                   precision=matmul_precision()) * scale
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, _MASKED)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.matmul(p, vh, preferred_element_type=jnp.float32,
                     precision=matmul_precision())
    return jnp.swapaxes(out, 0, 1).astype(q.dtype)
