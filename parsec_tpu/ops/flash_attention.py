"""Flash attention as a hand-written pallas TPU kernel.

The framework's hot-op escape hatch: XLA fuses most elementwise work
into matmuls, but attention's online-softmax recurrence leaves HBM
round-trips between the s = QKᵀ, softmax, and PV stages that XLA does
not eliminate at long sequence lengths. This kernel keeps the whole
per-(head, q-block) recurrence in VMEM scratch across the KV grid
dimension — the standard flash-attention tiling (Dao et al. 2022)
expressed in pallas (see /opt/skills/guides/pallas_guide.md; reference
runtime analog: user .jdf BODY CUDA kernels — the runtime schedules
them, the kernel owns the device).

Public entry: :func:`flash_attention` over ``(S, H, dh)`` operands (the
layout `compiled.ring_attention` uses). Runs in pallas interpret mode
only where the caller says so or the run was started on the CPU
platform (tests, CPU dry runs) — never because a chip failed to come up.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import mca_param
from ..utils.jax_platform import cpu_requested

mca_param.register("ops.flash_attention_block_q", 1024,
                   help="flash-attention query block size")
mca_param.register("ops.flash_attention_block_k", 1024,
                   help="flash-attention key/value block size")
# block-size note (v5e, S=16384, H=8, dh=64): 1024/1024 measured 3.2 ms
# vs 9.9 ms at 512/512 and 11.1 ms at 1024/512 — the (bq, bk) score
# tile must be large enough to amortize the dh-narrow QK^T contraction;
# 2048-query blocks fail to compile (VMEM) and 2048-key blocks regress.

_NEG = -1e30          # finite -inf: exp() stays NaN-free for fully
#                       masked rows (same convention as ring_attention)
_MINLANE = 128        # f32 lane tile: scalar-per-row state is stored
#                       broadcast to a full lane tile


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
               l_ref, *, scale: float, causal: bool, bq: int, bk: int,
               prec=None):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    qi = pl.program_id(1)
    # causal: KV blocks entirely in the future contribute nothing —
    # skip their compute outright (halves the causal work)
    live = (qi + 1) * bq > ki * bk if causal else ki >= 0

    @pl.when(live)
    def _fold():
        q = q_ref[0]                 # (bq, dh)
        k = k_ref[0]                 # (bk, dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec) * scale

        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, _NEG)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        if causal:
            # fully-masked rows: keep p exactly zero (m_new == _NEG)
            p = jnp.where(s > _NEG / 2, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        # log-sum-exp per query row: the merge key for combining partial
        # attention states (ring attention folds visiting KV blocks by
        # merging (o, lse) pairs). Stored broadcast across the lane tile
        # — TPU lowering requires lane-aligned output blocks.
        lse_ref[0] = jnp.broadcast_to(
            (m_ref[:, 0] + jnp.log(l))[:, None], lse_ref.shape[1:])


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: int = 0, block_k: int = 0,
                    interpret: Optional[bool] = None,
                    return_lse: bool = False):
    """Softmax attention over ``(S, H, dh)`` operands via the pallas
    flash kernel. ``interpret=None`` interprets iff the run was started
    on the CPU platform (``JAX_PLATFORMS=cpu``: tests and CPU dry runs
    execute the identical kernel); anywhere else Mosaic compiles it, and
    a chip that did not come up is an error, not an interpreted run.
    ``return_lse=True`` also returns the per-row log-sum-exp ``(S, H)``
    — the merge key for combining partial attention states (ring
    attention)."""
    S, H, dh = q.shape
    Sk = k.shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    bq = block_q or int(mca_param.get("ops.flash_attention_block_q", 1024))
    bk = block_k or int(mca_param.get("ops.flash_attention_block_k", 1024))
    bq = min(bq, S)
    bk = min(bk, Sk)
    if not block_q:          # default blocks adapt to the sequence; an
        while S % bq:        # explicit block size is a strict contract
            bq //= 2
    if not block_k:
        while Sk % bk:
            bk //= 2
    if S % bq or Sk % bk:
        raise ValueError(f"sequence lengths ({S}, {Sk}) must divide the "
                         f"block sizes ({bq}, {bk})")
    if interpret is None:
        interpret = cpu_requested()

    # (S, H, dh) → (H, S, dh); pad head dim to the f32 lane tile
    qT = jnp.swapaxes(q, 0, 1).astype(jnp.float32)
    kT = jnp.swapaxes(k, 0, 1).astype(jnp.float32)
    vT = jnp.swapaxes(v, 0, 1).astype(jnp.float32)
    dh_p = max(_MINLANE, ((dh + _MINLANE - 1) // _MINLANE) * _MINLANE)
    if dh_p != dh:
        pad = [(0, 0), (0, 0), (0, dh_p - dh)]
        qT, kT, vT = (jnp.pad(x, pad) for x in (qT, kT, vT))

    # honor the global MXU precision knob like every other tile kernel
    # (ops.matmul_precision): "highest" runs the kernel's dots in full
    # f32 — the TPU test mode and precision-variant benches rely on it.
    # Mosaic's dot lowering supports only DEFAULT and HIGHEST; "high"
    # (3-pass, fine for jnp kernels) maps to HIGHEST here rather than
    # failing to compile.
    from .tile_kernels import matmul_precision
    prec = matmul_precision()
    if prec == "high":
        prec = "highest"
    kern = functools.partial(_fa_kernel, scale=scale, causal=causal,
                             bq=bq, bk=bk, prec=prec)
    out, lse = pl.pallas_call(
        kern,
        grid=(H, S // bq, Sk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, dh_p), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((1, bk, dh_p), lambda h, qi, ki: (h, ki, 0)),
            pl.BlockSpec((1, bk, dh_p), lambda h, qi, ki: (h, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dh_p), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((1, bq, _MINLANE), lambda h, qi, ki: (h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((H, S, dh_p), q.dtype),
            jax.ShapeDtypeStruct((H, S, _MINLANE), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dh_p), jnp.float32),
            pltpu.VMEM((bq, _MINLANE), jnp.float32),
            pltpu.VMEM((bq, _MINLANE), jnp.float32),
        ],
        interpret=interpret,
    )(qT, kT, vT)
    o = jnp.swapaxes(out[:, :, :dh], 0, 1)
    if return_lse:
        return o, jnp.swapaxes(lse[:, :, 0], 0, 1)
    return o


def merge_attention_states(o1, lse1, o2, lse2):
    """Combine two partial softmax-attention results over disjoint key
    sets: ``o_i`` (..., dh) normalized partial outputs, ``lse_i`` (...)
    their log-sum-exps. Returns the merged ``(o, lse)`` — the standard
    flash/ring state-merge identity."""
    M = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - M)
    w2 = jnp.exp(lse2 - M)
    den = w1 + w2
    o = (o1 * w1[..., None] + o2 * w2[..., None]) / den[..., None]
    return o, M + jnp.log(den)
