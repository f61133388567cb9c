"""Sequence-parallel attention tests on the 8-device virtual CPU mesh:
ring attention (ppermute) and Ulysses (all-to-all) vs dense reference."""

import numpy as np
import pytest

from parsec_tpu.compiled.ring_attention import (dense_attention,
                                                ring_attention,
                                                ulysses_attention)
from parsec_tpu.compiled.spmd import make_mesh


def _qkv(rng, S=64, H=8, dh=16):
    shape = (S, H, dh)
    return (rng.standard_normal(shape).astype(np.float32),
            rng.standard_normal(shape).astype(np.float32),
            rng.standard_normal(shape).astype(np.float32))


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh(8, axis="seq")     # conftest's virtual CPU mesh


def _shard_seq(mesh, *arrays):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = NamedSharding(mesh, P("seq"))
    return [jax.device_put(a, sh) for a in arrays]


def test_ring_attention_matches_dense(rng, mesh8):
    import jax
    q, k, v = _qkv(rng)
    qs, ks, vs = _shard_seq(mesh8, q, k, v)
    out = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh8))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dense_attention(q, k, v)),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_long_sequence(rng, mesh8):
    import jax
    q, k, v = _qkv(rng, S=256, H=4, dh=32)
    qs, ks, vs = _shard_seq(mesh8, q, k, v)
    out = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh8))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dense_attention(q, k, v)),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_kv_chunked(rng, mesh8):
    """Flash-style inner chunking must be numerically identical."""
    import jax
    q, k, v = _qkv(rng, S=128, H=4, dh=16)
    qs, ks, vs = _shard_seq(mesh8, q, k, v)
    out = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh8,
                                                 kv_chunk=4))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dense_attention(q, k, v)),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_kv_chunk_must_divide(rng, mesh8):
    q, k, v = _qkv(rng, S=64, H=2, dh=8)
    qs, ks, vs = _shard_seq(mesh8, q, k, v)
    import jax
    with pytest.raises(ValueError):
        jax.jit(lambda a, b, c: ring_attention(
            a, b, c, mesh8, kv_chunk=3))(qs, ks, vs)


@pytest.mark.parametrize("chunk", [None, 4])
def test_ring_attention_causal(rng, mesh8, chunk):
    """Causal masking over global positions, with and without the
    flash-style inner chunking."""
    import jax
    q, k, v = _qkv(rng, S=64, H=4, dh=16)
    qs, ks, vs = _shard_seq(mesh8, q, k, v)
    out = jax.jit(lambda a, b, c: ring_attention(
        a, b, c, mesh8, kv_chunk=chunk, causal=True))(qs, ks, vs)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(dense_attention(q, k, v, causal=True)),
        rtol=2e-4, atol=2e-4)


def test_ulysses_matches_dense(rng, mesh8):
    import jax
    q, k, v = _qkv(rng)
    qs, ks, vs = _shard_seq(mesh8, q, k, v)
    out = jax.jit(
        lambda a, b, c: ulysses_attention(a, b, c, mesh8))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dense_attention(q, k, v)),
                               rtol=2e-4, atol=2e-4)


def test_ulysses_rejects_indivisible_heads(rng, mesh8):
    q, k, v = _qkv(rng, H=6)
    with pytest.raises(ValueError):
        ulysses_attention(q, k, v, mesh8)


def test_ring_output_sharding_preserved(rng, mesh8):
    """The output must stay sequence-sharded (no implicit gather)."""
    import jax
    q, k, v = _qkv(rng)
    qs, ks, vs = _shard_seq(mesh8, q, k, v)
    out = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh8))(qs, ks, vs)
    assert len(out.sharding.device_set) == 8


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_flash_impl(rng, mesh8, causal):
    """impl='flash' (pallas kernel per visiting block, (o, lse) merge)
    must match the dense reference — same kernel via interpret mode."""
    import jax
    q, k, v = _qkv(rng, S=128, H=2, dh=32)
    qs, ks, vs = _shard_seq(mesh8, q, k, v)
    out = jax.jit(lambda a, b, c: ring_attention(
        a, b, c, mesh8, causal=causal, impl="flash"))(qs, ks, vs)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_ring_attention_flash_single_device(rng):
    """n=1 mesh: the flash path reduces to one kernel call."""
    import jax
    q, k, v = _qkv(rng, S=128, H=2, dh=32)
    mesh = make_mesh(1, axis="seq")
    out = jax.jit(lambda a, b, c: ring_attention(
        a, b, c, mesh, impl="flash"))(q, k, v)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dense_attention(q, k, v)),
                               rtol=2e-3, atol=2e-3)


def test_ring_attention_bad_impl(rng):
    mesh = make_mesh(1, axis="seq")
    q, k, v = _qkv(rng, S=64, H=2, dh=16)
    with pytest.raises(ValueError, match="impl"):
        ring_attention(q, k, v, mesh, impl="nope")
