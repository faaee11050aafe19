"""Pallas kernels vs ref.py oracles — shape/dtype sweeps, interpret mode."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


@pytest.mark.parametrize("n,d,nseg,dtype", [
    (64, 8, 4, jnp.float32),
    (1000, 32, 16, jnp.float32),
    (513, 128, 7, jnp.bfloat16),
    (2048, 16, 64, jnp.float32),
])
def test_segment_reduce_sweep(n, d, nseg, dtype):
    rs = np.random.RandomState(n)
    v = jnp.asarray(rs.randn(n, d)).astype(dtype)
    ids = jnp.asarray(rs.randint(-1, nseg, n).astype(np.int32))
    got = ops.segment_reduce(v, ids, nseg, interpret=True)
    want = ref.segment_reduce(v, ids, nseg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)


@given(st.integers(1, 3000), st.integers(2, 64))
@settings(max_examples=20, deadline=None)
def test_hash_partition_property(n, buckets):
    rs = np.random.RandomState(n * buckets)
    t = jnp.asarray(rs.randint(-1, 100000, n).astype(np.int32))
    ids, hist = ops.hash_partition(t, buckets, interpret=True)
    rids, rhist = ref.hash_partition(t, buckets)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(rhist))
    # histogram counts all valid tokens exactly once
    assert int(np.asarray(hist).sum()) == int((np.asarray(t) >= 0).sum())


@pytest.mark.parametrize("n,buckets", [
    (1, 2),        # single token
    (1023, 8),     # one short of the smallest (8 x 128) tile
    (1024, 8),     # exactly one tile
    (1025, 8),     # one into the next 8 rows (kernel pads with -1)
    (3000, 16),    # ragged tail
    (70000, 16),   # two grid steps of BLOCK_ROWS x 128: the histogram accumulates
])
def test_hash_partition_interpret_matches_ref(n, buckets):
    """Satellite: bucket ids match kernels/ref.py and the histogram counts
    every valid token exactly once, across block-boundary sizes."""
    rs = np.random.RandomState(n + buckets)
    t = jnp.asarray(rs.randint(0, 100000, n).astype(np.int32))
    ids, hist = ops.hash_partition(t, buckets, interpret=True)
    rids, rhist = ref.hash_partition(t, buckets)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(rhist))
    assert int(np.asarray(hist).sum()) == n
    assert np.asarray(ids).shape == (n,)  # kernel's own block padding is stripped


def test_hash_partition_excludes_padding_tokens():
    """Satellite: -1 padding tokens get bucket id -1 and are not counted
    in the histogram — including a shard that is entirely padding."""
    rs = np.random.RandomState(11)
    toks = rs.randint(0, 500, 700).astype(np.int32)
    toks[::7] = -1  # sprinkle padding mid-stream, not just at the tail
    ids, hist = ops.hash_partition(jnp.asarray(toks), 8, interpret=True)
    ids, hist = np.asarray(ids), np.asarray(hist)
    np.testing.assert_array_equal(ids[toks == -1], -1)
    assert (ids[toks >= 0] >= 0).all() and (ids[toks >= 0] < 8).all()
    assert int(hist.sum()) == int((toks >= 0).sum())

    all_pad = jnp.full((256,), -1, jnp.int32)
    ids2, hist2 = ops.hash_partition(all_pad, 4, interpret=True)
    np.testing.assert_array_equal(np.asarray(ids2), np.full((256,), -1))
    np.testing.assert_array_equal(np.asarray(hist2), np.zeros((4,), np.int32))


@pytest.mark.parametrize("n", [100, 16384, 40000])
def test_ring_fused_step_sweep(n):
    rs = np.random.RandomState(n)
    acc = jnp.asarray(rs.randn(n).astype(np.float32))
    wire = jnp.asarray(rs.randn(n).astype(np.float32)).astype(jnp.bfloat16)
    ga, gw = ops.ring_fused_step(acc, wire, interpret=True)
    ra, rw = ref.ring_fused_step(acc, wire)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(ra), rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(gw).view(np.uint16), np.asarray(rw).view(np.uint16))


@pytest.mark.parametrize("b,h,s,d,causal,dtype", [
    (1, 2, 128, 64, True, jnp.float32),
    (2, 3, 256, 64, True, jnp.float32),
    (2, 2, 256, 128, False, jnp.float32),
    (1, 2, 384, 64, True, jnp.bfloat16),
])
def test_flash_attention_sweep(b, h, s, d, causal, dtype):
    rs = np.random.RandomState(s + d)
    q = jnp.asarray(rs.randn(b, h, s, d)).astype(dtype)
    k = jnp.asarray(rs.randn(b, h, s, d)).astype(dtype)
    v = jnp.asarray(rs.randn(b, h, s, d)).astype(dtype)
    got = ops.flash_attention(q, k, v, causal=causal, interpret=True)
    want = ref.flash_attention(q, k, v, causal=causal)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_flash_matches_model_chunked_attention():
    """The Pallas kernel and the model's chunked path agree (same math)."""
    from repro.models.attention import chunked_attention

    rs = np.random.RandomState(0)
    b, h, s, d = 2, 2, 256, 32
    q = jnp.asarray(rs.randn(b, h, s, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, h, s, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, h, s, d).astype(np.float32))
    flash = ops.flash_attention(q, k, v, causal=True, interpret=True)
    # chunked path uses (b, s, h, d) layout
    ch = chunked_attention(
        jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
        scale=1.0 / np.sqrt(d), causal=True, impl="triangle",
        chunk_q=64, chunk_k=64)
    np.testing.assert_allclose(
        np.asarray(jnp.moveaxis(ch, 2, 1)), np.asarray(flash), rtol=3e-4, atol=3e-4)


def test_segment_reduce_is_wordcount_reducer():
    """kernel(one-hot counts) == wordcount oracle for a token stream."""
    rs = np.random.RandomState(3)
    vocab = 32
    toks = rs.randint(0, vocab, 500).astype(np.int32)
    ones = jnp.ones((500, 1), jnp.float32)
    counts = ops.segment_reduce(ones, jnp.asarray(toks), vocab, interpret=True)
    from repro.core.wordcount import wordcount_reference

    np.testing.assert_array_equal(
        np.asarray(counts)[:, 0].astype(np.int64), wordcount_reference([toks], vocab))
