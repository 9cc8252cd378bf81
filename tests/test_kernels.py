"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.kernels.importance import ops as imp_ops
from repro.kernels.importance.ref import channel_importance_ref
from repro.kernels.masked_merge import ops as mm_ops
from repro.kernels.masked_merge.ref import masked_merge_ref
from repro.kernels.sparse_agg import ops as agg_ops
from repro.kernels.sparse_agg.ref import masked_weighted_sum_ref

SHAPES_2D = [(8, 16), (64, 128), (100, 300), (7, 1000), (1000, 7),
             (256, 512), (257, 513), (3, 3)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES_2D)
@pytest.mark.parametrize("dtype", DTYPES)
def test_importance_kernel_sweep(shape, dtype):
    key = jax.random.PRNGKey(hash(shape) % 2**31)
    wo = jax.random.normal(key, shape).astype(dtype)
    wn = (wo.astype(jnp.float32)
          + 0.1 * jax.random.normal(jax.random.fold_in(key, 1), shape)
          ).astype(dtype)
    got = imp_ops.channel_importance(wo, wn, channel_axis=0)
    want = channel_importance_ref(wo, wn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-5, atol=1e-5)


@pytest.mark.parametrize("rank_shape", [(4, 6, 10), (3, 4, 5, 6)])
@pytest.mark.parametrize("axis", [0, -1])
def test_importance_kernel_rank_axis(rank_shape, axis):
    key = jax.random.PRNGKey(0)
    wo = jax.random.normal(key, rank_shape)
    wn = wo * 1.07
    got = imp_ops.channel_importance(wo, wn, channel_axis=axis)
    c = rank_shape[axis]
    ref_in_o = jnp.moveaxis(wo, axis, 0).reshape(c, -1)
    ref_in_n = jnp.moveaxis(wn, axis, 0).reshape(c, -1)
    want = channel_importance_ref(ref_in_o, ref_in_n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-5)


@pytest.mark.parametrize("n,c,f", [(2, 8, 16), (4, 64, 128), (7, 100, 300),
                                   (16, 33, 70), (32, 128, 256),
                                   (13, 40, 600)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_sparse_agg_kernel_sweep(n, c, f, dtype):
    key = jax.random.PRNGKey(n * 1000 + c)
    sw = jax.random.normal(key, (n, c, f)).astype(dtype)
    sm = (jax.random.uniform(jax.random.fold_in(key, 1), (n, c, 1))
          > 0.5).astype(dtype)
    wts = jax.random.uniform(jax.random.fold_in(key, 2), (n,)) + 0.5
    num, den = agg_ops.masked_weighted_sum(sw, sm, wts)
    wn, wd = masked_weighted_sum_ref(
        sw, jnp.broadcast_to(sm, sw.shape), wts)
    np.testing.assert_allclose(np.asarray(num), np.asarray(wn),
                               rtol=5e-3 if dtype == jnp.bfloat16 else 3e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(den), np.asarray(wd),
                               rtol=3e-5, atol=1e-5)


@pytest.mark.parametrize("n", [5, 13, 33])
@pytest.mark.parametrize("shape", [(3, 3, 4, 24), (40, 130), (600,)])
def test_sparse_agg_kernel_channel_mask(n, shape):
    """A per-channel mask (size 1 on every axis but the last), as the
    engine builds it, broadcast by the wrapper over a stack whose trailing
    axes fold into rows; client counts off the 8-client slab exercise the
    zeroed tail of the last slab."""
    key = jax.random.PRNGKey(n + len(shape))
    sw = jax.random.normal(key, (n, *shape))
    sm = (jax.random.uniform(jax.random.fold_in(key, 1),
                             (n,) + (1,) * (len(shape) - 1) + shape[-1:])
          > 0.5).astype(jnp.float32)
    wts = jax.random.uniform(jax.random.fold_in(key, 2), (n,)) + 0.5
    num, den = agg_ops.masked_weighted_sum(sw, sm, wts)
    lanes = shape[-1]
    wn, wd = masked_weighted_sum_ref(
        sw.reshape(n, -1, lanes),
        jnp.broadcast_to(sm, sw.shape).reshape(n, -1, lanes), wts)
    assert num.shape == den.shape == shape
    np.testing.assert_allclose(np.asarray(num).reshape(wn.shape),
                               np.asarray(wn), rtol=3e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(den).reshape(wd.shape),
                               np.asarray(wd), rtol=3e-5, atol=1e-5)


@pytest.mark.parametrize("c,f", [(8, 16), (64, 128), (100, 37), (7, 7),
                                 (300, 500)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_masked_merge_kernel_sweep(c, f, dtype):
    key = jax.random.PRNGKey(c * 100 + f)
    g = jax.random.normal(key, (c, f)).astype(dtype)
    l = jax.random.normal(jax.random.fold_in(key, 1), (c, f)).astype(dtype)
    m = (jax.random.uniform(jax.random.fold_in(key, 2), (c,))
         > 0.5).astype(jnp.float32)
    got = mm_ops.masked_merge(g, l, m, channel_axis=0)
    want = masked_merge_ref(g, l, m)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-5)


@settings(max_examples=20, deadline=None)
@given(c=st.integers(1, 80), f=st.integers(1, 120), seed=st.integers(0, 99))
def test_property_importance_matches_oracle(c, f, seed):
    key = jax.random.PRNGKey(seed)
    wo = jax.random.normal(key, (c, f))
    wn = wo + jax.random.normal(jax.random.fold_in(key, 1), (c, f))
    got = imp_ops.channel_importance(wo, wn, channel_axis=0)
    want = channel_importance_ref(wo, wn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-5, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(c=st.integers(1, 60), f=st.integers(1, 90), seed=st.integers(0, 99))
def test_property_merge_is_select(c, f, seed):
    """Merged output rows equal either G or L exactly (binary mask)."""
    key = jax.random.PRNGKey(seed)
    g = jax.random.normal(key, (c, f))
    l = jax.random.normal(jax.random.fold_in(key, 1), (c, f))
    m = (jax.random.uniform(jax.random.fold_in(key, 2), (c,))
         > 0.5).astype(jnp.float32)
    out = np.asarray(mm_ops.masked_merge(g, l, m, channel_axis=0))
    gn, ln = np.asarray(g), np.asarray(l)
    for i in range(c):
        src = gn[i] if float(m[i]) == 1.0 else ln[i]
        np.testing.assert_allclose(out[i], src, rtol=1e-6)


# ----------------------------- flash attention ------------------------------

import math

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import gqa_attention_ref


@pytest.mark.parametrize("shape", [(2, 64, 4, 2, 32), (1, 100, 8, 8, 16),
                                   (2, 96, 4, 1, 32), (1, 130, 4, 2, 48)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 24), (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(shape, causal, window, dtype):
    b, s, h, hkv, hd = shape
    key = jax.random.PRNGKey(sum(shape))
    q = jax.random.normal(key, (b, s, h, hd)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (b, s, hkv, hd)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (b, s, hkv, hd)).astype(dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          bq=32, bk=16, interpret=True)
    want = gqa_attention_ref(q, k, v, causal=causal, window=window)
    tol = 3e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@settings(max_examples=15, deadline=None)
@given(s=st.integers(8, 140), hd=st.sampled_from([16, 32, 64]),
       seed=st.integers(0, 99))
def test_property_flash_matches_ref(s, hd, seed):
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (1, s, 4, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, s, 2, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, s, 2, hd))
    got = flash_attention(q, k, v, causal=True, bq=32, bk=32,
                          interpret=True)
    want = gqa_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-5, atol=5e-5)
