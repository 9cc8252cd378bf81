"""Eq. (4) sparse collective primitives vs a dense-allreduce oracle.

The client-sharded engines reduce Eq. (4) (num, den) partials over the
mesh's ``clients`` axis through ``core/sparse_collective.py``.  These
tests pin the primitives standalone: compaction/scatter round trips on a
single device, and the compacted cross-shard reduction against the dense
``lax.psum`` oracle — including ragged ``k_local`` per shard (differential
dropout riding the SPMD-static buffer) and the overflow certificate.

Multi-device cases run in a subprocess with
``--xla_force_host_platform_device_count`` so the main pytest process
keeps a single device (conftest policy)."""

import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparse_collective import (compact_topk,
                                          make_federated_numden_allreduce,
                                          scatter_accumulate)

pytestmark = pytest.mark.flcore

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run_sub(code: str, devices: int = 4) -> str:
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           "PYTHONPATH": SRC, "PATH": "/usr/bin:/bin"}
    import os
    env.update({k: v for k, v in os.environ.items()
                if k not in env and k != "XLA_FLAGS"})
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


# --------------------------------------------------- single-device units

def test_compact_topk_selects_by_score():
    vals = jnp.arange(24.0).reshape(6, 4)
    scores = jnp.asarray([0.1, 5.0, 0.0, 3.0, 4.0, 0.2])
    compact, idx = compact_topk(vals, scores, 3)
    assert sorted(np.asarray(idx).tolist()) == [1, 3, 4]
    for row, i in zip(np.asarray(compact), np.asarray(idx)):
        np.testing.assert_array_equal(row, np.asarray(vals)[i])


def test_scatter_accumulate_roundtrips_compaction():
    rng = np.random.default_rng(0)
    dense = jnp.asarray(rng.normal(size=(8, 5)), jnp.float32)
    scores = jnp.asarray(rng.uniform(1.0, 2.0, 8), jnp.float32)
    compact, idx = compact_topk(dense, scores, 8)
    num, cnt = scatter_accumulate(dense.shape, compact, idx, 2.0)
    np.testing.assert_allclose(np.asarray(num), 2.0 * np.asarray(dense),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(cnt), np.full(8, 2.0))


def test_scatter_accumulate_adds_duplicate_indices():
    compact = jnp.ones((3, 2), jnp.float32)
    idx = jnp.asarray([1, 1, 2], jnp.int32)
    num, cnt = scatter_accumulate((4, 2), compact, idx,
                                  jnp.asarray([1.0, 2.0, 4.0]))
    np.testing.assert_allclose(np.asarray(cnt), [0.0, 3.0, 4.0, 0.0])
    np.testing.assert_allclose(np.asarray(num)[1], [3.0, 3.0])


def test_make_federated_numden_rejects_bad_fraction():
    with pytest.raises(ValueError):
        make_federated_numden_allreduce(0.0, "clients")
    with pytest.raises(ValueError):
        make_federated_numden_allreduce(1.5, "clients")


# --------------------------------------- multi-device vs the dense oracle

_ORACLE_PRELUDE = """
import numpy as np
import jax, jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.sparse_collective import (
    make_federated_numden_allreduce, sparse_numden_allreduce)

P_DEV = jax.device_count()
mesh = Mesh(np.asarray(jax.devices()), ("clients",))
rng = np.random.default_rng(7)
C, F = 8, 5

def shard_reduce(fn, num, den):
    wrapped = jax.shard_map(fn, mesh=mesh,
                            in_specs=(P("clients"), P("clients")),
                            out_specs=(P(), P(), P()),
                            check_vma=False)
    return wrapped(num, den)

def dense_oracle(num, den):
    return (np.sum(np.asarray(num, np.float64), axis=0).astype(np.float32),
            np.sum(np.asarray(den, np.float64), axis=0).astype(np.float32))
"""


def test_sparse_numden_matches_dense_oracle_when_lossless():
    """Every shard's nonzero channels fit the buffer -> exact mass,
    overflow == 0, for uniform and RAGGED per-shard sparsity."""
    code = _ORACLE_PRELUDE + """
# each shard keeps <= 3 of 8 channels; buffer k=4 -> lossless
num = np.zeros((P_DEV, C, F), np.float32)
den = np.zeros((P_DEV, C), np.float32)
for s in range(P_DEV):
    keep = rng.choice(C, size=rng.integers(1, 4), replace=False)
    den[s, keep] = rng.uniform(0.5, 2.0, keep.size)
    num[s, keep] = rng.normal(size=(keep.size, F)) * den[s, keep][:, None]

def body(n, d):
    return sparse_numden_allreduce(n[0], d[0], 4, "clients")

num_tot, den_tot, overflow = shard_reduce(body, jnp.asarray(num),
                                          jnp.asarray(den))
on, od = dense_oracle(num, den)
assert float(overflow) == 0.0, float(overflow)
np.testing.assert_allclose(np.asarray(num_tot), on, rtol=1e-5, atol=1e-6)
np.testing.assert_allclose(np.asarray(den_tot), od, rtol=1e-5, atol=1e-6)
print("OK")
"""
    assert "OK" in _run_sub(code)


def test_sparse_numden_overflow_certifies_lossy_compaction():
    """More nonzero channels than the buffer: overflow counts exactly the
    channels that did not fit, and the reduced mass really differs."""
    code = _ORACLE_PRELUDE + """
num = np.zeros((P_DEV, C, F), np.float32)
den = np.ones((P_DEV, C), np.float32)          # all C channels nonzero
num[:] = rng.normal(size=num.shape)

def body(n, d):
    return sparse_numden_allreduce(n[0], d[0], 3, "clients")

num_tot, den_tot, overflow = shard_reduce(body, jnp.asarray(num),
                                          jnp.asarray(den))
# every shard overflows by C - k = 5 channels
assert float(overflow) == P_DEV * (C - 3), float(overflow)
on, od = dense_oracle(num, den)
assert not np.allclose(np.asarray(den_tot), od)
print("OK")
"""
    assert "OK" in _run_sub(code)


def test_ragged_k_local_zeroes_rows_beyond_each_shards_allocation():
    """Differential dropout on the static buffer: shard s keeps only its
    own k_local(s) <= k rows; the oracle masks the same rows host-side."""
    code = _ORACLE_PRELUDE + """
K = 4
num = rng.normal(size=(P_DEV, C, F)).astype(np.float32)
den = rng.uniform(0.5, 2.0, size=(P_DEV, C)).astype(np.float32)
k_locals = np.asarray([1 + (s % K) for s in range(P_DEV)], np.int32)

def body(n, d):
    idx = lax.axis_index("clients")
    return sparse_numden_allreduce(n[0], d[0], K, "clients",
                                   k_local=jnp.asarray(k_locals)[idx])

num_tot, den_tot, overflow = shard_reduce(body, jnp.asarray(num),
                                          jnp.asarray(den))

# host oracle: per shard, keep only the top-k_local channels by den
on = np.zeros((C, F), np.float64)
od = np.zeros((C,), np.float64)
for s in range(P_DEV):
    order = np.argsort(-den[s], kind="stable")
    keep = order[: k_locals[s]]
    on[keep] += num[s, keep]
    od[keep] += den[s, keep]
np.testing.assert_allclose(np.asarray(num_tot), on.astype(np.float32),
                           rtol=1e-5, atol=1e-6)
np.testing.assert_allclose(np.asarray(den_tot), od.astype(np.float32),
                           rtol=1e-5, atol=1e-6)
print("OK")
"""
    assert "OK" in _run_sub(code)


def test_keep_fraction_one_routes_to_dense_psum():
    """make_federated_numden_allreduce(1.0) must equal the oracle exactly
    on every channel (dense psum, no compaction, zero overflow)."""
    code = _ORACLE_PRELUDE + """
num = rng.normal(size=(P_DEV, C, F)).astype(np.float32)
den = rng.uniform(0.0, 2.0, size=(P_DEV, C)).astype(np.float32)
f = make_federated_numden_allreduce(1.0, "clients")

def body(n, d):
    return f(n[0], d[0])

num_tot, den_tot, overflow = shard_reduce(body, jnp.asarray(num),
                                          jnp.asarray(den))
on, od = dense_oracle(num, den)
assert float(overflow) == 0.0
np.testing.assert_allclose(np.asarray(num_tot), on, rtol=1e-6, atol=1e-6)
np.testing.assert_allclose(np.asarray(den_tot), od, rtol=1e-6, atol=1e-6)
print("OK")
"""
    assert "OK" in _run_sub(code)


def test_fractional_buffer_sizing_matches_ceil():
    """keep_fraction < 1 sizes the static buffer at ceil(C * fraction),
    floored at one channel."""
    code = _ORACLE_PRELUDE + """
f = make_federated_numden_allreduce(0.5, "clients")
num = np.zeros((P_DEV, C, F), np.float32)
den = np.zeros((P_DEV, C), np.float32)
# exactly ceil(8 * 0.5) = 4 nonzero channels per shard -> lossless
for s in range(P_DEV):
    keep = rng.choice(C, size=4, replace=False)
    den[s, keep] = 1.0
    num[s, keep] = rng.normal(size=(4, F))

def body(n, d):
    return f(n[0], d[0])

num_tot, den_tot, overflow = shard_reduce(body, jnp.asarray(num),
                                          jnp.asarray(den))
assert float(overflow) == 0.0, float(overflow)
on, od = dense_oracle(num, den)
np.testing.assert_allclose(np.asarray(num_tot), on, rtol=1e-5, atol=1e-6)
print("OK")
"""
    assert "OK" in _run_sub(code)
