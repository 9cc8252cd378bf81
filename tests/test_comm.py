"""Wire-format subsystem contracts (repro.comm).

Pins:
* codec roundtrip exactness: decode(encode(mask)) is exact for every
  codec, serialized length equals the measured byte formula, and the
  traced (jnp) formulas equal the numpy ones bit for bit;
* value-codec contracts: fp32 lossless, fp16 cast-exact, int8 stochastic
  rounding within one scale step and deterministic (keyed);
* accounting identity: with the default CommConfig (dense codec, 32-bit
  values) ``wire_bytes == uploaded_bytes`` EXACTLY on all four execution
  paths (reference loop, batched engine, grouped engine, multi-round
  scan) and the learning state matches a comm-less run bit for bit;
* sparse-codec parity: loop vs engine vs scanned agree on wire bytes
  (integer overheads — exact across XLA programs) and learning state;
* degenerate settings: zero-density uploads cost header-only bytes,
  full-density uploads make the dense fallback beat index coding, and a
  dead-uplink client under codec-measured bytes is cut by the deadline
  policy;
* the bitmask/index crossover sits where the byte formulas say (~1/8).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import codecs, payload, quantize
from repro.comm.payload import CommConfig, WireSpec, account_uplink
from repro.core import FedDDServer, ProtocolConfig, run_scheme
from repro.core.allocation import (ClientTelemetry,
                                   solve_dropout_rates,
                                   solve_dropout_rates_overhead_aware)

pytestmark = pytest.mark.flcore

SPARSE_CODECS = ("bitmask", "index", "auto")


def _rand_mask(rng, c, density):
    m = (rng.random(c) < density).astype(np.float32)
    return m


# --------------------------------------------------------------- codecs

def test_mask_roundtrip_exact_and_length_matches_formula():
    rng = np.random.default_rng(0)
    for _ in range(25):
        c = int(rng.integers(1, 80))
        m = _rand_mask(rng, c, rng.random())
        for codec in SPARSE_CODECS:
            buf = codecs.encode_mask(m, codec)
            assert np.array_equal(codecs.decode_mask(buf, c, codec), m)
            formula = int(codecs._leaf_overhead(m[None], c, codec, np)[0])
            assert len(buf) == formula, (codec, c)


def test_mask_roundtrip_empty_and_full():
    for c in (1, 8, 9, 64, 65):
        for m in (np.zeros(c, np.float32), np.ones(c, np.float32)):
            for codec in SPARSE_CODECS:
                buf = codecs.encode_mask(m, codec)
                assert np.array_equal(codecs.decode_mask(buf, c, codec), m)


def test_traced_overhead_equals_numpy_overhead():
    rng = np.random.default_rng(1)
    m = (rng.random((6, 47)) < 0.3).astype(np.float32)
    for codec in SPARSE_CODECS:
        a = np.asarray(codecs._leaf_overhead(jnp.asarray(m), 47, codec, jnp))
        b = codecs._leaf_overhead(m, 47, codec, np)
        assert np.array_equal(a, b), codec


def test_varint_bytes_boundaries():
    vals = [0, 1, 127, 128, 16383, 16384, (1 << 21) - 1, 1 << 21]
    want = [1, 1, 1, 2, 2, 3, 3, 4]
    got_np = codecs.varint_bytes(np.asarray(vals), np)
    got_j = np.asarray(codecs.varint_bytes(jnp.asarray(vals), jnp))
    assert list(got_np) == want
    assert list(got_j) == want


def test_stacked_overhead_matches_per_client():
    rng = np.random.default_rng(2)
    masks = {"w": jnp.asarray(rng.random((5, 1, 20)) < 0.4, jnp.float32),
             "b": jnp.asarray(rng.random((5, 20)) < 0.4, jnp.float32)}
    params = {"w": jnp.zeros((5, 7, 20)), "b": jnp.zeros((5, 20))}
    for codec in SPARSE_CODECS:
        for qbits in (32, 8):
            comm = CommConfig(codec=codec, qbits=qbits)
            got = np.asarray(codecs.mask_overhead_bytes_stacked(
                masks, params, comm))
            for i in range(5):
                mi = jax.tree_util.tree_map(lambda l: l[i], masks)
                pi = jax.tree_util.tree_map(lambda l: l[i], params)
                assert got[i] == codecs.mask_overhead_bytes(mi, pi, comm)


# ------------------------------------------------------------- quantize

def test_payload_roundtrip_values():
    rng = np.random.default_rng(3)
    params = {"w": jnp.asarray(rng.normal(size=(6, 12)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(12,)), jnp.float32)}
    masks = {"w": jnp.asarray(rng.random(12) < 0.5,
                              jnp.float32).reshape(1, 12),
             "b": jnp.asarray(rng.random(12) < 0.5, jnp.float32)}
    key = quantize.client_quant_key(jax.random.PRNGKey(0), 7)
    for codec in ("dense",) + SPARSE_CODECS:
        for qbits in (32, 16, 8):
            comm = CommConfig(codec=codec, qbits=qbits)
            pl = payload.encode_upload(params, masks, comm, key)
            vals, mk = payload.decode_upload(pl)
            for v, m, p in zip(jax.tree_util.tree_leaves(vals),
                               jax.tree_util.tree_leaves(mk),
                               jax.tree_util.tree_leaves(params)):
                sel = np.broadcast_to(np.asarray(m), p.shape) > 0
                if qbits == 32:      # lossless: bit-identical
                    assert np.array_equal(v[sel], np.asarray(p)[sel])
                elif qbits == 16:    # deterministic cast roundtrip
                    ref = np.asarray(p, np.float16).astype(np.float32)
                    assert np.array_equal(v[sel], ref[sel])
                else:                # bounded, keyed-deterministic
                    scale = np.max(np.abs(np.asarray(p))) / 127.0
                    err = np.max(np.abs(v[sel] - np.asarray(p)[sel]))
                    assert err <= scale + 1e-7
            # nbytes equals the measured accounting
            oh = codecs.mask_overhead_bytes(masks, params, comm)
            kept = sum(int(np.sum(np.broadcast_to(np.asarray(m), p.shape)
                                  > 0))
                       for p, m in zip(jax.tree_util.tree_leaves(params),
                                       jax.tree_util.tree_leaves(masks)))
            assert pl.nbytes == oh + kept * quantize.value_bytes(qbits)


def test_int8_decode_matches_engine_qdq_and_is_deterministic():
    """The serialized int8 payload decodes to EXACTLY the values the
    in-engine quantize->dequantize feeds the aggregation, and re-encoding
    with the same key reproduces the same bytes (different key: not)."""
    rng = np.random.default_rng(4)
    params = {"w": jnp.asarray(rng.normal(size=(5, 9)), jnp.float32)}
    masks = {"w": jnp.asarray(rng.random(9) < 0.6,
                              jnp.float32).reshape(1, 9)}
    comm = CommConfig(codec="index", qbits=8)
    key = quantize.client_quant_key(jax.random.PRNGKey(3), 2)
    pl = payload.encode_upload(params, masks, comm, key)
    vals, mk = payload.decode_upload(pl)
    ref = quantize.quantize_dequantize(params, key, 8)
    sel = np.broadcast_to(np.asarray(masks["w"]), (5, 9)) > 0
    assert np.array_equal(vals["w"][sel], np.asarray(ref["w"])[sel])
    pl2 = payload.encode_upload(params, masks, comm, key)
    assert pl.leaves[0].value_bytes == pl2.leaves[0].value_bytes
    other = payload.encode_upload(
        params, masks, comm, quantize.client_quant_key(
            jax.random.PRNGKey(99), 2))
    assert pl.leaves[0].value_bytes != other.leaves[0].value_bytes


def test_stacked_qdq_matches_per_client_loop():
    rng = np.random.default_rng(5)
    x = {"w": jnp.asarray(rng.normal(size=(4, 6, 10)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(4, 10)), jnp.float32)}
    rk = jax.random.PRNGKey(11)
    for qbits in (16, 8):
        got = quantize.quantize_dequantize_stacked(x, rk, qbits)
        for i in range(4):
            xi = jax.tree_util.tree_map(lambda l: l[i], x)
            ref = quantize.quantize_dequantize(
                xi, quantize.client_quant_key(rk, i), qbits)
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(ref)):
                assert np.array_equal(np.asarray(a[i]), np.asarray(b))


# ----------------------------------------------- protocol: 4-path parity

def _client_params(key, n, scale=1.0):
    def one(k):
        k1, k2 = jax.random.split(k)
        return {
            "fc0": {"w": scale * jax.random.normal(k1, (20, 12)),
                    "b": jnp.zeros(12)},
            "fc1": {"w": scale * jax.random.normal(k2, (12, 5)),
                    "b": jnp.zeros(5)},
        }
    return [one(jax.random.fold_in(key, i)) for i in range(n)]


def _telemetry(n, nbytes, seed=0):
    rng = np.random.default_rng(seed)
    return ClientTelemetry(
        model_bytes=np.full(n, nbytes),
        uplink_rate=rng.uniform(1e3, 5e3, n),
        downlink_rate=rng.uniform(5e3, 2e4, n),
        compute_latency=rng.uniform(1.0, 5.0, n),
        num_samples=rng.integers(10, 50, n).astype(float),
        label_coverage=rng.uniform(0.5, 1.0, n),
        train_loss=np.ones(n))


def _fixture(n=6, seed=0):
    params = _client_params(jax.random.PRNGKey(seed), 1)[0]
    nbytes = float(sum(l.size * l.dtype.itemsize
                       for l in jax.tree_util.tree_leaves(params)))
    return params, _telemetry(n, nbytes, seed)


def _ltf(p, idx, key):
    return (jax.tree_util.tree_map(
        lambda x: x * 0.99 + 0.01 * jax.random.normal(key, x.shape), p),
        1.0 / (idx + 1.0))


def _trees_equal(a, b):
    return all(bool(jnp.all(x == y)) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


def _assert_trees_close(a, b, rtol=1e-5, atol=1e-7):
    """Engine vs reference loop: one fused jitted step against per-client
    eager ops cannot be one compiled program, and under jax 0.9 XLA:CPU
    sums the float32 Eq. (4) terms in a different order in the two (the
    order also varies with the process's thread layout), so the learning
    state agrees to a few float32 ulps at O(1) scale, not bit for bit.
    An accounting or mask fault moves values by 1e-2 and more."""
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


@pytest.mark.parametrize("scheme", ["feddd", "fedavg", "fedcs", "oort"])
def test_default_comm_wire_equals_uploaded_all_paths(scheme):
    """dense codec + qbits=32: wire_bytes == uploaded_bytes bitwise and
    the learning state is bit-identical to a run with no comm config, on
    the loop, engine, and (via the comm-default ProtocolConfig) every
    routed path."""
    params, tel = _fixture()
    kw = dict(rounds=4, a_server=0.6, h=3, seed=0)
    for batched in (False, True):
        base = run_scheme(scheme, params, tel, _ltf, None,
                          batched=batched, **kw)
        comm = run_scheme(scheme, params, tel, _ltf, None, batched=batched,
                          comm=CommConfig(codec="dense", qbits=32), **kw)
        assert _trees_equal(base.global_params, comm.global_params)
        for rb, rc in zip(base.history, comm.history):
            assert rb.uploaded_fraction == rc.uploaded_fraction
            assert rc.wire_bytes == rc.uploaded_bytes     # the identity
            assert rb.uploaded_bytes == rc.uploaded_bytes
            assert rb.sim_time == rc.sim_time
            assert rb.mean_loss == rc.mean_loss


def test_default_comm_scanned_path_identity():
    """dense/32 on the multi-round scan: wire == uploaded bitwise and the
    stream matches the comm-less scanned stream."""
    params, tel = _fixture(n=8)

    @jax.jit
    def batched(stacked, key):
        new = jax.tree_util.tree_map(
            lambda x: x * 0.99 + 0.01 * jax.random.normal(
                jax.random.fold_in(key, 1), x.shape), stacked)
        l0 = jax.tree_util.tree_leaves(new)[0]
        return new, jnp.mean(jnp.abs(l0.reshape(l0.shape[0], -1)), axis=1)

    kw = dict(scheme="feddd", rounds=6, a_server=0.6, h=3, seed=0,
              allocator="jax", rounds_per_dispatch=3)
    r1 = FedDDServer(params, ProtocolConfig(**kw), tel).run(
        batched_train_fn=batched)
    r2 = FedDDServer(params, ProtocolConfig(comm=CommConfig(), **kw),
                     tel).run(batched_train_fn=batched)
    assert _trees_equal(r1.global_params, r2.global_params)
    for a, b in zip(r1.history, r2.history):
        assert a.uploaded_bytes == b.uploaded_bytes
        assert b.wire_bytes == b.uploaded_bytes
        assert a.sim_time == b.sim_time


def _overhead_of(rec, qbits):
    """The measured mask/scale overhead a record carries — an INTEGER
    byte count by construction, recovered exactly from the float fields
    (the value term inherits the loop-vs-engine density ulp, so totals
    are compared approx and overheads exactly)."""
    return round(rec.wire_bytes - rec.uploaded_bytes * (qbits / 32.0))


@pytest.mark.parametrize("codec", SPARSE_CODECS)
@pytest.mark.parametrize("qbits", [32, 16])
def test_sparse_codec_engine_matches_loop(codec, qbits):
    """Sparse codecs + lossless/cast values: the engine run reproduces
    the reference loop's measured overhead exactly (integer bytes) and
    the learning state to float32 ulps (:func:`_assert_trees_close`);
    byte totals agree to the pre-existing density-ulp tolerance."""
    params, tel = _fixture()
    kw = dict(rounds=4, a_server=0.6, h=3, seed=0,
              comm=CommConfig(codec=codec, qbits=qbits))
    loop = run_scheme("feddd", params, tel, _ltf, None, batched=False, **kw)
    eng = run_scheme("feddd", params, tel, _ltf, None, batched=True, **kw)
    _assert_trees_close(loop.global_params, eng.global_params)
    for rl, re_ in zip(loop.history, eng.history):
        assert _overhead_of(rl, qbits) == _overhead_of(re_, qbits) > 0
        assert rl.wire_bytes == pytest.approx(re_.wire_bytes, rel=1e-6)
        assert rl.uploaded_bytes == pytest.approx(re_.uploaded_bytes,
                                                  rel=1e-6)
        assert rl.sim_time == pytest.approx(re_.sim_time, rel=1e-9)
        assert rl.wire_bytes > rl.uploaded_bytes * (qbits / 32.0)


def test_int8_engine_matches_loop():
    """int8 stochastic rounding draws the same keyed noise on both paths
    (same fold discipline as masks): identical quantization decisions and
    wire overheads.  The QDQ barriers pin every JITTED rendering to the
    same bits (per-round engine == grouped == scanned — the other tests);
    the EAGER reference loop's per-op dispatch may legally round the
    division chain an ulp apart (XLA compiles per program), so params are
    held to ulp scale here, not bitwise."""
    params, tel = _fixture()
    kw = dict(rounds=3, a_server=0.6, h=2, seed=0,
              comm=CommConfig(codec="bitmask", qbits=8))
    loop = run_scheme("feddd", params, tel, _ltf, None, batched=False, **kw)
    eng = run_scheme("feddd", params, tel, _ltf, None, batched=True, **kw)
    for a, b in zip(jax.tree_util.tree_leaves(loop.global_params),
                    jax.tree_util.tree_leaves(eng.global_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-6)
    for rl, re_ in zip(loop.history, eng.history):
        assert _overhead_of(rl, 8) == _overhead_of(re_, 8) > 0
        assert rl.wire_bytes == pytest.approx(re_.wire_bytes, rel=1e-6)
        assert rl.mean_loss == re_.mean_loss


def test_sparse_codec_scanned_wire_matches_per_round():
    """The scanned path's wire-byte telemetry equals per-round engine
    dispatch exactly (int32 overheads in the trace), and the learning
    state matches bit for bit."""
    params, tel = _fixture(n=8)

    @jax.jit
    def batched(stacked, key):
        new = jax.tree_util.tree_map(
            lambda x: x * 0.99 + 0.01 * jax.random.normal(
                jax.random.fold_in(key, 1), x.shape), stacked)
        l0 = jax.tree_util.tree_leaves(new)[0]
        return new, jnp.mean(jnp.abs(l0.reshape(l0.shape[0], -1)), axis=1)

    kw = dict(scheme="feddd", rounds=6, a_server=0.6, h=3, seed=0,
              allocator="jax", comm=CommConfig(codec="index", qbits=16))
    seq = FedDDServer(params, ProtocolConfig(**kw), tel).run(
        batched_train_fn=batched)
    scan = FedDDServer(params, ProtocolConfig(rounds_per_dispatch=3, **kw),
                       tel).run(batched_train_fn=batched)
    assert _trees_equal(seq.global_params, scan.global_params)
    for a, b in zip(seq.history, scan.history):
        assert a.wire_bytes == b.wire_bytes
        assert a.uploaded_bytes == b.uploaded_bytes
        assert b.sim_time == pytest.approx(a.sim_time, rel=1e-9)


def test_sparse_codec_grouped_matches_loop():
    """Ragged fleet: grouped engine wire accounting equals the reference
    loop (per-leaf overheads computed at native widths)."""
    n = 5
    full = _client_params(jax.random.PRNGKey(0), 1)[0]

    def slice_w(p, frac):
        def s(l):
            if l.ndim == 0:
                return l
            w = max(1, int(l.shape[-1] * frac))
            return l[..., :w]
        return jax.tree_util.tree_map(s, p)

    clients = [full, slice_w(full, 0.6), full, slice_w(full, 0.6),
               slice_w(full, 0.8)]
    nbytes = [float(sum(l.size * l.dtype.itemsize
                        for l in jax.tree_util.tree_leaves(p)))
              for p in clients]
    tel = dataclasses.replace(_telemetry(n, 1.0),
                              model_bytes=np.asarray(nbytes))
    kw = dict(rounds=3, a_server=0.6, h=2, seed=0,
              comm=CommConfig(codec="index", qbits=32))
    loop = run_scheme("feddd", full, tel, _ltf, None, batched=False,
                      client_params=clients, **kw)
    grp = run_scheme("feddd", full, tel, _ltf, None, batched=True,
                     client_params=clients, **kw)
    _assert_trees_close(loop.global_params, grp.global_params)
    for rl, rg in zip(loop.history, grp.history):
        assert _overhead_of(rl, 32) == _overhead_of(rg, 32) > 0
        assert rl.wire_bytes == pytest.approx(rg.wire_bytes, rel=1e-6)
        assert rl.uploaded_bytes == pytest.approx(rg.uploaded_bytes,
                                                  rel=1e-6)


def test_dense_mask_uploads_charge_true_width_overhead():
    """Baseline (all-ones-mask) uploads carry a collapsed channel dim in
    the engines; their recorded overhead must be the closed-form
    full-upload constant at TRUE widths — identical to encoding a
    materialized all-ones mask AND to the analytic model the clock
    charges at dropout 0, on the loop and the engine alike."""
    params, tel = _fixture()
    comm = CommConfig(codec="bitmask", qbits=16)
    spec = WireSpec.from_params(params)
    const = codecs.full_upload_overhead_bytes(spec, comm)
    # equals the measured overhead of real all-ones masks...
    ones = jax.tree_util.tree_map(
        lambda l: jnp.ones((1,) * (l.ndim - 1) + (l.shape[-1],)), params)
    assert const == codecs.mask_overhead_bytes(ones, params, comm)
    # ...and the analytic model's overhead at dropout 0
    analytic = float(payload.analytic_wire_bytes(spec, 0.0, comm))
    values = spec.total_elements * quantize.value_bytes(16)
    assert const == round(analytic - values)
    n = tel.num_clients
    kw = dict(rounds=2, a_server=0.6, h=2, seed=0, comm=comm)
    for batched in (False, True):
        res = run_scheme("fedavg", params, tel, _ltf, None,
                         batched=batched, **kw)
        for r in res.history:
            assert _overhead_of(r, 16) == const * n, batched


def test_payload_roundtrip_square_leaf_channel_axis_0():
    """Square leaves are shape-ambiguous: the payload must carry the
    channel axis so a channel_axis=0 mask decodes onto axis 0."""
    rng = np.random.default_rng(9)
    c = 7
    params = {"w": jnp.asarray(rng.normal(size=(c, c)), jnp.float32)}
    m1d = (rng.random(c) < 0.5).astype(np.float32)
    masks = {"w": jnp.asarray(m1d).reshape(c, 1)}     # channel axis 0
    comm = CommConfig(codec="index", qbits=32)
    vals, mk = payload.decode_upload(
        payload.encode_upload(params, masks, comm, None))
    ref = np.broadcast_to(m1d.reshape(c, 1), (c, c)) > 0
    assert np.array_equal(mk["w"] > 0, ref)
    assert np.array_equal(vals["w"][ref], np.asarray(params["w"])[ref])


# ------------------------------------------------- degenerate settings

def test_zero_density_upload_charges_header_only_bytes():
    """A mask that keeps nothing ships no values — only the per-leaf
    framing (header + bitmask bits for 'bitmask'; header alone for
    'index'), and no int8 scale."""
    masks = {"w": jnp.zeros((3, 1, 16)), "b": jnp.zeros((3, 16))}
    params = {"w": jnp.zeros((3, 4, 16)), "b": jnp.zeros((3, 16))}
    bm = np.asarray(codecs.mask_overhead_bytes_stacked(
        masks, params, CommConfig(codec="bitmask", qbits=8)))
    ix = np.asarray(codecs.mask_overhead_bytes_stacked(
        masks, params, CommConfig(codec="index", qbits=8)))
    per_leaf_bm = codecs.HEADER_BYTES + codecs.bitmask_bytes(16)
    assert np.all(bm == 2 * per_leaf_bm)          # no scale bytes: kept==0
    assert np.all(ix == 2 * codecs.HEADER_BYTES)  # header-only
    # and the wire accounting is exactly that overhead (zero value bytes)
    up, wire = account_uplink(np.zeros(3), np.ones(3, bool),
                              np.full(3, 4096.0), ix,
                              CommConfig(codec="index", qbits=8))
    assert up == 0.0
    assert wire == float(2 * codecs.HEADER_BYTES * 3)


def test_full_density_dense_fallback_beats_index():
    """At density 1 the dense (values-only) codec is strictly cheaper
    than index coding — the crossover's upper end."""
    spec = WireSpec(((64, 64 * 32), (64, 64)))
    dense = float(payload.analytic_wire_bytes(spec, 0.0, CommConfig()))
    index = float(payload.analytic_wire_bytes(
        spec, 0.0, CommConfig(codec="index")))
    bitmask = float(payload.analytic_wire_bytes(
        spec, 0.0, CommConfig(codec="bitmask")))
    assert dense < bitmask < index


def test_bitmask_index_crossover_density():
    """Index coding wins at low density, bitmask at high density, with
    the crossover near density 1/8 (1 varint byte per kept channel vs
    C/8 bitmask bytes)."""
    c = 512
    m_low = np.zeros(c, np.float32)
    m_low[:: c // 16] = 1.0          # density 1/32
    m_high = np.ones(c, np.float32)
    m_high[:: c // 16] = 0.0         # density 31/32
    ix_low = int(codecs._leaf_overhead(m_low[None], c, "index", np)[0])
    ix_high = int(codecs._leaf_overhead(m_high[None], c, "index", np)[0])
    bm = int(codecs._leaf_overhead(m_low[None], c, "bitmask", np)[0])
    assert ix_low < bm < ix_high
    # analytic model places the crossover in (1/16, 1/4) around ~1/8
    spec = WireSpec(((c, c),))
    dens_grid = np.linspace(0.01, 0.99, 197)
    ix = np.asarray([float(payload.analytic_wire_bytes(
        spec, 1.0 - d, CommConfig(codec="index"))) for d in dens_grid])
    bmv = np.asarray([float(payload.analytic_wire_bytes(
        spec, 1.0 - d, CommConfig(codec="bitmask"))) for d in dens_grid])
    cross = dens_grid[np.argmax(ix > bmv)]
    assert 1 / 16 < cross < 1 / 4


def test_dead_uplink_client_cut_by_deadline_under_codec_bytes():
    """Deadline policy + codec-measured bytes: a client whose uplink is
    effectively dead never lands its (sparse-encoded) upload; the round
    aggregates without it and the wire accounting reflects the arrivals
    only."""
    from repro.sim import SimConfig

    params, tel = _fixture(n=6, seed=1)
    dead = dataclasses.replace(
        tel, uplink_rate=np.concatenate([[1e-6], tel.uplink_rate[1:]]))
    res = run_scheme("feddd", params, dead, _ltf, None,
                     sim=SimConfig(policy="deadline"), rounds=3,
                     a_server=0.6, h=2, seed=0,
                     comm=CommConfig(codec="index", qbits=16))
    n = dead.num_clients
    assert all(r.participants < n for r in res.history)
    for r in res.history:
        assert 0.0 < r.wire_bytes
        # fp16 values: the wire carries about half the raw bytes plus
        # positive mask overhead — never the full-fleet dense mass
        assert r.wire_bytes < float(np.sum(dead.model_bytes))
        assert r.wire_bytes > r.uploaded_bytes * 0.5


def test_sim_sync_static_matches_protocol_with_codec():
    """The sim's sync+static fidelity contract extends to non-default
    wire formats: identical wire_bytes and Eq. (12) times."""
    params, tel = _fixture(n=5, seed=2)
    kw = dict(rounds=3, a_server=0.6, h=2, seed=0,
              comm=CommConfig(codec="bitmask", qbits=16))
    proto = run_scheme("feddd", params, tel, _ltf, None, **kw)
    sim = run_scheme("feddd", params, tel, _ltf, None, sim=True, **kw)
    assert _trees_equal(proto.global_params, sim.global_params)
    for rp, rs in zip(proto.history, sim.history):
        assert rp.wire_bytes == rs.wire_bytes
        assert rp.sim_time == pytest.approx(rs.sim_time, rel=1e-12)


# --------------------------------------------- overhead-aware allocation

def test_overhead_aware_allocation_binds_on_wire_bytes():
    """The overhead-aware LP meets the A_server budget measured in
    ON-WIRE bytes; the linear proxy overshoots it when the codec has a
    density-independent floor."""
    n = 8
    rng = np.random.default_rng(7)
    nbytes = 4096.0
    tel = ClientTelemetry(
        model_bytes=np.full(n, nbytes),
        uplink_rate=rng.uniform(1e3, 5e3, n),
        downlink_rate=rng.uniform(5e3, 2e4, n),
        compute_latency=rng.uniform(1.0, 5.0, n),
        num_samples=rng.integers(10, 50, n).astype(float),
        label_coverage=rng.uniform(0.5, 1.0, n),
        train_loss=rng.uniform(0.5, 2.0, n))
    spec = WireSpec(((32, 512), (32, 512)))
    specs = [spec] * n
    comm = CommConfig(codec="bitmask", qbits=8,
                      overhead_aware_allocation=True)
    kw = dict(a_server=0.6, d_max=0.8, delta=1.0, global_model_bytes=nbytes)
    aware = solve_dropout_rates_overhead_aware(tel, specs, comm=comm, **kw)
    assert aware.feasible
    wire = payload.analytic_uplink_vector(specs, aware.dropout_rates, comm)
    full = payload.analytic_uplink_vector(specs, np.zeros(n), comm)
    target = 0.6 * float(np.sum(full))
    assert float(np.sum(wire)) == pytest.approx(target, rel=5e-2)
    # the linear proxy, charged on the same wire model, spends MORE bytes
    linear = solve_dropout_rates(tel, **kw)
    wire_lin = payload.analytic_uplink_vector(specs, linear.dropout_rates,
                                              comm)
    assert float(np.sum(wire_lin)) > float(np.sum(wire))


def test_overhead_aware_requires_numpy_allocator():
    with pytest.raises(ValueError, match="overhead_aware"):
        ProtocolConfig(
            allocator="jax",
            comm=CommConfig(codec="index", overhead_aware_allocation=True))


def test_comm_config_validation():
    with pytest.raises(ValueError, match="codec"):
        CommConfig(codec="huffman")
    with pytest.raises(ValueError, match="qbits"):
        CommConfig(qbits=4)


def test_overhead_aware_end_to_end_run():
    """A protocol run with overhead-aware allocation completes and keeps
    its measured wire bytes near the budget once rates adapt."""
    params, tel = _fixture(n=6, seed=3)
    res = run_scheme(
        "feddd", params, tel, _ltf, None, rounds=4, a_server=0.6, h=10,
        seed=0, comm=CommConfig(codec="bitmask", qbits=8,
                                overhead_aware_allocation=True))
    full_wire = float(np.sum(payload.analytic_uplink_vector(
        [WireSpec.from_params(params)] * tel.num_clients,
        np.zeros(tel.num_clients),
        CommConfig(codec="bitmask", qbits=8))))
    # rounds after the first allocation should track the wire budget
    for r in res.history[2:]:
        assert r.wire_bytes == pytest.approx(0.6 * full_wire, rel=0.15)
