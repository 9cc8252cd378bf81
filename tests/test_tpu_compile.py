"""The FedDD kernels and round step compile for a TPU v5e chip.

Nothing runs: each program is compiled for one chip, or all four, of a
DESCRIBED v5e:2x2 host (no device attached), at the full VGG widths of
the paper's Table 3 (``HETERO_A_SPECS[0]``) with a fleet of 32
clients.  A compile that Mosaic or XLA:TPU refuses (a block not aligned
to the tiling, too much VMEM, a program past the chip's memory) fails
here instead of on the chip.  Interpret-mode tests
(tests/test_kernels.py) cannot see any of that.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU compiler library, and every
xdist worker imports every test file.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.comm.payload import CommConfig
from repro.core import round_engine
from repro.core.selection import SelectionConfig
from repro.fl import HETERO_A_SPECS
from repro.kernels.importance import ops as imp_ops
from repro.kernels.masked_merge import ops as mm_ops
from repro.kernels.sparse_agg import ops as agg_ops

CLIENTS = 32
V5E_HBM_BYTES = 16 * 2**30       # one v5e chip (Google Cloud, "TPU v5e")


def _leaf_shapes(spec):
    """(name, shape) of every parameter leaf of a ``fl.models`` spec."""
    leaves, li = [], 0
    for layer in spec:
        if layer[0] == "conv":
            _, cin, cout, k = layer
            leaves += [(f"conv{li}.w", (k, k, cin, cout)),
                       (f"conv{li}.b", (cout,))]
        elif layer[0] == "fc":
            _, din, dout = layer
            leaves += [(f"fc{li}.w", (din, dout)), (f"fc{li}.b", (dout,))]
        else:
            continue
        li += 1
    return leaves


VGG_LEAVES = _leaf_shapes(HETERO_A_SPECS[0])


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four chips of a described v5e:2x2 host; the persistent compile
    cache is off meanwhile (entries for a described chip cannot be read
    back without one)."""
    from jax.experimental import topologies
    with contextlib.ExitStack() as stack:
        mp = stack.enter_context(pytest.MonkeyPatch.context())
        mp.setenv("TPU_LOG_DIR", "disabled")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        stack.callback(jax.config.update, "jax_enable_compilation_cache",
                       enabled)
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo.devices


@pytest.fixture(scope="module")
def chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


@pytest.fixture
def mosaic(monkeypatch):
    """The kernel wrappers choose interpret mode from
    ``jax.default_backend()``, which is the CPU in this process; compile
    the Mosaic kernels the chip would run instead."""
    for mod in (imp_ops, agg_ops, mm_ops):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)


def _spec(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    return compiled.as_text(), used


def _kernel_program(kernel, chip, shape, clients=CLIENTS):
    c = shape[-1]
    stacked = _spec(chip, (clients, *shape))
    if kernel == "importance":
        return (lambda wo, wn: imp_ops.channel_importance_batched(
            wo, wn, channel_axis=-1)), (stacked, stacked)
    if kernel == "sparse_agg":
        # the engine broadcasts its per-channel masks to the leaf's shape
        return agg_ops.masked_weighted_sum, (stacked, stacked,
                                             _spec(chip, (clients,)))
    return ((lambda g, l, m: mm_ops.masked_merge(g, l, m, channel_axis=-1)),
            (_spec(chip, shape), _spec(chip, shape), _spec(chip, (c,))))


@pytest.mark.parametrize("leaf", [n for n, _ in VGG_LEAVES])
@pytest.mark.parametrize("kernel",
                         ["importance", "sparse_agg", "masked_merge"])
def test_kernel_compiles_for_v5e(chip, mosaic, kernel, leaf):
    shape = dict(VGG_LEAVES)[leaf]
    fn, args = _kernel_program(kernel, chip, shape)
    text, used = _compile(fn, *args)
    assert "tpu_custom_call" in text
    assert used <= V5E_HBM_BYTES


@pytest.mark.parametrize("leaf", [n for n, _ in VGG_LEAVES])
def test_sparse_agg_partial_slab_compiles_for_v5e(chip, mosaic, leaf):
    """A fleet that is not a multiple of the kernel's 8-client slab: the
    last slab's tail is zeroed in the kernel."""
    fn, args = _kernel_program("sparse_agg", chip, dict(VGG_LEAVES)[leaf],
                               clients=13)
    text, used = _compile(fn, *args)
    assert "tpu_custom_call" in text
    assert used <= V5E_HBM_BYTES


def test_fused_round_step_compiles_for_v5e(chip, mosaic):
    """The batched engine's jitted round step with ``use_kernel=True``:
    importance on every leaf and sparse_agg in Eq. (4) lower to Mosaic
    kernels inside one program that fits one chip."""
    stacked = {n: _spec(chip, (CLIENTS, *s)) for n, s in VGG_LEAVES}
    global_params = {n: _spec(chip, s) for n, s in VGG_LEAVES}
    vec = _spec(chip, (CLIENTS,))
    key = _spec(chip, (2,), jnp.uint32)
    text, used = _compile(
        lambda old, new, g, d, w, k: round_engine._round_step(
            old, new, g, d, w, k, sel_cfg=SelectionConfig(use_kernel=True),
            full_round=False),
        stacked, stacked, global_params, vec, vec, key)
    assert text.count("tpu_custom_call") >= len(VGG_LEAVES)
    assert used <= V5E_HBM_BYTES


@pytest.mark.parametrize("collective", ["dense", "sparse"])
def test_sharded_round_step_compiles_for_v5e_2x2(v5e_2x2, collective):
    """The client-sharded engine's step over a 4-chip ``clients`` mesh:
    the Eq. (4) reduction lowers to cross-chip collectives and each chip's
    share fits its memory."""
    mesh = Mesh(np.asarray(v5e_2x2), ("clients",))
    rows = NamedSharding(mesh, PartitionSpec("clients"))
    rep = NamedSharding(mesh, PartitionSpec())
    stacked = {n: jax.ShapeDtypeStruct((CLIENTS, *s), jnp.float32,
                                       sharding=rows)
               for n, s in VGG_LEAVES}
    global_params = {n: jax.ShapeDtypeStruct(s, jnp.float32, sharding=rep)
                     for n, s in VGG_LEAVES}
    vec = jax.ShapeDtypeStruct((CLIENTS,), jnp.float32, sharding=rows)
    ids = jax.ShapeDtypeStruct((CLIENTS,), jnp.int32, sharding=rows)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    step = round_engine._sharded_step_fn(
        mesh, SelectionConfig(), False, False, CommConfig(), collective,
        1.0)
    compiled = step.lower(stacked, stacked, global_params, vec, vec, ids,
                          key).compile()
    text = compiled.as_text()
    assert "all-reduce" in text
    if collective == "sparse":
        assert "all-gather" in text
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) <= V5E_HBM_BYTES
