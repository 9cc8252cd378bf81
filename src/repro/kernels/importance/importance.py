"""Pallas TPU kernel: fused per-channel importance (FedDD Eq. (20)).

Tiling: grid (C/BC, F/BF); W_old/W_new blocks (BC, BF) stream HBM->VMEM; the
(BC, 1) partial sum-of-squares accumulates in the output block, which is
revisited across the fan-in grid axis (output index_map ignores j, so the
block stays VMEM-resident over the minor grid dimension — the standard TPU
reduction pattern).  The output is a (C, 1) column rather than a (C,)
vector: Mosaic tiles a 1-D block differently from XLA's layout of the
same array (T(256) vs T(1024)) and refuses it on TPU.  MXU is not
involved (elementwise + row reduce): the kernel is memory-bound by
design, its value is fusing three elementwise ops + reduction into one
HBM pass over two weight tensors.

Block sizes default to (256, 512): 2 * 256*512*4B = 1 MiB of VMEM for the
inputs — comfortably within the ~16 MiB v5e VMEM budget while keeping the
last dim a multiple of the 128-lane register tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

EPS = 1e-8
DEFAULT_BC = 256
DEFAULT_BF = 512


def _importance_kernel(c: int, f: int, w_old_ref, w_new_ref, out_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)
    wo = w_old_ref[...].astype(jnp.float32)
    wn = w_new_ref[...].astype(jnp.float32)
    bc, bf = wo.shape
    # mask the padded tail of non-divisible shapes (padding is undefined)
    row = i * bc + jax.lax.broadcasted_iota(jnp.int32, (bc, bf), 0)
    col = j * bf + jax.lax.broadcasted_iota(jnp.int32, (bc, bf), 1)
    valid = (row < c) & (col < f)
    wo = jnp.where(valid, wo, 1.0)
    wn = jnp.where(valid, wn, 1.0)
    dw = wn - wo
    denom = jnp.where(jnp.abs(wo) < EPS, jnp.where(wo < 0, -EPS, EPS), wo)
    imp = jnp.abs(dw * wn / denom)
    partial = jnp.sum(imp * imp, axis=1, keepdims=True)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(j != 0)
    def _acc():
        out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("bc", "bf", "interpret"))
def channel_importance_sumsq(w_old: jax.Array, w_new: jax.Array, *,
                             bc: int = DEFAULT_BC, bf: int = DEFAULT_BF,
                             interpret: bool = False) -> jax.Array:
    """(C, F) x2 -> (C,) float32 sum of squared importances (pre-sqrt)."""
    c, f = w_old.shape
    bc = min(bc, c)
    bf = min(bf, f)
    grid = (pl.cdiv(c, bc), pl.cdiv(f, bf))
    return pl.pallas_call(
        functools.partial(_importance_kernel, c, f),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc, bf), lambda i, j: (i, j)),
            pl.BlockSpec((bc, bf), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bc, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((c, 1), jnp.float32),
        interpret=interpret,
    )(w_old, w_new).reshape(c)
