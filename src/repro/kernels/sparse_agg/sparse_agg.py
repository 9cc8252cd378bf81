"""Pallas TPU kernel: FedDD server aggregation (Eq. (4)) over client-stacked
tensors.

Inputs are stacked (N, R, L) client weights and masks and an (N,) weight
vector; outputs are the fp32 (R, L) numerator and denominator.  Tiling:
grid (R/BR, L/BL, N/BN); the client axis is the minor grid dimension and
the REDUCTION axis — each step loads a (BN, BR, BL) slab of clients and
adds its partial sums into the (BR, BL) output blocks, which stay
VMEM-resident across it (output index_map ignores k).  VMEM use is
therefore independent of the fleet size: with the default (8, 128, 512)
slab, 2 inputs x 2 buffers x 2 MiB = 8 MiB, inside v5e's 16 MiB scoped
default at any N.  (Loading the whole client axis per tile would pass
that budget from about 32 clients.)

This is the fusion the server hot loop wants: one HBM pass over the
stacked tensors produces both Eq. (4) reduction terms (XLA would otherwise
materialise the (N, R, L) masked product).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BN = 8
DEFAULT_BR = 128
DEFAULT_BL = 512


def _agg_kernel(n: int, w_stack_ref, m_stack_ref, wts_ref, num_ref, den_ref):
    k = pl.program_id(2)
    sw = w_stack_ref[...].astype(jnp.float32)     # (BN, BR, BL)
    sm = m_stack_ref[...].astype(jnp.float32)     # (BN, BR, BL)
    wb = wts_ref[...].astype(jnp.float32)         # (BN, 1, 1)
    bn = sw.shape[0]
    if n % bn:
        # the last client slab runs past N: its padding is undefined, so
        # zero it (a 0 weight alone would still let a NaN through)
        sw = jnp.where(k * bn + jax.lax.broadcasted_iota(
            jnp.int32, sw.shape, 0) < n, sw, 0.0)
        sm = jnp.where(k * bn + jax.lax.broadcasted_iota(
            jnp.int32, sm.shape, 0) < n, sm, 0.0)
        wb = jnp.where(k * bn + jax.lax.broadcasted_iota(
            jnp.int32, wb.shape, 0) < n, wb, 0.0)
    num = jnp.sum(sw * sm * wb, axis=0)
    den = jnp.sum(sm * wb, axis=0)

    @pl.when(k == 0)
    def _init():
        num_ref[...] = num
        den_ref[...] = den

    @pl.when(k != 0)
    def _acc():
        num_ref[...] += num
        den_ref[...] += den


@functools.partial(jax.jit, static_argnames=("bn", "br", "bl", "interpret"))
def masked_weighted_sum_2d(stack_w: jax.Array, stack_m: jax.Array,
                           weights: jax.Array, *,
                           bn: int = DEFAULT_BN, br: int = DEFAULT_BR,
                           bl: int = DEFAULT_BL, interpret: bool = False
                           ) -> Tuple[jax.Array, jax.Array]:
    """(N, R, L) weights + (N, R, L) masks + (N,) ->
    ((R, L) num fp32, (R, L) den fp32)."""
    n, r, l = stack_w.shape
    if stack_m.shape != stack_w.shape:
        raise ValueError(f"mask shape {stack_m.shape} != {stack_w.shape}")
    bn = min(bn, n)
    br = min(br, r)
    bl = min(bl, l)
    grid = (pl.cdiv(r, br), pl.cdiv(l, bl), pl.cdiv(n, bn))
    slab = pl.BlockSpec((bn, br, bl), lambda i, j, k: (k, i, j))
    num, den = pl.pallas_call(
        functools.partial(_agg_kernel, n),
        grid=grid,
        in_specs=[
            slab,
            slab,
            pl.BlockSpec((bn, 1, 1), lambda i, j, k: (k, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, bl), lambda i, j, k: (i, j)),
            pl.BlockSpec((br, bl), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, l), jnp.float32),
            jax.ShapeDtypeStruct((r, l), jnp.float32),
        ],
        interpret=interpret,
    )(stack_w, stack_m, weights.reshape(n, 1, 1))
    return num, den
