"""jit'd wrapper: reshapes arbitrary-rank stacked client tensors to
(N, R, L) and dispatches to the Pallas kernel (interpret=True off-TPU)."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.sparse_agg.sparse_agg import masked_weighted_sum_2d


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def masked_weighted_sum(stack_w: jax.Array, stack_m: jax.Array,
                        weights: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """stack_w: (N, ...); stack_m broadcastable to it; weights: (N,).

    The last axis stays the lane axis and every other trailing axis folds
    into rows — on TPU a layout-preserving reshape, where folding onto the
    second axis would re-tile a (N, 3, 3, Cin, Cout) conv stack with its
    3-row axis padded to 8.  The mask is broadcast to the weights' shape.

    Returns (num, den) with the original trailing shape, fp32.
    """
    n = stack_w.shape[0]
    orig = stack_w.shape[1:]
    lanes = orig[-1]
    sw = stack_w.reshape(n, -1, lanes)
    sm = jnp.broadcast_to(stack_m, stack_w.shape).reshape(n, -1, lanes)
    num, den = masked_weighted_sum_2d(sw, sm, weights,
                                      interpret=not _on_tpu())
    return num.reshape(orig), den.reshape(orig)
