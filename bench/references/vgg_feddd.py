"""Plain FedDD reference for VGG fleets (paper arXiv 2308.16835).

Written from the paper's equations and imports nothing of the program:
the VGG forward pass below ("SAME" 3x3 convolutions + ReLU, 2x2 max
pools, dense layers with ReLU except the last, each convolution and
matmul at the given precision), run by the plain FedDD server and local
training of ``bench/references/feddd.py``, which says what the reference
computes, at which precision, and where it places the clients.
"""

from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from bench.references import feddd


def forward(params, layers, x, precision):
    li, n_fc, seen = 0, sum(l[0] == "fc" for l in layers), 0
    for layer in layers:
        if layer[0] == "conv":
            p = params[f"conv{li}"]
            x = jax.lax.conv_general_dilated(
                x, p["w"], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=precision)
            x = jax.nn.relu(x + p["b"])
            li += 1
        elif layer[0] == "pool":
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        else:
            x = x.reshape(x.shape[0], -1)
            p = params[f"fc{li}"]
            x = jnp.dot(x, p["w"], precision=precision) + p["b"]
            seen += 1
            if seen < n_fc:
                x = jax.nn.relu(x)
            li += 1
    return x


def run(setup: Dict, calls: Sequence[int], **kw) -> Dict:
    """The readings of the first rounds of ``calls`` (``feddd.run``)."""
    return feddd.run(setup, calls, forward, **kw)
