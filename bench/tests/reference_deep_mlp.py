"""Plain reference of the test-only MLP family: its forward pass, run by
the plain FedDD server of ``bench/references/feddd.py``."""

import jax
import jax.numpy as jnp

from bench.references import feddd


def forward(params, model, x, precision):
    for i in range(len(model[2])):
        p = params["body"][f"block{i}"]["dense"]
        x = jax.nn.relu(jnp.dot(x, p["w"], precision=precision) + p["b"])
    p = params["head"]["out"]
    return jnp.dot(x, p["w"], precision=precision) + p["b"]


def run(setup, calls, **kw):
    return feddd.run(setup, calls, forward, **kw)
