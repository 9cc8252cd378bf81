"""A client model family that only the tests define: a two-block MLP whose
parameters nest four levels deep (``body.block0.dense.w``).  It is what a
configuration of a new kind brings as new files: this family module, its
plain reference (``reference_deep_mlp.py``), a configuration, limits.
A family used only with fused traffic needs no per-client trainer and no
eval."""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

from bench import fleet

# ("mlp", input width, hidden widths, classes)


def models(conf):
    spec = ("mlp", conf["input"], tuple(conf["hidden"]), conf["classes"])
    return spec, [spec] * conf["clients"]


def shapes(model):
    _, d, hidden, classes = model
    dims = [d, *hidden]
    body = {f"block{i}": {"dense": {"w": (dims[i], dims[i + 1]),
                                    "b": (dims[i + 1],)}}
            for i in range(len(hidden))}
    return {"body": body,
            "head": {"out": {"w": (dims[-1], classes), "b": (classes,)}}}


def _is_shape(x):
    return isinstance(x, tuple) and all(isinstance(s, int) for s in x)


def param_bytes(model):
    return 4 * sum(math.prod(s) for s in jax.tree_util.tree_leaves(
        shapes(model), is_leaf=_is_shape))


def make_weights(seed, global_model, kinds=()):
    @jax.jit
    def build(key):
        flat, tree = jax.tree_util.tree_flatten(shapes(global_model),
                                                is_leaf=_is_shape)
        keys = jax.random.split(key, len(flat))
        g = jax.tree_util.tree_unflatten(tree, [
            jax.random.normal(k, s, jnp.float32) / math.sqrt(s[0])
            if len(s) == 2 else jnp.zeros(s, jnp.float32)
            for k, s in zip(keys, flat)])
        return g, [jax.tree_util.tree_map(
            lambda a, s: a[tuple(slice(0, n) for n in s)], g, shapes(m),
            is_leaf=_is_shape) for m in kinds]

    return build(jax.random.PRNGKey(fleet.seed32(seed)))


def apply(params, x):
    for name in sorted(params["body"]):
        p = params["body"][name]["dense"]
        x = jax.nn.relu(x @ p["w"] + p["b"])
    return x @ params["head"]["out"]["w"] + params["head"]["out"]["b"]


def data(conf, clients, eval_samples):
    rng = np.random.default_rng(conf["data_seed"])
    s, d, c = conf["samples_per_client"], conf["input"], conf["classes"]
    centers = rng.normal(0, 2.0, (c, d))
    y = rng.integers(0, c, clients * s).astype(np.int32)
    x = (centers[y] + rng.normal(0, 1.0, (clients * s, d))).astype(
        np.float32)
    return (x.reshape(clients, s, d), y.reshape(clients, s),
            x[:eval_samples], y[:eval_samples])


def client_step(setup):
    return (fleet.make_client_step(apply, setup["batch"], setup["lr"],
                                   setup["epochs"]),
            (setup["xs"], setup["ys"]))


def forward_flops(model, samples):
    _, d, hidden, classes = model
    dims = [d, *hidden, classes]
    return samples * sum(2 * a * b for a, b in zip(dims, dims[1:]))


def train_flops(model, samples):
    return 3 * forward_flops(model, samples)


def cpu_cut(conf, clients):
    return dict(conf, clients=clients)
