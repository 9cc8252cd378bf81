"""What a cell is made of, from its configuration file and the seed.

Everything here is the benchmark's own: the layer list of a VGG client,
its initial weights (one jitted call on the device), the CIFAR-10-shaped
stand-in data, the Table 4 client telemetry, and the fused per-client SGD
step a user hands to ``make_batched_train_fn``.  The data generator and
the telemetry sampler are copies of the program's (``repro.data.synthetic
.make_dataset("cifar10")`` and ``repro.fl.heterogeneity
.sample_system_telemetry``), so the yardstick does not move when the
program changes them.  The plain reference (``bench/references``) builds
the same weights, data and telemetry from the same seed through this
module, never from anything the program made.
"""

from __future__ import annotations

import math
import zlib
from typing import Dict, List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

Layer = Tuple  # ("conv", cin, cout, k) | ("pool",) | ("fc", din, dout)


def seed32(seed: int) -> int:
    """The run's seed folded into the 31 bits a PRNG key and the
    protocol's ``seed`` take."""
    return int(seed) % (2 ** 31 - 1)


# ------------------------------------------------------------------ model

def vgg_layers(conv: Sequence[int], fc: Sequence[int], in_ch: int = 3,
               classes: int = 10, kernel: int = 3) -> Tuple[Layer, ...]:
    """A VGG client as a layer list: 3x3 conv + ReLU + 2x2 max pool per
    conv width, then dense layers (ReLU except the last).  32x32 inputs
    through five pools leave 1x1, so the first dense layer's fan-in is the
    last conv width."""
    layers: List[Layer] = []
    cin = in_ch
    for w in conv:
        layers += [("conv", cin, int(w), kernel), ("pool",)]
        cin = int(w)
    dims = [cin] + [int(d) for d in fc] + [classes]
    layers += [("fc", dims[i], dims[i + 1]) for i in range(len(dims) - 1)]
    return tuple(layers)


def leaf_shapes(layers: Sequence[Layer]) -> Dict[str, Dict[str, tuple]]:
    """``{"conv0": {"w": (k, k, cin, cout), "b": (cout,)}, "fc5": ...}``:
    layers are numbered in order over conv and dense layers alike."""
    out, li = {}, 0
    for layer in layers:
        if layer[0] == "conv":
            _, cin, cout, k = layer
            out[f"conv{li}"] = {"w": (k, k, cin, cout), "b": (cout,)}
            li += 1
        elif layer[0] == "fc":
            _, din, dout = layer
            out[f"fc{li}"] = {"w": (din, dout), "b": (dout,)}
            li += 1
    return out


def init_params(key, layers: Sequence[Layer]) -> Dict:
    """He-style normal weights (fan-in scaled), zero biases, float32."""
    params = {}
    for name, shapes in leaf_shapes(layers).items():
        key, sub = jax.random.split(key)
        w = shapes["w"]
        fan_in = math.prod(w[:-1])
        params[name] = {
            "w": jax.random.normal(sub, w, jnp.float32) / math.sqrt(fan_in),
            "b": jnp.zeros(shapes["b"], jnp.float32)}
    return params


def slice_to(params: Dict, shapes: Dict) -> Dict:
    """The leading corner of every leaf (HeteroFL width slicing)."""
    return {name: {k: params[name][k][tuple(slice(0, s) for s in shp)]
                   for k, shp in leaves.items()}
            for name, leaves in shapes.items()}


def make_weights(seed: int, global_layers, client_layers=()):
    """Global weights and, for a ragged fleet, each width's sub-model
    sliced from them, in one jitted call on the default device."""
    shapes = [leaf_shapes(c) for c in client_layers]

    @jax.jit
    def build(key):
        g = init_params(key, global_layers)
        return g, [slice_to(g, s) for s in shapes]

    return build(jax.random.PRNGKey(seed32(seed)))


def param_bytes(layers: Sequence[Layer]) -> int:
    return 4 * sum(math.prod(s) for leaves in leaf_shapes(layers).values()
                   for s in leaves.values())


# ------------------------------------------------------------------- data

def make_data(seed: int, num_train: int, num_test: int,
              shape=(32, 32, 3), classes: int = 10, latent_dim: int = 32,
              modes_per_class: int = 3, class_sep: float = 3.2,
              noise: float = 0.9):
    """The CIFAR-10-shaped Gaussian-mixture stand-in: ``(xtr, ytr, xte,
    yte)`` as float32 images in [-1, 1] and int32 labels.  Copy of the
    program's ``make_dataset("cifar10")``."""
    h, w, c = shape
    base = int(seed) + zlib.crc32(b"cifar10") % (2 ** 16)
    rng = np.random.default_rng(base)
    proj = rng.normal(0, 1.0 / np.sqrt(latent_dim), (latent_dim, h * w * c))
    centers = rng.normal(0, class_sep, (classes, modes_per_class,
                                        latent_dim))

    def sample(n: int, off: int):
        r = np.random.default_rng(int(seed) + off)
        y = r.integers(0, classes, n).astype(np.int32)
        mode = r.integers(0, modes_per_class, n)
        z = centers[y, mode] + r.normal(0, noise, (n, latent_dim))
        return np.tanh(z @ proj).astype(np.float32).reshape(n, h, w, c), y

    xtr, ytr = sample(num_train, 1)
    xte, yte = sample(max(num_test, 1), 2)
    return xtr, ytr, xte[:num_test], yte[:num_test]


def client_shards(seed: int, clients: int, shard: int, num_test: int):
    """Each client's ``shard`` samples (a seeded shuffle of the training
    set cut into equal blocks) as ``(clients, shard, ...)`` host arrays,
    plus the held-out test set."""
    xtr, ytr, xte, yte = make_data(seed, clients * shard, num_test)
    order = np.random.default_rng(seed).permutation(clients * shard)
    xs = xtr[order].reshape(clients, shard, *xtr.shape[1:])
    ys = ytr[order].reshape(clients, shard)
    return xs, ys, xte, yte


# -------------------------------------------------------------- telemetry

def telemetry(seed: int, model_bytes: Sequence[float],
              num_samples: Sequence[int], local_epochs: int = 1) -> Dict:
    """Paper Table 4 system heterogeneity: uplink U[1,5]e4 bit/s,
    downlink U[4,20]e4 bit/s, CPU U[1,10] GHz, U[1,10] Mcycles/sample.
    Copy of the program's ``sample_system_telemetry``; label coverage 1
    (the stand-in data is split IID)."""
    n = len(model_bytes)
    rng = np.random.default_rng(seed)
    bits_u = rng.uniform(1e4, 5e4, n)
    bits_d = rng.uniform(4e4, 2e5, n)
    f_ghz = rng.uniform(1, 10, n)
    c_mc = rng.uniform(1, 10, n)
    samples = np.asarray(num_samples, float)
    return {
        "model_bytes": np.asarray(model_bytes, float),
        "uplink_rate": bits_u / 8.0,
        "downlink_rate": bits_d / 8.0,
        "compute_latency": c_mc * 1e6 * samples * local_epochs
        / (f_ghz * 1e9),
        "num_samples": samples,
        "label_coverage": np.ones(n),
        "train_loss": np.ones(n),
    }


# ------------------------------------------------------ local SGD (user)

def make_client_step(apply_fn, batch: int, lr: float, epochs: int):
    """``step(params, x, y) -> (params, mean loss)``: ``epochs`` passes of
    minibatch SGD over the client's samples in their stored order, as a
    user writes the per-client step that ``make_batched_train_fn`` vmaps.
    ``apply_fn(params, x) -> logits`` is the model under test."""
    def loss_fn(p, x, y):
        logits = apply_fn(p, x)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - gold)

    def sgd(p, b):
        loss, g = jax.value_and_grad(loss_fn)(p, *b)
        return jax.tree_util.tree_map(lambda w, d: w - lr * d, p, g), loss

    def step(p, x, y):
        xb = x.reshape(-1, batch, *x.shape[1:])
        yb = y.reshape(-1, batch)
        losses = []
        for _ in range(epochs):
            p, l = jax.lax.scan(sgd, p, (xb, yb))
            losses.append(l)
        return p, jnp.mean(jnp.concatenate(losses))

    return step
