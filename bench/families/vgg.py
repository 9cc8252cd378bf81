"""The VGG client of the FedDD paper (arXiv 2308.16835, Table 3).

A model is its layer list: 3x3 conv + ReLU + 2x2 max pool per conv
width, then dense layers.  A configuration gives the global widths under
``"global"`` and the fleet as groups of clients of one width under
``"fleet"`` (``{"conv": [...], "fc": [...], "clients": n}``); a narrower
client is the leading channels of the global model (HeteroFL slicing).
The data is a CIFAR-10-shaped stand-in; the program's model code
(``repro.fl.models``) is what the training and eval callables run.
"""

from __future__ import annotations

import math
import types
import zlib
from typing import Dict, List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from bench import fleet

Layer = Tuple  # ("conv", cin, cout, k) | ("pool",) | ("fc", din, dout)
SIDE = 32       # input images are SIDE x SIDE x 3


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


def models(conf: Dict):
    """The global layer list and one per client, from the configuration."""
    return (vgg_layers(conf["global"]["conv"], conf["global"]["fc"]),
            [vgg_layers(g["conv"], g["fc"])
             for g in conf["fleet"] for _ in range(g["clients"])])


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


def make_weights(seed: int, global_layers, kinds=()):
    """Global weights and each kind's sub-model sliced from them, in one
    jitted call on the default device."""
    shapes = [leaf_shapes(c) for c in kinds]

    @jax.jit
    def build(key):
        g = init_params(key, global_layers)
        return g, [slice_to(g, s) for s in shapes]

    return build(jax.random.PRNGKey(fleet.seed32(seed)))


def param_bytes(layers: Sequence[Layer]) -> int:
    return 4 * sum(math.prod(s) for leaves in leaf_shapes(layers).values()
                   for s in leaves.values())


# ------------------------------------------------------------------- data

def make_data(seed: int, num_train: int, num_test: int,
              shape=(SIDE, SIDE, 3), classes: int = 10,
              latent_dim: int = 32, modes_per_class: int = 3,
              class_sep: float = 3.2, noise: float = 0.9):
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


def data(conf: Dict, clients: int, eval_samples: int):
    """Each client's samples (a seeded shuffle of the training set cut
    into equal blocks) as ``(clients, samples, ...)`` host arrays, plus
    the held-out test set."""
    seed, shard = conf["data_seed"], conf["samples_per_client"]
    xtr, ytr, xte, yte = make_data(seed, clients * shard, eval_samples)
    order = np.random.default_rng(seed).permutation(clients * shard)
    xs = xtr[order].reshape(clients, shard, *xtr.shape[1:])
    ys = ytr[order].reshape(clients, shard)
    return xs, ys, xte, yte


# ----------------------------------------------- the program's callables

def client_step(setup: Dict):
    """The fused per-client SGD step over the program's VGG forward, and
    the stacked client data it takes."""
    from repro.fl.models import apply_spec
    layers = setup["global_model"]
    step = fleet.make_client_step(lambda p, x: apply_spec(p, layers, x),
                                  setup["batch"], setup["lr"],
                                  setup["epochs"])
    return step, (setup["xs"], setup["ys"])


def local_train_fn(setup: Dict, layers):
    """The program's per-client trainer of one width, over every
    client's samples."""
    from repro.fl.models import make_local_train_fn
    n, s = setup["xs"].shape[:2]
    ds = types.SimpleNamespace(x=setup["xs"].reshape(n * s,
                                                     *setup["xs"].shape[2:]),
                               y=setup["ys"].reshape(n * s))
    return make_local_train_fn(layers, ds, np.arange(n * s).reshape(n, s),
                               lr=setup["lr"], batch_size=setup["batch"],
                               local_epochs=setup["epochs"])


def eval_fn(setup: Dict):
    from repro.fl.models import make_eval_fn
    return make_eval_fn(setup["global_model"],
                        types.SimpleNamespace(x=setup["xte"],
                                              y=setup["yte"]),
                        batch_size=512)


# ------------------------------------------------------------------ counts

def layer_forward_flops(layers, hw: int = SIDE):
    """Per layer, 2 x multiply-adds of one sample's forward pass ("SAME"
    k x k convolutions, 2x2 pools halving the side, dense layers)."""
    out, side = [], hw
    for layer in layers:
        if layer[0] == "conv":
            _, cin, cout, k = layer
            out.append(2 * side * side * k * k * cin * cout)
        elif layer[0] == "pool":
            side //= 2
        else:
            out.append(2 * layer[1] * layer[2])
    return out


def train_flops_per_sample(layers, hw: int = SIDE) -> int:
    """Forward + backward of one sample: forward F, weight gradient F and
    input gradient F per layer, except the first layer, whose input (the
    image) needs no gradient."""
    f = layer_forward_flops(layers, hw)
    return 3 * sum(f) - f[0]


def train_flops(layers, samples: int) -> int:
    return train_flops_per_sample(layers) * samples


def forward_flops(layers, samples: int) -> int:
    return sum(layer_forward_flops(layers)) * samples


# -------------------------------------------------------------- CPU size

def cpu_cut(conf: Dict, clients: int) -> Dict:
    """Every width divided by 8; a homogeneous fleet of ``clients``, a
    ragged one cut to one client of each of its two widest and two
    narrowest models."""
    def cut(spec):
        return {"conv": [max(1, w // 8) for w in spec["conv"]],
                "fc": [max(1, w // 8) for w in spec["fc"]]}

    homogeneous = len(conf["fleet"]) == 1
    groups = conf["fleet"] if homogeneous \
        else conf["fleet"][:2] + conf["fleet"][-2:]
    return dict(conf, **{"global": cut(conf["global"]),
                         "fleet": [dict(cut(g), clients=clients
                                        if homogeneous else 1)
                                   for g in groups]})
