"""What a cell is made of besides its client model, from its
configuration file and the seed.

Everything here is the benchmark's own: the seed's folding, the Table 4
client telemetry, and the minibatch SGD loop of the fused per-client step
a user hands to ``make_batched_train_fn`` (the model's forward comes from
its family, ``bench/families``).  The telemetry sampler is a copy of the
program's ``repro.fl.heterogeneity.sample_system_telemetry``, so the
yardstick does not move when the program changes it.  The plain
reference (``bench/references``) builds the same weights, data and
telemetry from the same seed through this module and the family, never
from anything the program made.
"""

from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

import jax
import jax.numpy as jnp


def seed32(seed: int) -> int:
    """The run's seed folded into the 31 bits a PRNG key and the
    protocol's ``seed`` take."""
    return int(seed) % (2 ** 31 - 1)


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
        gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    def sgd(p, b):
        loss, g = jax.value_and_grad(loss_fn)(p, *b)
        return jax.tree_util.tree_map(lambda w, d: w - lr * d, p, g), loss

    def step(p, x, y):
        xb = x.reshape(-1, batch, *x.shape[1:])
        yb = y.reshape(-1, batch, *y.shape[1:])
        losses = []
        for _ in range(epochs):
            p, l = jax.lax.scan(sgd, p, (xb, yb))
            losses.append(l)
        return p, jnp.mean(jnp.concatenate(losses))

    return step
