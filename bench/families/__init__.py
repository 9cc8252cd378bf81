"""Client model families, one module each, found by the name a
configuration gives under ``"family"``.

A family module (``bench/families/<family>.py``) holds everything the
harness needs of one kind of client model, so that a configuration of a
new kind is new files only.  A model is described by a hashable value
(the VGG family's is its layer list); clients with equal descriptions
share one sub-model.  It defines:

* ``models(conf) -> (global_model, [client_model, ...])``: the global
  model and one description per client;
* ``data(conf, clients, eval_samples) -> (xs, ys, xte, yte)``: host
  arrays drawn from ``conf["data_seed"]``, each client's samples stacked
  as ``(clients, samples, ...)``;
* ``param_bytes(model) -> int``: float32 bytes of a model's parameters;
* ``make_weights(seed, global_model, kinds) -> (global, [sub, ...])``:
  the global weights and each kind's sub-model, in one jitted call;
* ``client_step(setup) -> (step, data)``: the per-client fused step
  ``step(params, *client_data) -> (params, loss)`` a user hands to
  ``make_batched_train_fn``, and its stacked host data;
* ``local_train_fn(setup, model)``: the program's per-client trainer
  ``(params, client, rng) -> (params, loss)`` for a ragged fleet;
* ``eval_fn(setup)``: the program's evaluation of the global model;
* ``train_flops(model, samples)`` and ``forward_flops(model, samples)``:
  forward + backward and forward FLOPs, counted from shapes;
* ``cpu_cut(conf, clients) -> conf``: the configuration cut to a size a
  CPU test run holds, with ``clients`` clients in a homogeneous fleet.

Its plain reference is ``bench/references/<reference>.py``.
"""

import importlib


def load(name: str):
    """The family module ``bench/families/<name>.py``."""
    return importlib.import_module(f"{__name__}.{name}")
