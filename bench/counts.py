"""Operations and bytes of a FedDD round, counted from shapes.

What the algorithm needs, whatever implements it: the roofline and MFU
readers divide these by measured device time.  A client model is the
layer list of ``bench/fleet.py``.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Sequence

from bench import fleet

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def layer_forward_flops(layers, hw: int = 32):
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


def train_flops_per_sample(layers, hw: int = 32) -> int:
    """Forward + backward of one sample: forward F, weight gradient F and
    input gradient F per layer, except the first layer, whose input (the
    image) needs no gradient."""
    f = layer_forward_flops(layers, hw)
    return 3 * sum(f) - f[0]


def round_flops(client_layers: Sequence, samples: int, epochs: int,
                eval_layers=None, eval_samples: int = 0) -> int:
    """Local training of every client for one round, plus one evaluation
    pass of the global model when the mix evaluates every round."""
    flops = sum(train_flops_per_sample(l) * samples * epochs
                for l in client_layers)
    if eval_layers is not None and eval_samples:
        flops += sum(layer_forward_flops(eval_layers)) * eval_samples
    return flops


def server_bytes_per_round(client_layers: Sequence, global_layers) -> int:
    """The least HBM traffic of the server step: each client's parameters
    before and after local training are read once (importance scores,
    Eq. (4) and Eq. (5) from the same pass) and its next parameters
    written once; the global model is read and written once.  float32."""
    clients = sum(fleet.param_bytes(l) for l in client_layers)
    return 3 * clients + 2 * fleet.param_bytes(global_layers)


def peaks(device_kind: str) -> Dict:
    """The chip's peaks; a device kind not in the table is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}: add them with their source")
    return table[device_kind]
