"""Operations and bytes of a FedDD round, counted from shapes.

What the algorithm needs, whatever implements it: the roofline and MFU
readers divide these by measured device time.  A client model is
described and counted by its family (``bench/families``).
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Sequence

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def round_flops(family, client_models: Sequence, samples: int, epochs: int,
                eval_model=None, eval_samples: int = 0) -> int:
    """Local training of every client for one round, plus one evaluation
    pass of the global model when the mix evaluates every round."""
    flops = sum(family.train_flops(m, samples * epochs)
                for m in client_models)
    if eval_model is not None and eval_samples:
        flops += family.forward_flops(eval_model, eval_samples)
    return flops


def server_bytes_per_round(family, client_models: Sequence,
                           global_model) -> int:
    """The least HBM traffic of the server step over the whole fleet: each
    client's parameters before and after local training are read once
    (importance scores, Eq. (4) and Eq. (5) from the same pass) and its
    next parameters written once; the global model is read and written
    once.  float32."""
    clients = sum(family.param_bytes(m) for m in client_models)
    return 3 * clients + 2 * family.param_bytes(global_model)


def peaks(device_kind: str) -> Dict:
    """The chip's peaks; a device kind not in the table is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}: add them with their source")
    return table[device_kind]
