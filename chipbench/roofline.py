"""Chip peaks and the operations and bytes of the kernels the benchmark reads.

``peaks(device_kind)`` looks the chip up in ``peaks.json``; a kind that is
not there is an error, never a default.  The cost functions count what the
algorithm needs for one call, from its shapes:

* the fused congestion kernel (``kernels/congestion.py``, rank-3 form)
  computes, for each of ``bt`` stacked {0,1} incidences of shape (P, S),
  ``loads = B^T r`` and ``costs = B w``: 2 * P * S operations each, reading
  the incidence once, the rates and prices once, and writing loads and
  costs once, all float32.
"""

from __future__ import annotations

import json
import pathlib

_PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(_PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def congestion_cost(bt: int, p: int, s: int) -> tuple[float, float]:
    """(operations, bytes) of one fused congestion call on (bt, p, s)."""
    flops = 4.0 * bt * p * s
    nbytes = 4.0 * (bt * p * s + 2 * bt * p + 2 * bt * s)
    return flops, nbytes


def least_time(flops: float, nbytes: float, pk: dict) -> float:
    """Seconds the chip needs at best: the larger of its two bounds."""
    return max(flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
