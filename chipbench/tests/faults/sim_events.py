"""Faults of the ``sim_events`` timed path: each wraps ``Cell.simulate``.

``FAULTS`` maps a fault's name to the ``Cell`` method it replaces and the
replacement ``fn(self, orig, *args)``; the check must find each run not
correct.
"""

from __future__ import annotations

import types

import numpy as np


def state_unchanged(self, orig, seed):
    """The scan step returns its state unchanged."""
    from repro.sim import engine

    real = engine._sim_scan

    def frozen(carry0, ts, *a, **k):
        z = np.zeros((len(ts), carry0[0].shape[0]), np.float32)
        return carry0, z, z.astype(np.int32), z

    engine._sim_scan = frozen
    try:
        return orig(self, seed)
    finally:
        engine._sim_scan = real


def half_batch(self, orig, seed):
    """Half of the batch left out: its answers copied from the rest."""
    tops, comms, systems = self.tops, self.comms, self.systems
    h = len(tops) // 2
    self.tops, self.comms, self.systems = tops[:h], comms[:h], systems[:h]
    try:
        res = orig(self, seed)
    finally:
        self.tops, self.comms, self.systems = tops, comms, systems
    idx = np.arange(len(tops)) % h
    out = {}
    for k, v in vars(res).items():
        if isinstance(v, np.ndarray) and v.ndim and v.shape[-1] == h and k in (
                "throughput", "active", "blackholed"):
            out[k] = v[:, idx]
        elif isinstance(v, np.ndarray) and v.ndim and v.shape[0] == h:
            out[k] = v[idx]
        else:
            out[k] = v
    return types.SimpleNamespace(**out)


def delivered_altered(self, orig, seed):
    """An answer altered where it is produced: one instance's delivered
    volume 1% high."""
    res = orig(self, seed)
    res.comm_delivered = np.array(res.comm_delivered)
    res.comm_delivered[-1] *= 1.01
    return res


def commodity_swapped(self, orig, seed):
    """Whose flows got through altered, every total kept: in each instance
    the delivered volumes of its busiest and its idlest commodity swap."""
    res = orig(self, seed)
    d = np.array(res.comm_delivered)
    for b, (src, _, _) in enumerate(self.inst):
        hi, lo = np.argmax(d[b, : len(src)]), np.argmin(d[b, : len(src)])
        d[b, [hi, lo]] = d[b, [lo, hi]]
    res.comm_delivered = d
    return res


FAULTS = {"state_unchanged": ("simulate", state_unchanged),
          "half_batch": ("simulate", half_batch),
          "answer_altered": ("simulate", delivered_altered),
          "commodity_swapped": ("simulate", commodity_swapped)}
