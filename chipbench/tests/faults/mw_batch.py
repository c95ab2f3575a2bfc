"""Faults of the ``mw_batch`` timed path: each wraps ``Cell.solve``.

``FAULTS`` maps a fault's name to the ``Cell`` method it replaces and the
replacement ``fn(self, orig, *args)``; the check must find each run not
correct.
"""

from __future__ import annotations


def state_unchanged(self, orig, systems):
    """The MW step returns its state unchanged: the uniform split."""
    from repro.core import flow

    real = flow._mw_window_batch

    def frozen(pe, owner, demands, inv_cap, slot_valid, carry, *a, **k):
        return carry

    flow._mw_window_batch = frozen
    try:
        return orig(self, systems)
    finally:
        flow._mw_window_batch = real


def half_batch(self, orig, systems):
    """Half of the batch left out: its answers copied from the rest."""
    half = orig(self, systems[: len(systems) // 2])
    return half + half[: len(systems) - len(half)]


def alpha_altered(self, orig, systems):
    """An answer altered where it is produced: one alpha off by 1%."""
    out = orig(self, systems)
    out[-1].alpha *= 1.01
    return out


FAULTS = {"state_unchanged": ("solve", state_unchanged),
          "half_batch": ("solve", half_batch),
          "answer_altered": ("solve", alpha_altered)}
