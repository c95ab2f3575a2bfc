"""Faults of the ``capacity_wave`` timed path: each wraps ``Cell.solve``.

``FAULTS`` maps a fault's name to the ``Cell`` method it replaces and the
replacement ``fn(self, orig, *args)``; the check must find each run not
correct.
"""

from __future__ import annotations


def state_unchanged(self, orig, groups):
    """The MW step returns its state unchanged: the uniform split."""
    from repro.core import flow

    real = flow._mw_window_batch

    def frozen(pe, owner, demands, inv_cap, slot_valid, carry, *a, **k):
        return carry

    flow._mw_window_batch = frozen
    try:
        return orig(self, groups)
    finally:
        flow._mw_window_batch = real


def half_batch(self, orig, groups):
    """Half of the wave left out: its answers copied from the rest."""
    half = max(len(groups) // 2, 1)
    verdicts, results = orig(self, groups[:half])
    take = [i % half for i in range(len(groups))]
    return [verdicts[i] for i in take], [results[i] for i in take]


def alpha_altered(self, orig, groups):
    """An answer altered where it is produced: one alpha off by 1%."""
    verdicts, results = orig(self, groups)
    results[-1][-1].alpha *= 1.01
    return verdicts, results


def rejected_accepted(self, orig, groups):
    """A candidate the solve rejected is reported accepted."""
    verdicts, results = orig(self, groups)
    verdicts = list(verdicts)
    verdicts[verdicts.index(False)] = True
    return verdicts, results


FAULTS = {"state_unchanged": ("solve", state_unchanged),
          "half_batch": ("solve", half_batch),
          "answer_altered": ("solve", alpha_altered),
          "rejected_accepted": ("solve", rejected_accepted)}
