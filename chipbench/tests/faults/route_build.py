"""Faults of the ``route_build`` timed path: each wraps ``Cell.build``.

``FAULTS`` maps a fault's name to the ``Cell`` method it replaces and the
replacement ``fn(self, orig, *args)``; the check must find each run not
correct.
"""

from __future__ import annotations

import numpy as np


def stale_routes(self, orig, tops, comms):
    """Routing state left unchanged: the previous fabric's tables."""
    if not hasattr(self, "_prev"):
        # the warm-up unit: the window's units reuse its tables
        self._prev = orig(self, tops, comms)
    return self._prev


def half_routes(self, orig, tops, comms):
    """Half of the batch left out: its tables copied from the rest."""
    half = list(orig(self, tops[: len(tops) // 2], comms[: len(comms) // 2]))
    return half + half[: len(tops) - len(half)]


def path_altered(self, orig, tops, comms):
    """An answer altered where it is produced: one hop of one path."""
    systems = list(orig(self, tops, comms))
    ps = systems[-1]
    pe = np.array(ps.path_edges)
    pe[len(pe) // 2, 0] = (pe[len(pe) // 2, 0] + 1) % ps.n_slots
    ps.path_edges = pe
    return systems


FAULTS = {"state_unchanged": ("build", stale_routes),
          "half_batch": ("build", half_routes),
          "answer_altered": ("build", path_altered)}
