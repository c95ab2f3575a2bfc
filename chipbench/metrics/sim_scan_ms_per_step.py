"""Device milliseconds of the simulator's scan program per simulated step.

The summed device time of the ``_sim_scan`` program runs in the traced
window (``sim/engine.py``: arrivals, path selection, waterfilling and
drain, one scan per segment between events), over the simulated steps of
the window's simulations.
"""


def read(ctx):
    s, runs = ctx["trace"].module_s("_sim_scan")
    if not runs or not ctx.get("sim_steps"):
        return None
    return s * 1e3 / ctx["sim_steps"]
