"""Host seconds of event handling per simulation.

The summed wall time of the program's ``sim/reroute`` spans
(``sim/events.py``: applying the failure or repair, re-routing with
``update_path_system``, re-stacking the batch and migrating the live
flows) inside the window, over the simulations the window finished.
"""


def read(ctx):
    spans = [s for s in ctx["spans"] if s.name == "sim/reroute"]
    if not spans or not ctx.get("sims"):
        return None
    return sum(s.wall_s for s in spans) / ctx["sims"]
