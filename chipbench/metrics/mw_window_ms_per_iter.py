"""Device milliseconds of the batched MW window program per MW iteration.

The summed device time of the ``_mw_window_batch`` program runs in the
traced window (``core/flow.py``), over the MW iterations the window's
solves ran.
"""


def read(ctx):
    s, runs = ctx["trace"].module_s("_mw_window_batch")
    if not runs or not ctx.get("mw_iters"):
        return None
    return s * 1e3 / ctx["mw_iters"]
