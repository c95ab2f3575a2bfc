"""Device milliseconds of the APSP min-plus kernel per routing build.

The summed device time of the ``minplus_pallas`` operations
(``kernels/minplus.py``, driven by ``ops.apsp_minplus_blocked``) in the
traced window, over the builds the window finished.
"""


def read(ctx):
    s, calls = ctx["trace"].op_s("minplus")
    if not calls or not ctx.get("builds"):
        return None
    return s * 1e3 / ctx["builds"]
