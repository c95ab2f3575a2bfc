"""Megabytes sent from host to device per MW solve.

The summed ``bytes`` of the program's ``mw/upload`` spans (``core/flow.py``
``mw_concurrent_flow_batch``: every host table the solve uploads) inside
the window, in units of 1e6 bytes, over the solves the window finished.
"""


def read(ctx):
    spans = [s for s in ctx["spans"] if s.name == "mw/upload"]
    if not spans or not ctx.get("units"):
        return None
    return sum(s.attrs.get("bytes", 0) for s in spans) / 1e6 / ctx["units"]
