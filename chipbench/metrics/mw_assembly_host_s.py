"""Host seconds of MW batch assembly and upload per solve.

The summed wall time of the program's ``mw/assemble`` spans (stacking the
path systems, ``PathSystemBatch.from_systems``) and ``mw/upload`` spans
(the host tables sent to the device and the carry's set-up), both in
``core/flow.py`` ``mw_concurrent_flow_batch``, inside the window, over the
solves the window finished.
"""

NAMES = ("mw/assemble", "mw/upload")


def read(ctx):
    spans = [s for s in ctx["spans"] if s.name in NAMES]
    if not spans or not ctx.get("units"):
        return None
    return sum(s.wall_s for s in spans) / ctx["units"]
