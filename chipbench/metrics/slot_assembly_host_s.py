"""Host seconds of slot assembly per routing build.

The summed wall time of the program's ``build/slots`` spans (each
instance's paths copied out of the enumeration and converted to padded
link-slot rows) and ``build/assemble`` spans (``PathSystemBatch
.from_systems``: the stacked tables and gather tables), both in
``core/routing.py`` ``build_path_system_batch``, inside the window, over
the builds the window finished.
"""

NAMES = ("build/slots", "build/assemble")


def read(ctx):
    spans = [s for s in ctx["spans"] if s.name in NAMES]
    if not spans or not ctx.get("builds"):
        return None
    return sum(s.wall_s for s in spans) / ctx["builds"]
