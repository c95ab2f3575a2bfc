"""Host seconds of APSP per routing build.

The summed wall time of the program's ``build/apsp`` spans
(``core/routing.py`` ``build_path_system_batch``: the distance-matrix
cache misses, that is the adjacency, the min-plus kernel's calls and tile
transfers, and the int16 result) inside the window, over the builds the
window finished.
"""


def read(ctx):
    spans = [s for s in ctx["spans"] if s.name == "build/apsp"]
    if not spans or not ctx.get("builds"):
        return None
    return sum(s.wall_s for s in spans) / ctx["builds"]
