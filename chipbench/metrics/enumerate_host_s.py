"""Host seconds of k-shortest-path enumeration per routing build.

The summed wall time of the program's ``build/shard`` spans
(``core/routing.py``, one per enumeration shard) that lie inside the
window, over the builds the window finished.
"""


def read(ctx):
    spans = [s for s in ctx["spans"] if s.name == "build/shard"]
    if not spans or not ctx.get("builds"):
        return None
    return sum(s.wall_s for s in spans) / ctx["builds"]
