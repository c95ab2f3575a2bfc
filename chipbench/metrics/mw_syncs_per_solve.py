"""Host syncs per MW solve.

The number of the program's ``mw/sync`` spans (``core/flow.py``
``mw_concurrent_flow_batch``, adaptive solves: one per window, the host's
read of every instance's best alpha and the stop decisions) inside the
window, over the solves the window finished.
"""


def read(ctx):
    n = sum(1 for s in ctx["spans"] if s.name == "mw/sync")
    if not n or not ctx.get("units"):
        return None
    return n / ctx["units"]
