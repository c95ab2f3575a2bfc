"""Share of the traced window in which no operation ran on the device, in %.

1 - (union of the device's operation intervals / window), averaged over
the chips the cell uses (``xtrace.Trace.busy_s``).  Reads every metric
named ``idle_share.<suffix>``: the suffix only splits the metric by the
end-to-end metric it moves.
"""


def read(ctx):
    tr = ctx["trace"]
    if tr.window_s <= 0 or not tr.devices:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
