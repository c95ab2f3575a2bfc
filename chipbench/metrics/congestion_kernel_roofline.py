"""Share of its roofline that the fused congestion kernel reaches, in %.

The least time the chip could take for the kernel's calls in the traced
window (per call the larger of bytes over peak bandwidth and operations
over peak rate, ``roofline.congestion_cost`` at the stacked incidence
shape read from the kernel's own operand) over their summed device time.
Peaks come from ``peaks.json`` by device kind.  No kernel call in the
window: nothing to read.
"""

from chipbench import roofline

KERNEL = "congestion_pallas_batch"


def read(ctx):
    tr = ctx["trace"]
    s, calls = tr.op_s(KERNEL)
    shapes = tr.op_shapes(KERNEL)
    if not calls or s <= 0 or len(shapes) != 1 or len(shapes[0]) != 3:
        return None
    pk = roofline.peaks(ctx["device_kind"])
    flops, nbytes = roofline.congestion_cost(*shapes[0])
    return 100.0 * calls * roofline.least_time(flops, nbytes, pk) / s
