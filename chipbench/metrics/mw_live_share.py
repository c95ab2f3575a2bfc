"""Share of the instance-iterations an MW solve computed that belonged to
live instances, %.

An adaptive batched solve (``core/flow.py`` ``mw_concurrent_flow_batch``)
runs every instance of its padded batch in every window, frozen ones and
bucket padding included; each window is one ``mw/window_batch`` span with
``active`` live instances of ``instances`` and ``step`` iterations.  The
reading is Σ active × step ÷ Σ instances × step over the window's spans.
"""


def read(ctx):
    spans = [s for s in ctx["spans"] if s.name == "mw/window_batch"
             and "instances" in s.attrs]
    total = sum(s.attrs["instances"] * s.attrs["step"] for s in spans)
    if not total:
        return None
    live = sum(s.attrs["active"] * s.attrs["step"] for s in spans)
    return 100.0 * live / total
