"""Tests of the readers of the MW window-sync layer
(``metrics/mw_live_share.py``, ``metrics/mw_syncs_per_solve.py``) on
hand-built spans, and of their silence on a program without the spans."""

from __future__ import annotations

import pytest

from chipbench import run
from repro.obs import Span


def _span(name, **attrs):
    return Span(name=name, span_id=0, parent_id=-1, tid=0, depth=0, t0=0.0,
                wall_s=1.0, trmalloc_delta=None, attrs=attrs)


def _reader(name):
    return run._load(run.metric_reader(name), f"chipbench_metric_{name}")


def _wave(active):
    """One adaptive solve: a window of 50 and a sync per entry of
    ``active``, over a batch of 4 (3 instances and a bucket pad)."""
    out = [_span("mw/assemble"), _span("mw/upload", bytes=1)]
    for a in active:
        out += [_span("mw/window_batch", t0=0, step=50, active=a,
                      instances=4), _span("mw/sync", t0=0, active=a)]
    return out + [_span("mw/readback", instances=4)]


# two early stops (after windows 6 and 8), one budget-bound instance
TWO_SOLVES = (_wave([3] * 6 + [2] * 2 + [1] * 2)
              + _wave([3] * 6 + [2] * 2 + [1] * 2))
# the parent program: no sync spans, windows without ``instances``
PARENT = [_span("mw/window_batch", t0=0, step=50, active=3),
          _span("mw/readback", instances=4)]


@pytest.mark.parametrize("name,want", [
    ("mw_live_share", 100.0 * (6 * 3 + 2 * 2 + 2 * 1) / (10 * 4)),
    ("mw_syncs_per_solve", 10.0),
])
def test_sync_layer_readers(name, want):
    read = _reader(name).read
    assert read({"spans": TWO_SOLVES, "units": 2}) == pytest.approx(want)
    assert read({"spans": PARENT, "units": 2}) is None
    assert read({"spans": [], "units": 2}) is None


def test_live_share_all_live_is_100():
    read = _reader("mw_live_share").read
    full = [_span("mw/window_batch", t0=0, step=400, active=4, instances=4)]
    assert read({"spans": full, "units": 1}) == 100.0


def test_live_share_weights_windows_by_steps():
    read = _reader("mw_live_share").read
    spans = [_span("mw/window_batch", t0=0, step=50, active=4, instances=4),
             _span("mw/window_batch", t0=50, step=10, active=1,
                   instances=4)]
    assert read({"spans": spans, "units": 1}) == pytest.approx(
        100.0 * (200 + 10) / (200 + 40))
