"""Tests of the per-layer readers of the program's host-phase spans
(``metrics/apsp_host_s.py`` and the others), on hand-built spans."""

from __future__ import annotations

import pytest

from chipbench import run
from repro.obs import Span


def _span(name, wall_s, parent=-1, **attrs):
    return Span(name=name, span_id=0, parent_id=parent, tid=0, depth=0,
                t0=0.0, wall_s=wall_s, trmalloc_delta=None, attrs=attrs)


def _reader(name):
    return run._load(run.metric_reader(name), f"chipbench_metric_{name}")


ROUTE_SPANS = [
    _span("build/batch", 8.0), _span("build/apsp", 0.5, switches=2048),
    _span("build/apsp", 0.25, switches=2048),
    _span("build/slots", 1.0, rows=700),
    _span("build/assemble", 0.5, rows=700),
    _span("build/enumerate", 4.0, pairs=100),
    _span("build/shard", 2.0, pairs=60), _span("build/shard", 1.5, pairs=40),
    _span("build/shard", 0.1, pairs=10),
]
MW_SPANS = [
    _span("mw/assemble", 0.3, rows=700), _span("mw/upload", 0.05, bytes=4e7),
    _span("mw/assemble", 0.2, rows=700), _span("mw/upload", 0.05, bytes=2e7),
    _span("mw/window_batch", 0.01), _span("mw/readback", 26.0),
]


@pytest.mark.parametrize("name,spans,ctx,want", [
    ("apsp_host_s", ROUTE_SPANS, {"builds": 3}, 0.75 / 3),
    ("slot_assembly_host_s", ROUTE_SPANS, {"builds": 3}, 1.5 / 3),
    ("enum_attempts_per_pair", ROUTE_SPANS, {}, 110 / 100),
    ("mw_assembly_host_s", MW_SPANS, {"units": 2}, 0.6 / 2),
    ("mw_h2d_mb_per_solve", MW_SPANS, {"units": 2}, 60 / 2),
])
def test_host_phase_readers(name, spans, ctx, want):
    read = _reader(name).read
    assert read({"spans": spans, **ctx}) == pytest.approx(want)
    # the parent of this reader's spans, or a trace without them: no reading
    other = MW_SPANS if spans is ROUTE_SPANS else ROUTE_SPANS
    assert read({"spans": other, **ctx}) is None
    assert read({"spans": [], **ctx}) is None


def test_enum_attempts_one_when_every_pair_enumerated_once():
    read = _reader("enum_attempts_per_pair").read
    once = [_span("build/enumerate", 1.0, pairs=50),
            _span("build/shard", 0.5, pairs=30),
            _span("build/shard", 0.5, pairs=20)]
    assert read({"spans": once}) == 1.0
    # build/shard spans without their parent (a program that lacks it)
    assert read({"spans": once[1:]}) is None
