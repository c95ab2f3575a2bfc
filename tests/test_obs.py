"""Tests for ``repro.obs`` — tracing/metrics layer (INVARIANTS.md OB-1).

The load-bearing contract: spans live only at host boundaries, so a traced
run executes the IDENTICAL compiled program as an untraced one — asserted
bit-for-bit over an MW solve (single + batch), a batch and a delta-update
build, and a ``simulate_events`` fail/heal chain.  Each host phase of a
route build, an MW solve and a sim re-route is its own span, and spans
land natively on the JAX profiler's host plane.  Plus the tracer/metrics
unit surface: span nesting, the zero-overhead no-op path, Chrome-trace
(Perfetto) export schema, counters and gauges, the event bus, the report
CLI, and the ``REPRO_TRACE`` registry knob's import-time validation.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro import obs
from repro.core import (
    build_path_system,
    jellyfish,
    mw_concurrent_flow,
    mw_concurrent_flow_batch,
    random_permutation_traffic,
)
from repro.core.routing import update_path_system
from repro.core.failures import fail_links
from repro.core.traffic import (
    permutation_commodities,
    random_server_permutation,
)
from repro.sim import Event, SimConfig, simulate_events, steady_poisson

ROOT = pathlib.Path(__file__).resolve().parent.parent

_SIM_FIELDS = (
    "throughput", "active", "fct_hist", "fct_sum", "fct_count",
    "comm_delivered", "comm_offered", "util_sum", "drops", "admitted",
    "blackholed", "blackholed_total", "inflight", "demands", "slot_valid",
)


@pytest.fixture
def traced():
    """Enable tracing for one test; restore the previous state after."""
    prev = obs.set_trace(True)
    obs.reset_trace()
    yield
    obs.set_trace(prev)
    obs.reset_trace()


# --------------------------------------------------------------------------- #
# tracer unit surface
# --------------------------------------------------------------------------- #


def test_span_noop_when_disabled():
    prev = obs.set_trace(False)
    try:
        obs.reset_trace()
        before = len(obs.get_spans())
        with obs.span("should/not/record", x=1):
            pass
        obs.instant("nor/this")
        obs.counter_event("nor/that", 1.0)
        assert len(obs.get_spans()) == before
        assert obs.get_events() == []
        # the disabled path hands back one shared object — no allocation
        assert obs.span("a") is obs.span("b")
    finally:
        obs.set_trace(prev)


def test_span_nesting_and_fields(traced):
    with obs.span("outer", kind="test"):
        with obs.span("inner"):
            pass
    spans = {sp.name: sp for sp in obs.get_spans()}
    assert set(spans) == {"outer", "inner"}
    outer, inner = spans["outer"], spans["inner"]
    assert inner.parent_id == outer.span_id
    assert outer.parent_id == -1
    assert inner.depth == outer.depth + 1
    assert outer.wall_s >= inner.wall_s >= 0.0
    assert outer.attrs == {"kind": "test"}
    rec = outer.to_record()
    assert rec["kind"] == "span" and rec["name"] == "outer"
    # no per-span process-lifetime RSS mark: it named nothing of the span
    assert "rss_mb" not in rec and not hasattr(outer, "rss_mb")


def test_span_attrs_set_inside(traced):
    """An attribute known only once the work ran is added by ``set``; the
    disabled span takes the same call and keeps nothing."""
    with obs.span("counted", a=1) as sp:
        sp.set(found=7)
    (got,) = obs.get_spans()
    assert got.attrs == {"a": 1, "found": 7}
    prev = obs.set_trace(False)
    try:
        with obs.span("off") as sp:
            sp.set(found=1)
    finally:
        obs.set_trace(prev)
    assert [s.name for s in obs.get_spans()] == ["counted"]


def test_jsonl_and_chrome_export(traced, tmp_path):
    with obs.span("export/a", n=3):
        obs.instant("export/tick", note="hi")
        obs.counter_event("export/value", 2.5)
    jsonl = obs.write_jsonl(tmp_path / "t.jsonl")
    recs = [json.loads(line) for line in jsonl.read_text().splitlines()]
    assert {r["kind"] for r in recs} == {"span", "instant", "counter"}
    chrome = obs.write_chrome_trace(tmp_path / "t.chrome.json")
    payload = json.loads(chrome.read_text())
    assert obs.validate_chrome_trace(payload) == []
    phases = sorted(ev["ph"] for ev in payload["traceEvents"])
    assert phases == ["C", "X", "i"]
    x = next(ev for ev in payload["traceEvents"] if ev["ph"] == "X")
    assert x["name"] == "export/a" and x["dur"] >= 0
    assert x["args"]["n"] == 3


def test_validate_chrome_trace_catches_breakage():
    assert obs.validate_chrome_trace({}) != []
    assert obs.validate_chrome_trace({"traceEvents": 3}) != []
    bad = {"traceEvents": [{"name": "x", "ph": "X", "ts": 0.0, "pid": 1,
                            "tid": 1}]}  # complete event without dur
    assert any("dur" in p for p in obs.validate_chrome_trace(bad))
    bad2 = {"traceEvents": [{"name": "x", "ph": "?", "ts": 0.0, "pid": 1,
                             "tid": 1}]}
    assert any("phase" in p for p in obs.validate_chrome_trace(bad2))


def test_report_cli(traced, tmp_path, capsys):
    from repro.obs.__main__ import main

    with obs.span("report/solve"):
        pass
    path = obs.write_jsonl(tmp_path / "r.jsonl")
    assert main(["report", str(path)]) == 0
    out = capsys.readouterr().out
    assert "report/solve" in out
    assert main(["report", str(tmp_path / "missing-dir" / "*.jsonl")]) != 0


# --------------------------------------------------------------------------- #
# metrics + event bus
# --------------------------------------------------------------------------- #


def test_counter_gauge_hist():
    """Counters and gauges (the log2 histogram is gone: nothing read it)."""
    obs.reset_metrics()
    obs.counter("t/c").inc()
    obs.counter("t/c").inc(2.5)
    obs.gauge("t/g").set(0.75)
    snap = obs.snapshot()
    assert snap == {"t/c": pytest.approx(3.5), "t/g": pytest.approx(0.75)}
    assert not hasattr(obs, "hist") and not hasattr(obs, "Hist2")
    with pytest.raises(TypeError):
        obs.gauge("t/c")  # registered as a Counter
    obs.reset_metrics()
    assert obs.snapshot() == {}


def test_event_bus_and_compile_counter():
    obs.reset_metrics()
    seen = []

    def sub(name, **attrs):
        seen.append((name, attrs))

    obs.subscribe(sub)
    try:
        obs.emit("test/ping", x=1)
    finally:
        obs.unsubscribe(sub)
    obs.emit("test/ping", x=2)  # after unsubscribe: bus no longer calls sub
    assert seen == [("test/ping", {"x": 1})]
    assert obs.snapshot()["event/test/ping"] == 2

    with obs.count_compiles() as c:
        obs.emit("xla/backend_compile", event="e1")
        obs.emit("something/else")
    assert c.count == 1
    obs.reset_metrics()


def test_track_compiles_rides_the_bus():
    """retrace.track_compiles is now a bus subscriber; a bus-published
    compile event is indistinguishable from a real jax.monitoring one."""
    from repro.analysis.retrace import track_compiles

    with track_compiles() as c:
        obs.emit("xla/backend_compile", event="synthetic_backend_compile")
    assert c.count >= 1
    assert "synthetic_backend_compile" in c.events
    obs.reset_metrics()


def test_bench_helpers():
    dt = obs.timed(lambda: sum(range(100)), warmup=1, iters=2)
    assert dt >= 0.0
    out, secs, peak = obs.timed_peak(lambda: list(range(1000)))
    assert len(out) == 1000 and secs >= 0.0 and peak > 0
    rec = obs.perf_record("row", 1.25, tracemalloc_peak_bytes=peak,
                          compiles=2, extra_field="x")
    assert rec["name"] == "row" and rec["seconds"] == 1.25
    assert rec["ru_maxrss_mb"] > 0.0
    assert rec["tracemalloc_peak_bytes"] == peak
    assert rec["compiles"] == 2 and rec["extra_field"] == "x"


# --------------------------------------------------------------------------- #
# OB-1: traced runs are bit-identical to untraced runs
# --------------------------------------------------------------------------- #


def _flow_fields(res):
    return (res.alpha, np.asarray(res.rates).copy(), res.max_load,
            res.method, res.iters)


def test_mw_solve_traced_bit_identical():
    top = jellyfish(24, 8, 5, seed=0)
    comm = random_permutation_traffic(top, seed=0)
    ps = build_path_system(top, comm, k=4)

    base = _flow_fields(
        mw_concurrent_flow(ps, iters=120, early_stop=True, check_every=40)
    )
    prev = obs.set_trace(True)
    try:
        obs.reset_trace()
        traced = _flow_fields(
            mw_concurrent_flow(ps, iters=120, early_stop=True, check_every=40)
        )
        spans = obs.get_spans()
    finally:
        obs.set_trace(prev)
        obs.reset_trace()

    assert base[0] == traced[0]  # alpha, bit-exact
    assert np.array_equal(base[1], traced[1])  # rates, bit-exact
    assert base[2:] == traced[2:]
    assert any(sp.name == "mw/window_batch" for sp in spans)


def test_mw_batch_traced_bit_identical():
    tops = [jellyfish(20, 8, 5, seed=s) for s in range(2)]
    systems = [
        build_path_system(t, random_permutation_traffic(t, seed=s), k=4)
        for s, t in enumerate(tops)
    ]
    base = [
        _flow_fields(r)
        for r in mw_concurrent_flow_batch(systems, iters=80, early_stop=True,
                                          check_every=40)
    ]
    prev = obs.set_trace(True)
    try:
        obs.reset_trace()
        traced = [
            _flow_fields(r)
            for r in mw_concurrent_flow_batch(systems, iters=80,
                                              early_stop=True,
                                              check_every=40)
        ]
    finally:
        obs.set_trace(prev)
        obs.reset_trace()
    for b, t in zip(base, traced):
        assert b[0] == t[0] and np.array_equal(b[1], t[1]) and b[2:] == t[2:]


def test_delta_update_traced_bit_identical():
    top = jellyfish(24, 8, 5, seed=2)
    comm = random_permutation_traffic(top, seed=0)
    ps = build_path_system(top, comm, k=4)
    top_f = fail_links(top, n_links=3, seed=3)

    base = update_path_system(ps, top, top_f, comm)
    prev = obs.set_trace(True)
    try:
        obs.reset_trace()
        traced = update_path_system(ps, top, top_f, comm)
    finally:
        obs.set_trace(prev)
        obs.reset_trace()
    assert np.array_equal(base.path_edges, traced.path_edges)
    assert np.array_equal(base.path_owner, traced.path_owner)
    assert np.array_equal(base.path_len, traced.path_len)
    assert np.array_equal(base.row_map, traced.row_map)


def test_simulate_events_traced_bit_identical():
    tops = [jellyfish(20, 8, 5, seed=s + 1) for s in range(2)]
    comms = [
        permutation_commodities(
            t, random_server_permutation(t.n_servers, np.random.default_rng(s))
        )
        for s, t in enumerate(tops)
    ]
    wl = steady_poisson(40, 3.0)
    sched = [
        Event(step=12, kind="fail_links", n_links=3, seed=5, tag="f"),
        Event(step=24, kind="heal_links", heal_of="f"),
    ]
    cfg = SimConfig(max_flows=256, max_arrivals=8, wf_iters=6)

    base = simulate_events(tops, comms, sched, wl, k=4, policy="ecmp",
                           config=cfg, seed=7)
    prev = obs.set_trace(True)
    try:
        obs.reset_trace()
        traced = simulate_events(tops, comms, sched, wl, k=4, policy="ecmp",
                                 config=cfg, seed=7)
        spans = obs.get_spans()
    finally:
        obs.set_trace(prev)
        obs.reset_trace()

    for f in _SIM_FIELDS:
        a, b = getattr(base.result, f), getattr(traced.result, f)
        assert np.array_equal(np.asarray(a), np.asarray(b)), f
    names = {sp.name for sp in spans}
    assert "sim/segment" in names and "sim/reroute" in names


def test_solver_metrics_recorded():
    """The host window loop records alpha telemetry + early-stop reasons."""
    obs.reset_metrics()
    top = jellyfish(20, 8, 5, seed=1)
    comm = random_permutation_traffic(top, seed=0)
    ps = build_path_system(top, comm, k=4)
    mw_concurrent_flow(ps, iters=120, early_stop=True, check_every=40,
                       rel_tol=0.5)  # coarse tol: plateaus fast
    snap = obs.snapshot()
    assert snap["mw/solves"] >= 1
    assert snap["mw/windows_batch"] >= 1
    assert snap["mw/iters"] >= 40
    assert snap["mw/alpha"] > 0.0
    assert any(k.startswith("mw/stop/") for k in snap)
    obs.reset_metrics()


def test_buildpipe_metrics_recorded():
    from repro.core import stream_builds

    obs.reset_metrics()
    got = list(stream_builds((lambda i=i: i * i for i in range(4)),
                             enabled=True))
    assert got == [0, 1, 4, 9]
    snap = obs.snapshot()
    assert snap["pipeline/builds"] == 4
    assert snap["pipeline/stall_s"] >= 0.0
    assert snap["pipeline/overlap_s"] >= 0.0
    assert set(snap) == {"pipeline/builds", "pipeline/stall_s",
                         "pipeline/overlap_s"}
    obs.reset_metrics()


# --------------------------------------------------------------------------- #
# host phases as spans
# --------------------------------------------------------------------------- #


def _children(spans, parent):
    return sorted((s for s in spans if s.parent_id == parent.span_id),
                  key=lambda s: s.t0)


def _two_instances():
    tops = [jellyfish(24, 8, 5, seed=4), jellyfish(20, 8, 5, seed=5)]
    comms = [random_permutation_traffic(t, seed=s) for s, t in enumerate(tops)]
    return tops, comms


def test_route_build_phases_are_spans(traced):
    from repro.core import build_path_system_batch

    tops, comms = _two_instances()
    batch = build_path_system_batch(tops, comms, k=4, max_slack=2,
                                    cache=False)
    spans = obs.get_spans()
    (top,) = [s for s in spans if s.name == "build/batch"]
    kids = _children(spans, top)
    assert [s.name for s in kids] == [
        "build/prepare", "build/apsp", "build/slack", "build/enumerate",
        "build/slots", "build/assemble"]
    by = {s.name: s for s in kids}
    assert by["build/apsp"].attrs["switches"] == 44  # both fabrics missed
    assert by["build/slots"].attrs["rows"] == sum(
        ps.n_paths for ps in batch.systems)
    assert by["build/assemble"].attrs == by["build/slots"].attrs
    assert sum(s.wall_s for s in kids) <= top.wall_s
    enum = by["build/enumerate"]
    shards = [s for s in spans if s.name == "build/shard"]
    assert shards and all(s.parent_id == enum.span_id for s in shards)
    # every reachable pair entering is attempted at least once
    assert 0 < enum.attrs["pairs"] <= sum(s.attrs["pairs"] for s in shards)
    assert enum.attrs["pairs"] <= by["build/slack"].attrs["pairs"]


def test_route_build_traced_byte_identical():
    from repro.core import build_path_system_batch

    tops, comms = _two_instances()
    base = build_path_system_batch(tops, comms, k=4, cache=False)
    prev = obs.set_trace(True)
    try:
        traced = build_path_system_batch(tops, comms, k=4, cache=False)
    finally:
        obs.set_trace(prev)
        obs.reset_trace()
    for name in ("path_edges", "path_owner", "demands", "inv_cap",
                 "slot_valid", "slot_gather"):
        a, b = getattr(base, name), getattr(traced, name)
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), name
    for a, b in zip(base.systems, traced.systems):
        for name in ("path_edges", "path_len", "path_owner", "demands",
                     "unrouted"):
            assert getattr(a, name).tobytes() == getattr(b, name).tobytes()


def test_mw_batch_phases_are_spans(traced):
    tops = [jellyfish(20, 8, 5, seed=s) for s in range(3)]
    systems = [
        build_path_system(t, random_permutation_traffic(t, seed=s), k=4)
        for s, t in enumerate(tops)
    ]
    obs.reset_trace()
    mw_concurrent_flow_batch(systems, iters=30)  # non-adaptive
    names = [s.name for s in sorted(obs.get_spans(), key=lambda s: s.t0)]
    assert names == ["mw/assemble", "mw/upload", "mw/window_batch",
                     "mw/readback"]
    by = {s.name: s for s in obs.get_spans()}
    assert by["mw/assemble"].attrs["rows"] == sum(
        ps.n_paths for ps in systems)
    assert by["mw/upload"].attrs["bytes"] > sum(
        ps.path_edges.nbytes for ps in systems)
    # three instances, bucketed to a batch of four
    # seg_max: the split normalisation's passes, k = 4 paths bucketed to 4
    assert by["mw/window_batch"].attrs == {"t0": 0, "step": 30, "active": 3,
                                           "instances": 4, "seg_max": 4}


def test_sim_reroute_phases_are_spans(traced):
    tops = [jellyfish(20, 8, 5, seed=s + 1) for s in range(2)]
    comms = [
        permutation_commodities(
            t, random_server_permutation(t.n_servers, np.random.default_rng(s))
        )
        for s, t in enumerate(tops)
    ]
    sched = [Event(step=10, kind="fail_links", n_links=2, seed=5, tag="f")]
    simulate_events(tops, comms, sched, steady_poisson(20, 3.0), k=4,
                    policy="ecmp", seed=3,
                    config=SimConfig(max_flows=64, max_arrivals=8,
                                     wf_iters=4))
    spans = obs.get_spans()
    (reroute,) = [s for s in spans if s.name == "sim/reroute"]
    assert [s.name for s in _children(spans, reroute)] == [
        "sim/reroute/update", "sim/reroute/restack", "sim/reroute/migrate"]
    (first,) = [s for s in spans if s.name == "sim/restack"]
    assert first.t0 < reroute.t0 and first.attrs["rows"] > 0


def test_spans_land_on_the_profiler_host_plane(traced, tmp_path):
    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        with obs.span("obs_test/native"):
            jax.numpy.ones(8).block_until_ready()
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {
        e.name
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
    }
    assert "obs_test/native" in names


def test_import_pulls_in_no_jax():
    env = dict(os.environ, REPRO_TRACE="1")
    env["PYTHONPATH"] = str(ROOT / "src")
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, repro.obs as o\n"
         "with o.span('x'): pass\n"
         "print(sorted(m for m in sys.modules if m.split('.')[0] in "
         "('jax', 'numpy')))"],
        env=env, capture_output=True, text=True, cwd=str(ROOT),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "[]"


# --------------------------------------------------------------------------- #
# REPRO_TRACE registry discipline
# --------------------------------------------------------------------------- #


def test_trace_env_misvalue_raises_at_import():
    env = dict(os.environ, REPRO_TRACE="yes")  # not an int flag
    env["PYTHONPATH"] = str(ROOT / "src")
    proc = subprocess.run(
        [sys.executable, "-c", "import repro.obs"],
        env=env, capture_output=True, text=True, cwd=str(ROOT),
    )
    assert proc.returncode != 0
    assert "REPRO_TRACE" in proc.stderr


def test_trace_env_flag_seeds_default():
    env = dict(os.environ, REPRO_TRACE="1")
    env["PYTHONPATH"] = str(ROOT / "src")
    proc = subprocess.run(
        [sys.executable, "-c",
         "from repro.obs import trace_enabled; print(trace_enabled())"],
        env=env, capture_output=True, text=True, cwd=str(ROOT),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "True"
