"""The Fig 1c capacity search (``repro.core.capacity``) against plain references.

* The search, sequential and in speculative waves, against a linear scan
  over the server count with the exact LP probe, at fat-tree k = 4 and
  k = 6 equipment.
* ``probe_wave`` against the float64 windowed MW of
  ``chipbench/ref_capacity.py`` (no kernels, no batching): the same
  verdicts and iteration counts, alphas within float32 rounding.
* ``same_equipment_jellyfish``: every switch uses at most its ports, and at
  most one port is left unmatched.
* Tracing: the wave's span and counters, one ``mw/sync`` per window of an
  adaptive batched solve, ``instances`` on every ``mw/window_batch``, and
  results bit-identical with tracing on.
"""

from __future__ import annotations

import numpy as np
import pytest

from chipbench import ref_capacity
from repro import obs
from repro.core import (
    build_path_system,
    fattree_equipment,
    lp_concurrent_flow,
    max_servers_at_full_capacity,
    mw_concurrent_flow_batch,
    probe_wave,
    random_permutation_traffic,
    same_equipment_jellyfish,
    spread_servers,
)


def _lp_ok(n, ports, m, n_matrices, tol=1e-6):
    top = same_equipment_jellyfish(n, ports, m, seed=0)
    for s in range(n_matrices):
        ps = build_path_system(top, random_permutation_traffic(top, seed=s),
                               k=8, max_slack=3)
        if lp_concurrent_flow(ps).alpha < 1.0 - tol:
            return False
    return True


def _lp_scan(n, ports, lo, hi, n_matrices):
    """The plain search: count up from ``lo`` until a probe rejects."""
    m = lo
    while m < hi and _lp_ok(n, ports, m + 1, n_matrices):
        m += 1
    return m


@pytest.mark.parametrize("k", [4, 6])
def test_search_equals_lp_linear_scan(k):
    eq = fattree_equipment(k)
    n, ports = eq["switches"], eq["ports_per_switch"]
    lo, hi = eq["servers"] // 2, 2 * eq["servers"]
    want = _lp_scan(n, ports, lo, hi, n_matrices=3)
    assert lo < want < hi
    for levels in (1, 2):
        got = max_servers_at_full_capacity(n, ports, lo, hi, seeds=(0,),
                                           wave_levels=levels)
        assert got == want, (levels, got, want)


def _wave_inputs():
    """Three candidates on k = 8 fat-tree equipment (80 switches of 8
    ports), one fabric each; the first routes two permutations."""
    groups, tables = [], []
    for c, m in enumerate((132, 140, 150)):
        top = same_equipment_jellyfish(80, 8, m, seed=c)
        g = [build_path_system(top, random_permutation_traffic(top, seed=s),
                               k=8, max_slack=3)
             for s in range(2 if c == 0 else 1)]
        groups.append(g)
        tables.append([(ps.path_edges, ps.path_len, ps.path_owner,
                        ps.demands, 2 * top.n_edges) for ps in g])
    return groups, tables


def test_probe_wave_matches_float64_reference():
    groups, tables = _wave_inputs()
    verdicts, results = probe_wave(groups, iters=500, mw_backend="scatter")
    want_v, want = ref_capacity.wave(tables, 500, 1e-6)
    assert verdicts == want_v == [True, True, False]
    for got, ref in zip(results, want):
        assert [r.iters for r in got] == [t for _, _, t in ref]
        # float32 against float64: the anneal amplifies rounding over the
        # iterations (+-0.5% an instance at 400 on jf2048, PERF.md); here
        # the largest gap is 4.2e-4, on the instance that runs all 500
        np.testing.assert_allclose([r.alpha for r in got],
                                   [a for a, _, _ in ref], rtol=1e-2)
    # early stops at different windows, and one budget-bound instance
    assert [r.iters for g in results for r in g] == [150, 150, 350, 500]


@pytest.mark.parametrize("k,scale", [(4, 1.0), (6, 1.3), (8, 1.1),
                                     (10, 1.25), (12, 0.9), (14, 1.37)])
def test_same_equipment_degrees(k, scale):
    eq = fattree_equipment(k)
    n, ports = eq["switches"], eq["ports_per_switch"]
    m = int(eq["servers"] * scale)
    top = same_equipment_jellyfish(n, ports, m, seed=k)
    servers = spread_servers(m, n)
    assert servers.sum() == m and servers.max() - servers.min() <= 1
    assert np.all(servers[:-1] >= servers[1:])  # extras on the lowest ids
    deg = np.bincount(top.edges.ravel(), minlength=n)
    assert np.all(deg + servers <= ports)
    assert int((ports - servers - deg).sum()) <= 1
    assert len({tuple(e) for e in top.edges.tolist()}) == top.n_edges


def _fields(res):
    return [(r.alpha, np.asarray(r.rates).copy(), r.max_load, r.iters)
            for r in res]


def test_wave_traced_spans_and_bit_identical():
    groups, _ = _wave_inputs()
    base = probe_wave(groups, iters=500, mw_backend="scatter")
    prev = obs.set_trace(True)
    before = obs.snapshot()
    try:
        obs.reset_trace()
        traced = probe_wave(groups, iters=500, mw_backend="scatter")
        spans = obs.get_spans()
        after = obs.snapshot()
    finally:
        obs.set_trace(prev)
        obs.reset_trace()
    assert base[0] == traced[0]
    for b, t in zip(base[1], traced[1]):
        for fb, ft in zip(_fields(b), _fields(t)):
            assert fb[0] == ft[0] and np.array_equal(fb[1], ft[1])
            assert fb[2:] == ft[2:]
    (wave,) = [s for s in spans if s.name == "capacity/wave"]
    assert wave.attrs == {"candidates": 3, "instances": 4, "accepted": 2}
    for name, n in (("capacity/accepted", 2), ("capacity/rejected", 1)):
        assert after[name] - before.get(name, 0) == n
    windows = [s for s in spans if s.name == "mw/window_batch"]
    syncs = [s for s in spans if s.name == "mw/sync"]
    # 500 iterations in windows of 50: the budget-bound instance runs all
    assert len(windows) == len(syncs) == 10
    assert [s.attrs["active"] for s in windows] == [4, 4, 4, 2, 2, 2, 2, 1,
                                                    1, 1]
    # frozen instances leave the computed batch at the window edge
    assert [s.attrs["instances"] for s in windows] == [
        s.attrs["active"] for s in windows]
    assert [(s.attrs["t0"], s.attrs["before"], s.attrs["after"])
            for s in spans if s.name == "mw/compact"] == [(150, 4, 2),
                                                          (350, 2, 1)]


def test_fixed_budget_window_carries_instances():
    groups, _ = _wave_inputs()
    systems = [ps for g in groups for ps in g]
    prev = obs.set_trace(True)
    try:
        obs.reset_trace()
        mw_concurrent_flow_batch(systems[:3], iters=40)
        spans = obs.get_spans()
    finally:
        obs.set_trace(prev)
        obs.reset_trace()
    (win,) = [s for s in spans if s.name == "mw/window_batch"]
    assert win.attrs["instances"] == 4 and win.attrs["active"] == 3
    assert not [s for s in spans if s.name == "mw/sync"]
