"""Tests of the benchmark's yardstick: trace reduction, peaks and kernel
costs, generators and plain references (CPU, small sizes)."""

from __future__ import annotations

import pathlib
import sys

import numpy as np
import pytest

from chipbench import gen, ref, roofline, xtrace

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def small():
    """Three units of a jitted 1024^2 matmul and the fused congestion kernel
    at (2, 512, 1024), traced on one TPU v5 lite (``record_trace.py``)."""
    return xtrace.Trace.from_file(str(DATA / "small.xplane.pb"))


def test_trace_window_and_busy(small):
    assert small.window_s == pytest.approx(5428830e-9)
    assert len(small.devices) == 1
    # union of the device's operation intervals inside the window
    assert small.busy_s() == pytest.approx(114326e-9)
    assert 0 < small.busy_s() < small.window_s


def test_trace_modules_ops_and_shapes(small):
    s, runs = small.module_s("congestion_pallas")
    assert runs == 3 and s == pytest.approx(79358e-9)
    s, calls = small.op_s("congestion_pallas_batch")
    assert calls == 3 and s == pytest.approx(73886e-9)
    assert small.op_shapes("congestion_pallas_batch") == [(2, 512, 1024)]
    assert small.op_s("no such kernel") == (0.0, 0)
    top = small.top_ops(3)
    assert top[0][0] == "_congestion_pallas_batch.1"
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)
    assert [m for m, _ in small.top_modules(2)] == ["jit_congestion_pallas",
                                                    "jit__lambda"]


def test_trace_idle_gaps_name_host_activity(small):
    gaps = small.idle_gaps(4)
    assert len(gaps) == 4
    assert [g for _, g in gaps] == sorted((g for _, g in gaps), reverse=True)
    idle = small.window_s - small.busy_s()
    assert sum(g for _, g in small.idle_gaps(1000)) == pytest.approx(idle)
    assert all(name and name != xtrace.WINDOW for name, _ in gaps)


def test_host_spans_name_the_gaps_they_cover():
    tr = xtrace.Trace.from_file(str(DATA / "small.xplane.pb"))
    a, b = _longest_gap(tr)
    # a host-clock span (window opened at host time 10.0 s) at the middle of
    # the longest gap, shorter than any host event there, names that gap
    mid = 10.0 + ((a + b) / 2 - tr.t0) * 1e-9
    tr.add_host_spans([("pipeline/stall", mid - 1e-9, mid + 1e-9)], 10.0)
    label, seconds = tr.idle_gaps(1)[0]
    assert label == "pipeline/stall"
    assert seconds == pytest.approx((b - a) * 1e-9)


def _longest_gap(tr):
    busy = tr._busy(tr.devices[0])
    edges = [tr.t0] + [x for iv in busy for x in iv] + [tr.t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)]
    return max(gaps, key=lambda g: g[1] - g[0])


def test_union_and_clip():
    iv = xtrace._union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert iv == [[0, 3], [5, 9]]
    assert xtrace._clip([(0, 4), (6, 8)], 2, 7) == [(2, 4), (6, 7)]


def test_peaks_by_device_kind():
    pk = roofline.peaks("TPU v5 lite")
    assert pk["hbm_bytes_per_s"] == 819e9 and pk["flops_per_s"] == 197e12
    assert "cloud.google.com" in pk["source"]
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_congestion_cost_at_a_small_shape():
    flops, nbytes = roofline.congestion_cost(2, 4, 8)
    # loads B^T r and costs B w: 2 * P * S operations each, per member
    assert flops == 2 * 2 * (2 * 4 * 8)
    # incidence once, rates and costs (P), prices and loads (S), float32
    assert nbytes == 4 * (2 * 4 * 8 + 2 * 2 * 4 + 2 * 2 * 8)
    pk = {"flops_per_s": 100.0, "hbm_bytes_per_s": 1000.0}
    assert roofline.least_time(flops, nbytes, pk) == pytest.approx(
        max(flops / 100.0, nbytes / 1000.0))


@pytest.mark.parametrize("n,r", [(40, 6), (512, 18), (101, 4)])
def test_rrg_is_simple_regular_and_seeded(n, r):
    e = gen.rrg_edges(n, r, gen.rng_for(2**40 + 3, "fabric", 0))
    assert np.all(e[:, 0] < e[:, 1])
    assert len(np.unique(e[:, 0] * n + e[:, 1])) == len(e) == n * r // 2
    assert np.all(np.bincount(e.ravel(), minlength=n) == r)
    again = gen.rrg_edges(n, r, gen.rng_for(2**40 + 3, "fabric", 0))
    assert np.array_equal(e, again)
    other = gen.rrg_edges(n, r, gen.rng_for(2**40 + 3, "fabric", 1))
    assert not np.array_equal(e, other)


def test_permutation_pairs_conserve_servers():
    src, dst, dem, ns = gen.permutation_pairs(30, 4, gen.rng_for(7))
    assert ns == 120 and np.all(src != dst)
    assert dem.sum() <= 120 and np.all(dem >= 1)
    out = np.bincount(src, weights=dem, minlength=30)
    assert np.all(out <= 4)


def test_k_shortest_on_a_ring():
    n = 6
    e = np.array([[i, (i + 1) % n] for i in range(n)])
    e = np.sort(e, axis=1)
    dist = ref.bfs_hops(n, e)
    nb = ref.neighbour_lists(n, e)
    assert ref.k_shortest(nb, dist, 0, 3, 8, 3) == [[0, 1, 2, 3], [0, 5, 4, 3]]
    assert ref.k_shortest(nb, dist, 3, 0, 8, 3) == [[3, 2, 1, 0], [3, 4, 5, 0]]
    assert ref.k_shortest(nb, dist, 0, 3, 8, 3, reverse_ties=True) == [
        [0, 5, 4, 3], [0, 1, 2, 3]]
    assert ref.k_shortest(nb, dist, 0, 1, 8, 3) == [[0, 1]]
    assert ref.k_shortest(nb, dist, 0, 1, 8, 4) == [[0, 1], [0, 5, 4, 3, 2, 1]]


def test_certify_reads_what_an_answer_says():
    # two commodities on disjoint single-hop paths over a 2-edge fabric
    pe = np.array([[0], [1]])
    plen = np.array([1, 1])
    owner = np.array([0, 1])
    dem = np.array([2.0, 1.0])
    # alpha 0.5: each ships half its demand, the busier slot full
    assert ref.certify(pe, plen, owner, dem, 4, [1.0, 0.5], 0.5) == (0.0, 0.0)
    gap, over = ref.certify(pe, plen, owner, dem, 4, [1.1, 0.5], 0.5)
    assert over == pytest.approx(0.1) and gap > 0
    assert ref.certify(pe, plen, owner, dem, 4, [1.0], 0.5)[0] == np.inf


def test_mw_reference_reaches_the_optimum_of_a_split():
    # one commodity of demand 2 over two parallel unit links: alpha = 1
    pe = np.array([[0], [1]])
    a, r = ref.mw_solve(pe, np.array([1, 1]), np.array([0, 0]),
                        np.array([2.0]), 2, 200)
    assert a == pytest.approx(1.0, rel=1e-6)
    assert r.sum() == pytest.approx(2.0)
    a16, _ = ref.mw_solve(pe, np.array([1, 1]), np.array([0, 0]),
                          np.array([2.0]), 2, 200, "bf16")
    assert a16 == pytest.approx(1.0, rel=1e-2)


def test_metric_readers_by_name_up_to_the_first_dot():
    from chipbench import run

    for name in ("idle_share.mw", "idle_share.sim", "idle_share.route"):
        assert run.metric_reader(name).name == "idle_share.py"
    assert run.metric_reader("apsp_device_ms").name == "apsp_device_ms.py"


def test_program_bytes_counts_a_programs_temporaries():
    import jax
    import jax.numpy as jnp

    from chipbench import run

    mod = type(sys)("chipbench_probe_module")
    # a (1024, 1024) float32 outer product lives only inside the program
    mod.outer_sum = jax.jit(lambda x: (x[:, None] * x[None, :]).sum())
    sys.modules[mod.__name__] = mod
    try:
        with run.recording([f"{mod.__name__}.outer_sum"]) as calls:
            mod.outer_sum(jnp.ones(1024, jnp.float32))
            mod.outer_sum(jnp.ones(1024, jnp.float32))  # the same program
        assert len(calls) == 1
        assert run.program_bytes(calls) >= 1024 * 1024 * 4
    finally:
        del sys.modules[mod.__name__]
