"""Tests for the ``repro.sim`` subsystem (paper §3: Table 1, Fig 9).

Covers the four contracts of the new time-domain engine:

* waterfilling invariants — feasibility, the max-min bottleneck
  certificate (every flow is rate-limited by a saturated link on its path
  where it holds a maximal rate), and order invariance of the allocation;
* steady-state parity with the MW solver — persistent permutation traffic
  placed at the MW-optimal split waterfills to the MW concurrent alpha
  within 2% on RRG(256, 24, 18);
* ECMP determinism — golden integer-mixing hash values, cross-process
  stability under different PYTHONHASHSEEDs, and bit-identical ECMP path
  sets across APSP backends and enumeration shards (the
  ``tests/test_apsp_blocked.py`` parity discipline);
* engine plumbing — conservation accounting across policies, batched
  multi-seed scans, workload generators (churn/tenant scenarios riding
  ``update_path_system``), and ``REPRO_SIM_*`` import-time validation.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core import (
    build_path_system,
    fattree,
    jellyfish,
    mw_concurrent_flow,
    random_permutation_traffic,
)
from repro.core.routing import PathSystem, clear_routing_cache, set_apsp_backend
from repro.sim import (
    SimConfig,
    ecmp_group_sizes,
    ecmp_path_system,
    fct_percentiles,
    flow_hash,
    hash_select_rows,
    path_diversity,
    per_commodity_throughput,
    simulate,
    steady_poisson,
    steady_state_throughput,
    waterfill_rates,
)
from repro.sim.workloads import (
    diurnal_wave,
    elephant_mice,
    permutation_churn,
    run_tenant_churn,
    tenant_churn_segments,
)


def _small_ps(seed=0, n=60, ports=10, net=6, k=8):
    top = jellyfish(n, ports, net, seed=seed)
    comm = random_permutation_traffic(top, seed=seed + 1)
    return build_path_system(top, comm, k=k)


# --------------------------------------------------------------------------- #
# waterfilling invariants
# --------------------------------------------------------------------------- #


def _bottleneck_certificate(ps, rates, loads, nflow):
    """Max-min certificate: each flow's rate is limited by a saturated link
    on its path at which the flow's rate is maximal among crossing flows."""
    E2 = ps.n_slots
    rel = loads[:E2] * 1.0  # unit capacities throughout the tests
    slot_max = np.zeros(E2 + 1)
    for p in range(ps.n_paths):
        if nflow[p] <= 0:
            continue
        hops = ps.path_edges[p][ps.path_edges[p] < E2]
        np.maximum.at(slot_max, hops, rates[p])
    ok = np.ones(ps.n_paths, dtype=bool)
    for p in range(ps.n_paths):
        if nflow[p] <= 0:
            continue
        hops = ps.path_edges[p][ps.path_edges[p] < E2]
        ok[p] = bool(
            np.any((rel[hops] >= 1.0 - 1e-3)
                   & (rates[p] >= slot_max[hops] - 1e-4))
        )
    return ok


def test_waterfill_feasible_and_bottlenecked():
    ps = _small_ps()
    nflow = np.zeros((1, ps.n_paths), np.float32)
    nflow[0] = ps.demands[ps.path_owner]
    rates, loads = waterfill_rates([ps], n_flows_per_path=nflow, wf_iters=64)
    r, ld = rates[0, : ps.n_paths], loads[0, : ps.n_slots]
    # feasibility: no directed slot above its (unit) capacity
    assert ld.max() <= 1.0 + 1e-4
    assert (r[nflow[0] > 0] > 0).all()
    ok = _bottleneck_certificate(ps, r, ld, nflow[0])
    assert ok.all(), f"{(~ok).sum()} flows not bottlenecked at a saturated link"


def test_waterfill_order_invariant():
    ps = _small_ps(seed=3)
    rng = np.random.default_rng(0)
    perm = rng.permutation(ps.n_paths)
    shuffled = PathSystem(
        n_edges=ps.n_edges,
        path_edges=ps.path_edges[perm],
        path_len=ps.path_len[perm],
        path_owner=ps.path_owner[perm],
        demands=ps.demands,
        capacities=ps.capacities,
        n_commodities=ps.n_commodities,
        src=ps.src,
        dst=ps.dst,
        unrouted=ps.unrouted,
    )
    nf = ps.demands[ps.path_owner].astype(np.float32)
    r1, _ = waterfill_rates([ps], n_flows_per_path=nf[None, :], wf_iters=64)
    r2, _ = waterfill_rates(
        [shuffled], n_flows_per_path=nf[perm][None, :], wf_iters=64
    )
    np.testing.assert_allclose(
        r1[0, : ps.n_paths][perm], r2[0, : ps.n_paths], rtol=1e-5, atol=1e-6
    )


def test_waterfill_batch_matches_single():
    a, b = _small_ps(seed=1), _small_ps(seed=2, n=40, ports=10, net=6)
    ra, _ = waterfill_rates([a], wf_iters=32)
    rb, _ = waterfill_rates([b], wf_iters=32)
    rab, _ = waterfill_rates([a, b], wf_iters=32)
    np.testing.assert_allclose(
        rab[0, : a.n_paths], ra[0, : a.n_paths], rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        rab[1, : b.n_paths], rb[0, : b.n_paths], rtol=1e-5, atol=1e-6
    )


@pytest.mark.slow
def test_steady_state_matches_mw_alpha():
    """Persistent permutation traffic at the MW-optimal split waterfills to
    the MW concurrent alpha within 2% on RRG(256, 24, 18) — the sim's
    capacity accounting and the MW loads model agree end to end."""
    top = jellyfish(256, 24, 18, seed=0)
    comm = random_permutation_traffic(top, seed=1)
    ps = build_path_system(top, comm, k=8, max_slack=3)
    mw = mw_concurrent_flow(ps, iters=400)
    owner = ps.path_owner
    tot = np.bincount(owner, weights=mw.rates, minlength=ps.n_commodities)
    split = mw.rates / np.maximum(tot[owner], 1e-12)
    nflow = (ps.demands[owner] * split).astype(np.float32)[None, :]
    rates, loads = waterfill_rates([ps], n_flows_per_path=nflow, wf_iters=32)
    delivered = np.bincount(
        owner,
        weights=nflow[0] * rates[0, : ps.n_paths],
        minlength=ps.n_commodities,
    )
    norm_min = float((delivered / ps.demands).min())
    assert loads.max() <= 1.0 + 1e-4
    assert abs(norm_min - mw.alpha) <= 0.02 * mw.alpha, (
        f"sim steady-state min normalized throughput {norm_min:.4f} vs "
        f"mw alpha {mw.alpha:.4f}"
    )


def test_loads_fn_matches_fused_backends():
    """The loads-only closure (sim waterfilling) equals the fused
    congestion closure's loads half — BIT-exactly on the order-preserving
    backends, to float tolerance on dense."""
    import jax.numpy as jnp

    from repro.core.flow import (
        PathSystemBatch,
        make_congestion_fn_batch,
        make_loads_fn_batch,
    )

    batch = PathSystemBatch.from_systems(
        [_small_ps(seed=1), _small_ps(seed=2, n=40, ports=10, net=6)]
    )
    B, S = batch.n_batch, batch.s_max
    pe = jnp.asarray(batch.path_edges)
    tab = jnp.asarray(batch.slot_gather)
    rng = np.random.default_rng(0)
    rates = jnp.asarray(rng.random((B, batch.p_max)).astype(np.float32))
    zeros = jnp.zeros((B, S), jnp.float32)
    for be in ("gather", "scatter", "dense"):
        fused = make_congestion_fn_batch(pe, S, be, tab)
        loads_fn = make_loads_fn_batch(pe, S, be, tab)
        want = np.asarray(fused(rates, zeros)[0])
        got = np.asarray(loads_fn(rates))
        if be == "dense":
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        else:
            np.testing.assert_array_equal(got, want)


# --------------------------------------------------------------------------- #
# ECMP: hash determinism + path-set parity across APSP backends
# --------------------------------------------------------------------------- #

_HASH_SRC = np.array([0, 3, 17, 250, 511], dtype=np.uint32)
_HASH_DST = np.array([1, 7, 42, 13, 509], dtype=np.uint32)
_HASH_FID = np.array([0, 1, 2**20, 12345, 4294967295], dtype=np.uint32)
#: Golden values: any change silently reshuffles every ECMP flow placement.
_HASH_GOLDEN_5EED = [2060987080, 45655268, 3184681298, 105157940, 3795607632]
_HASH_GOLDEN_0 = [208060452, 2317150453, 3607758292, 2622168110, 44152540]


def test_flow_hash_golden_values():
    got = flow_hash(_HASH_SRC, _HASH_DST, _HASH_FID, 0x5EED)
    assert got.dtype == np.uint32
    assert got.tolist() == _HASH_GOLDEN_5EED
    assert flow_hash(_HASH_SRC, _HASH_DST, _HASH_FID, 0).tolist() == (
        _HASH_GOLDEN_0
    )


def test_flow_hash_jax_matches_numpy():
    import jax
    import jax.numpy as jnp

    args = (jnp.asarray(_HASH_SRC), jnp.asarray(_HASH_DST),
            jnp.asarray(_HASH_FID))
    eager = np.asarray(flow_hash(*args, 0x5EED))
    jitted = np.asarray(
        jax.jit(lambda a, b, c: flow_hash(a, b, c, 0x5EED))(*args)
    )
    assert eager.tolist() == _HASH_GOLDEN_5EED
    assert jitted.tolist() == _HASH_GOLDEN_5EED


def test_flow_hash_stable_across_processes():
    """The hash must not depend on process state (PYTHONHASHSEED et al.)."""
    import os
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    prog = (
        "import numpy as np\n"
        "from repro.sim import flow_hash\n"
        "print(flow_hash(np.uint32(17), np.uint32(42), np.uint32(7), "
        "0x5EED))\n"
    )
    outs = set()
    for hash_seed in ("0", "42"):
        env = dict(os.environ, PYTHONHASHSEED=hash_seed)
        env["PYTHONPATH"] = str(root / "src")
        proc = subprocess.run(
            [sys.executable, "-c", prog], env=env, capture_output=True,
            text=True, cwd=str(root),
        )
        assert proc.returncode == 0, proc.stderr
        outs.add(proc.stdout.strip())
    assert len(outs) == 1
    expected = int(flow_hash(np.uint32(17), np.uint32(42), np.uint32(7),
                             0x5EED))
    assert outs.pop() == str(expected)


def test_ecmp_sets_identical_across_apsp_backends():
    """ECMP path sets are a pure function of the graph — bit-identical
    across APSP backends (dense / blocked / minplus_blocked on CPU)."""
    top = jellyfish(72, 12, 8, seed=5)
    comm = random_permutation_traffic(top, seed=6)
    results = {}
    for be in ("dense", "blocked", "minplus_blocked"):
        prev = set_apsp_backend(be)
        clear_routing_cache()
        try:
            results[be] = ecmp_path_system(top, comm, n_ways=64)
        finally:
            set_apsp_backend(prev)
    clear_routing_cache()
    base = results["dense"]
    for be in ("blocked", "minplus_blocked"):
        got = results[be]
        assert np.array_equal(base.path_edges, got.path_edges), be
        assert np.array_equal(base.path_owner, got.path_owner), be
        assert np.array_equal(base.path_len, got.path_len), be


def test_ecmp_sets_identical_across_shards(monkeypatch):
    """Tiny frontier tiles force many dst shards; path sets must not move."""
    from repro.core import routing

    top = jellyfish(72, 12, 8, seed=7)
    comm = random_permutation_traffic(top, seed=8)
    clear_routing_cache()
    base = ecmp_path_system(top, comm, n_ways=64, cache=False)
    monkeypatch.setattr(routing, "_FRONTIER_TILE_BYTES", 1 << 12)
    clear_routing_cache()
    sharded = ecmp_path_system(top, comm, n_ways=64, cache=False)
    assert np.array_equal(base.path_edges, sharded.path_edges)
    assert np.array_equal(base.path_owner, sharded.path_owner)


def test_ecmp_groups_on_fattree_analytic():
    k = 6
    ft = fattree(k)
    comm = random_permutation_traffic(ft, seed=0)
    eps = ecmp_path_system(ft, comm, n_ways=(k // 2) ** 2)
    groups = ecmp_group_sizes(eps)
    kept = ~eps.unrouted
    src, dst = eps.src[kept], eps.dst[kept]
    inter = (src // k) != (dst // k)
    assert (groups[inter] == (k // 2) ** 2).all()
    assert (groups[~inter] == k // 2).all()
    # every ECMP path is shortest: lengths match the pod structure
    assert (eps.path_len[np.isin(eps.path_owner, np.flatnonzero(inter))]
            == 4).all()


def test_hash_select_rows_deterministic_and_in_group():
    ps = ecmp_path_system(
        jellyfish(48, 10, 6, seed=2).copy(),
        random_permutation_traffic(jellyfish(48, 10, 6, seed=2), seed=3),
        n_ways=16,
    )
    rows = hash_select_rows(ps, salt=1)
    again = hash_select_rows(ps, salt=1)
    assert np.array_equal(rows, again)
    # every selected row belongs to the flow's own commodity
    d = np.maximum(np.round(ps.demands).astype(int), 1)
    ci = np.repeat(np.arange(ps.n_commodities), d)
    assert np.array_equal(ps.path_owner[rows], ci)
    # a different salt must actually reshuffle something
    assert not np.array_equal(rows, hash_select_rows(ps, salt=2))


def test_path_diversity_counts():
    ps = _small_ps(seed=9)
    div = path_diversity(ps)
    assert div["links_total"] == ps.n_edges
    assert 0 < div["links_covered"] <= ps.n_edges
    assert div["paths_per_commodity"].sum() == ps.n_paths
    # ECMP on the same instance covers no more links than 8-shortest
    top = jellyfish(60, 10, 6, seed=9)
    comm = random_permutation_traffic(top, seed=10)
    eps = ecmp_path_system(top, comm, n_ways=64)
    assert path_diversity(eps)["links_covered"] <= div["links_covered"]


# --------------------------------------------------------------------------- #
# engine: conservation, policies, batching, workloads
# --------------------------------------------------------------------------- #


def _tiny_systems(n_seeds=2):
    out = []
    for s in range(n_seeds):
        top = jellyfish(40, 10, 6, seed=s)
        comm = random_permutation_traffic(top, seed=s + 10)
        out.append(build_path_system(top, comm, k=8))
    return out


@pytest.mark.parametrize("policy", ["ecmp", "ksp_lc", "mptcp"])
def test_simulate_conservation(policy):
    systems = _tiny_systems()
    wl = steady_poisson(40, rate=5.0, size=12.0)
    cfg = SimConfig(max_flows=512, max_arrivals=8, wf_iters=8)
    res = simulate(systems, wl, policy=policy, config=cfg, seed=1)
    assert res.throughput.shape == (40, 2)
    assert (res.throughput >= -1e-6).all()
    # every admitted flow either completed or is still in flight
    in_flight = res.active[-1]
    assert ((res.fct_count + in_flight) == res.admitted).all()
    # volume conservation: admitted bytes = delivered bytes + bytes still
    # in flight; per-commodity offered accounting agrees with the totals
    total = res.throughput.sum(axis=0)
    offered = res.comm_offered.sum(axis=1)
    assert (total <= offered + 1e-3).all()
    np.testing.assert_allclose(
        res.comm_delivered.sum(axis=1), total, rtol=1e-5, atol=1e-3
    )
    # (mptcp splits a flow across subflows, conserving total size, so the
    # per-subflow admitted count is not directly comparable to size*count)
    if policy != "mptcp":
        np.testing.assert_allclose(offered, res.admitted * 12.0, rtol=1e-5)
    # FCT percentiles well-defined once flows completed
    if (res.fct_count > 0).all():
        p = fct_percentiles(res)
        assert np.isfinite(p).all()
        assert (p[:, 0] <= p[:, -1] + 1e-9).all()
    # per-commodity accounting adds up to the timeseries total
    np.testing.assert_allclose(
        per_commodity_throughput(res).sum(axis=1) * res.n_steps * res.dt,
        res.throughput.sum(axis=0),
        rtol=1e-4,
    )


def test_simulate_deterministic():
    systems = _tiny_systems(1)
    wl = steady_poisson(24, rate=4.0, size=10.0)
    cfg = SimConfig(max_flows=256, max_arrivals=8, wf_iters=6)
    a = simulate(systems, wl, policy="ecmp", config=cfg, seed=7)
    b = simulate(systems, wl, policy="ecmp", config=cfg, seed=7)
    np.testing.assert_array_equal(a.throughput, b.throughput)
    np.testing.assert_array_equal(a.fct_hist, b.fct_hist)
    c = simulate(systems, wl, policy="ecmp", config=cfg, seed=8)
    assert not np.array_equal(a.throughput, c.throughput)


def test_simulate_one_scan_many_seeds():
    """The acceptance shape: B instances advance in ONE scan, per-instance
    telemetry stays separated."""
    systems = _tiny_systems(4)
    wl = steady_poisson(32, rate=6.0, size=10.0)
    cfg = SimConfig(max_flows=512, max_arrivals=8, wf_iters=6)
    res = simulate(systems, wl, policy="ksp_lc", config=cfg, seed=0)
    assert res.throughput.shape == (32, 4)
    thr = steady_state_throughput(res)
    assert (thr > 0).all()
    util = res.util_sum / res.n_steps
    assert (util[res.slot_valid] <= 1.0 + 1e-4).all()


def test_workload_generators_validate():
    with pytest.raises(ValueError):
        diurnal_wave(10, 1.0, amplitude=1.5)
    with pytest.raises(ValueError):
        elephant_mice(10, 1.0, p_elephant=2.0)
    wl = diurnal_wave(50, 4.0, amplitude=0.5, period=25)
    assert wl.n_steps == 50 and wl.rate.min() >= 2.0 - 1e-5
    em = elephant_mice(10, 1.0, p_elephant=0.1)
    assert em.size_elephant > em.size_mice


def test_permutation_churn_epochs():
    tops = [jellyfish(40, 10, 6, seed=s) for s in (0, 1)]
    batch, wl = permutation_churn(
        tops, n_epochs=3, steps_per_epoch=8, rate=4.0, seed=2
    )
    assert wl.demand_epochs.shape[0] == 3
    assert wl.n_steps == 24
    assert wl.epoch_of_step.max() == 2
    # each epoch keeps demand only on a subset of the union commodities
    live = (wl.demand_epochs > 0).sum(axis=2)
    assert (live > 0).all()
    res = simulate(
        batch, wl, policy="ecmp",
        config=SimConfig(max_flows=256, max_arrivals=8, wf_iters=6), seed=0,
    )
    assert res.throughput.shape == (24, 2)
    assert res.admitted.sum() > 0


def test_tenant_churn_rides_delta_routing():
    tops = [jellyfish(24, 10, 6, seed=s) for s in (0, 1)]
    segments = tenant_churn_segments(tops, n_events=2, grow=1, seed=3)
    assert len(segments) == 3
    # arrival event grew every instance by one switch
    assert all(
        b.n_commodities >= a.n_commodities
        for a, b in zip(segments[0]["systems"], segments[1]["systems"])
    )
    # the delta-routed system carries a row_map (update_path_system ran)
    assert segments[1]["systems"][0].row_map is not None
    # departure event zeroed a slice of demand weights
    assert segments[2]["demands"][0].min() == 0.0
    results = run_tenant_churn(
        segments, steps_per_segment=10, rate=3.0,
        config=SimConfig(max_flows=256, max_arrivals=8, wf_iters=6),
    )
    assert len(results) == 3
    assert all(r.throughput.shape[0] == 10 for r in results)


# --------------------------------------------------------------------------- #
# REPRO_SIM_* env validation (import-time, subprocess)
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("var", ["REPRO_SIM_MAX_STEPS", "REPRO_SIM_MAX_BATCH"])
def test_sim_env_validated_at_import(var):
    import os
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    for bad in ("ten", "0", "-3"):
        env = dict(os.environ, **{var: bad})
        env["PYTHONPATH"] = str(root / "src")
        proc = subprocess.run(
            [sys.executable, "-c", "import repro.sim"],
            env=env, capture_output=True, text=True, cwd=str(root),
        )
        assert proc.returncode != 0, (var, bad)
        assert var in proc.stderr, (var, bad)


def test_sim_caps_enforced(monkeypatch):
    from repro.sim import engine

    systems = _tiny_systems(1)
    monkeypatch.setattr(engine, "SIM_MAX_STEPS", 8)
    with pytest.raises(ValueError, match="REPRO_SIM_MAX_STEPS"):
        engine.simulate(systems, steady_poisson(9, 1.0))
    monkeypatch.setattr(engine, "SIM_MAX_STEPS", 200_000)
    monkeypatch.setattr(engine, "SIM_MAX_BATCH", 1)
    with pytest.raises(ValueError, match="REPRO_SIM_MAX_BATCH"):
        engine.simulate(_tiny_systems(2), steady_poisson(4, 1.0))


def test_simulate_rejects_unknown_policy():
    with pytest.raises(ValueError, match="policy"):
        simulate(_tiny_systems(1), steady_poisson(4, 1.0), policy="spray")
