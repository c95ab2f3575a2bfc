"""Batched MW solver + speculative bisection validation.

Parity contract: an instance's ``mw_concurrent_flow_batch`` result does
not depend on the batch it is solved in — a batch of one
(``mw_concurrent_flow``), other instances, or bucket padding — bit-exactly
on the scatter backend and on the gather backend (ordered fan-in sums
match the scatter association), including EXACT per-instance iteration
counts under the adaptive early-stop, whose frozen instances leave the
computed batch (and compile nothing after a warm-up).  Plus ragged/empty
batches, one routing under several demand vectors, the one congestion
backend decision, the jit-churn window padding, the speculative
bisection's sequential-equality guarantee, the REPRO_LP_PATH_LIMIT import
validation, and the MPTCP warm start.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import (
    PathSystemBatch,
    build_path_system,
    jellyfish,
    max_feasible,
    mw_concurrent_flow,
    mw_concurrent_flow_batch,
    random_permutation_traffic,
    speculative_max_feasible,
)
from repro.core.routing import PathSystem


def _systems(sizes, k=4, seed=3):
    out = []
    for i, n in enumerate(sizes):
        top = jellyfish(n, 10, 6, seed=i)
        out.append(
            build_path_system(top, random_permutation_traffic(top, seed=seed), k=k)
        )
    return out


def _empty_system():
    return PathSystem(
        n_edges=0,
        path_edges=np.zeros((0, 1), np.int32),
        path_len=np.zeros(0, np.int32),
        path_owner=np.zeros(0, np.int32),
        demands=np.zeros(0, np.float32),
        capacities=np.zeros(0, np.float32),
        n_commodities=0,
    )


# --------------------------------------------------------------------------- #
# batched-vs-sequential parity
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("backend", ["scatter", "gather", "dense"])
def test_batch_matches_sequential_fixed_budget(backend):
    systems = _systems((24, 40, 32))
    seq = [mw_concurrent_flow(ps, iters=120, backend="scatter") for ps in systems]
    bat = mw_concurrent_flow_batch(systems, iters=120, backend=backend)
    for s, b in zip(seq, bat):
        assert abs(s.alpha - b.alpha) <= 1e-5 * max(s.alpha, 1.0)
        assert s.iters == b.iters == 120
        # dense reassociates the incidence products (einsum), so its
        # trajectory drifts at float tolerance; scatter/gather are bit-exact
        tol = dict(rtol=5e-3, atol=1e-4) if backend == "dense" else dict(
            rtol=1e-6, atol=1e-7
        )
        np.testing.assert_allclose(s.rates, b.rates, **tol)


@pytest.mark.parametrize("backend", ["scatter", "gather"])
def test_batch_bit_exact_order_preserving_backends(backend):
    """scatter and gather reproduce the sequential accumulation order, so
    alpha agreement is BIT-level, not just tolerance-level."""
    systems = _systems((40, 60))
    seq = [mw_concurrent_flow(ps, iters=150, backend="scatter") for ps in systems]
    bat = mw_concurrent_flow_batch(systems, iters=150, backend=backend)
    for s, b in zip(seq, bat):
        assert s.alpha == b.alpha


def test_batch_adaptive_iteration_counts_agree_exactly():
    """Frozen-instance early-stop: every instance stops at the same window
    (same iteration count) its sequential adaptive solve would."""
    systems = _systems((24, 40, 60, 32))
    kw = dict(iters=300, early_stop=True, check_every=25, target_alpha=0.55)
    seq = [mw_concurrent_flow(ps, backend="scatter", **kw) for ps in systems]
    bat = mw_concurrent_flow_batch(systems, backend="gather", **kw)
    iters = sorted(b.iters for b in bat)
    assert iters[0] < iters[-1], "sizes chosen so freeze windows differ"
    for s, b in zip(seq, bat):
        assert s.iters == b.iters
        assert s.alpha == b.alpha


#: Adaptive solves whose instances freeze at different windows.  With 3
#: instances the batch is bucketed to 4 and the empty pad is dropped before
#: the first window; with 9 it is bucketed to 12, and the 7 left live after
#: the first freezes run at a batch of 8 topped up by one frozen instance.
_FREEZE_KW = dict(iters=300, early_stop=True, check_every=25,
                  target_alpha=0.55)
_FREEZE_SIZES = {
    "four": (24, 40, 60, 32),
    "three_and_pad": (24, 40, 60),
    "nine_with_filler": (24, 40, 60, 32, 48, 36, 28, 44, 52),
}


@pytest.mark.parametrize("case", sorted(_FREEZE_SIZES))
@pytest.mark.parametrize("backend", ["scatter", "gather"])
def test_compacted_batch_bit_exact_to_alone(backend, case):
    """Frozen instances leave the computed batch at a window edge: every
    instance's alpha, rates and iteration count equal its solve alone, bit
    for bit."""
    systems = _systems(_FREEZE_SIZES[case])
    bat = mw_concurrent_flow_batch(systems, backend=backend, **_FREEZE_KW)
    assert len({b.iters for b in bat}) >= 3, "freeze windows differ"
    for ps, b in zip(systems, bat):
        s = mw_concurrent_flow(ps, backend=backend, **_FREEZE_KW)
        assert (s.alpha, s.iters, s.max_load) == (b.alpha, b.iters,
                                                  b.max_load)
        np.testing.assert_array_equal(s.rates, b.rates)


@pytest.mark.parametrize("case, compactions", [
    ("three_and_pad", [(0, 4, 3), (75, 3, 2), (125, 2, 1)]),
    ("nine_with_filler", [(75, 12, 8), (225, 8, 4)]),
])
def test_compacted_windows_compute_the_live_instances(case, compactions):
    """Each window computes the live instances only (the live count, or
    that rounded up to a multiple of 4 above 4), and each re-stack is one
    ``mw/compact`` span and one ``mw/compactions`` count."""
    from repro import obs

    systems = _systems(_FREEZE_SIZES[case])
    prev = obs.set_trace(True)
    before = obs.snapshot().get("mw/compactions", 0)
    try:
        obs.reset_trace()
        mw_concurrent_flow_batch(systems, backend="scatter", **_FREEZE_KW)
        spans = sorted(obs.get_spans(), key=lambda s: s.t0)
        after = obs.snapshot()["mw/compactions"]
    finally:
        obs.set_trace(prev)
        obs.reset_trace()
    windows = [s for s in spans if s.name == "mw/window_batch"]
    live = [s.attrs["active"] for s in windows]
    assert [s.attrs["instances"] for s in windows] == [
        n if n <= 4 else -(-n // 4) * 4 for n in live]
    assert [(s.attrs["t0"], s.attrs["before"], s.attrs["after"])
            for s in spans if s.name == "mw/compact"] == compactions
    assert after - before == len(compactions)


def test_compacted_solve_compiles_nothing_after_warm_up():
    """A warm-up solve whose instances all freeze at the first check
    compiles every window program a later solve of the same envelope can
    compact to: that solve, whose instances freeze at three different
    windows, compiles nothing."""
    import jax

    from repro.core import flow

    systems = _systems(_FREEZE_SIZES["three_and_pad"])
    jax.clear_caches()  # a fresh process: nothing compiled yet
    flow._RUNGS_COMPILED.clear()
    compiles = []

    def on(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        mw_concurrent_flow_batch(systems, iters=300, check_every=25,
                                 backend="scatter", target_alpha=0.0)
        warm = len(compiles)
        res = mw_concurrent_flow_batch(systems, backend="scatter",
                                       **_FREEZE_KW)
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert warm > 0
    assert sorted({r.iters for r in res}) == [75, 125, 300]
    assert len(compiles) == warm


def test_batch_plateau_early_stop_agrees():
    systems = _systems((24, 40))
    kw = dict(iters=400, early_stop=True, check_every=50, rel_tol=5e-3,
              patience=1)
    seq = [mw_concurrent_flow(ps, backend="scatter", **kw) for ps in systems]
    bat = mw_concurrent_flow_batch(systems, backend="gather", **kw)
    for s, b in zip(seq, bat):
        assert s.iters == b.iters
        assert abs(s.alpha - b.alpha) <= 1e-6


def test_batch_warm_start_matches_sequential():
    from repro.core import fail_links, update_path_system

    tops = [jellyfish(n, 10, 6, seed=7 + i) for i, n in enumerate((40, 50))]
    comms = [random_permutation_traffic(t, seed=1) for t in tops]
    systems = [build_path_system(t, c, k=4) for t, c in zip(tops, comms)]
    warms = [mw_concurrent_flow(ps, iters=80) for ps in systems]
    failed = [fail_links(t, n_links=3, seed=9) for t in tops]
    deltas = [
        update_path_system(ps, t, f, c)
        for ps, t, f, c in zip(systems, tops, failed, comms)
    ]
    seq = [
        mw_concurrent_flow(ps, iters=60, backend="scatter", warm=w)
        for ps, w in zip(deltas, warms)
    ]
    bat = mw_concurrent_flow_batch(deltas, iters=60, backend="gather",
                                   warm=warms)
    for s, b in zip(seq, bat):
        assert s.alpha == b.alpha


# --------------------------------------------------------------------------- #
# ragged batches, padding edge cases
# --------------------------------------------------------------------------- #


def test_batch_with_empty_instance():
    systems = _systems((24, 40))
    mixed = [systems[0], _empty_system(), systems[1]]
    bat = mw_concurrent_flow_batch(mixed, iters=80)
    assert bat[1].alpha == 0.0 and len(bat[1].rates) == 0 and bat[1].iters == 0
    for ps, b in zip((systems[0], systems[1]), (bat[0], bat[2])):
        s = mw_concurrent_flow(ps, iters=80, backend="scatter")
        assert abs(s.alpha - b.alpha) <= 1e-6
        assert len(b.rates) == ps.n_paths


def test_batch_all_empty():
    out = mw_concurrent_flow_batch([_empty_system(), _empty_system()], iters=50)
    assert all(r.alpha == 0.0 and r.iters == 0 for r in out)


def test_batch_of_one():
    (ps,) = _systems((40,))
    s = mw_concurrent_flow(ps, iters=100, backend="scatter")
    (b,) = mw_concurrent_flow_batch([ps], iters=100)
    assert s.alpha == b.alpha
    np.testing.assert_allclose(s.rates, b.rates, rtol=1e-6, atol=1e-7)


def test_batch_result_independent_of_composition():
    """Padding envelope (who else is in the batch) must not change an
    instance's result — the wave driver relies on this."""
    systems = _systems((24, 60, 32))
    alone = mw_concurrent_flow_batch([systems[0]], iters=120)[0]
    grouped = mw_concurrent_flow_batch(systems, iters=120)[0]
    assert alone.alpha == grouped.alpha


def test_pathsystembatch_gather_tables_cover_real_hops():
    systems = _systems((24, 40))
    batch = PathSystemBatch.from_systems(systems)
    assert batch.slot_gather is not None
    B, S, D = batch.slot_gather.shape
    P, L = batch.path_edges.shape[1:]
    for i, ps in enumerate(systems):
        real = int((batch.slot_gather[i] < P * L).sum())
        hops = int(ps.path_len.sum())
        assert real == hops  # every real hop appears exactly once


# --------------------------------------------------------------------------- #
# split normalisation over contiguous commodity runs
# --------------------------------------------------------------------------- #


def _owner_rows(runs, tail, dummy):
    """Canonical owner row: commodity k repeated runs[k] times, then a
    padding tail of the dummy commodity."""
    return np.concatenate([np.repeat(np.arange(len(runs)), runs),
                           np.full(tail, dummy)]).astype(np.int32)


def _seg_case(name):
    rng = np.random.default_rng(11)
    if name == "ragged":  # runs of 1..8 rows, a 13-row dummy tail
        runs = rng.permutation(np.repeat(np.arange(1, 9), 3))
        owner = _owner_rows(runs, 13, len(runs))[None]
        return owner, len(runs) + 1
    if name == "mixed_seg_max":  # instances whose longest runs differ
        K, P = 9, 80
        rows = []
        for top in (1, 5, 8):
            runs = rng.integers(1, top + 1, size=K)
            runs[0] = top
            rows.append(_owner_rows(runs, P - runs.sum(), K))
        return np.stack(rows), K + 1
    if name == "single_row":  # one (P,) owner row, no dummy
        runs = rng.integers(1, 8, size=10)
        return _owner_rows(runs, 0, 0), len(runs)
    raise ValueError(name)


@pytest.mark.parametrize("case", ["ragged", "mixed_seg_max"])
def test_seg_norm_bit_exact_to_scatter_add(case):
    """The shifted-add normalisation equals the XLA:CPU scatter-add one
    bit for bit on every real row; the dummy tail (longer than seg_max)
    keeps its divisor 1."""
    import jax
    import jax.numpy as jnp

    from repro.core import flow

    owner, n_comm = _seg_case(case)
    Bt, P = owner.shape
    rng = np.random.default_rng(5)
    x = (rng.random((Bt, P)) * np.exp(rng.normal(0, 4, (Bt, P)))).astype(
        np.float32) + np.float32(1e-3)
    seg_max = max(flow._seg_passes(o[o < n_comm - 1]) for o in owner)

    @jax.jit
    def new(x, owner):
        pos, run = flow._seg_layout(owner, seg_max, n_comm - 1)
        return flow._seg_norm(x, pos, run, seg_max)

    @jax.jit
    def scatter(x, owner):
        s = jnp.zeros((Bt, n_comm), jnp.float32).at[
            jnp.arange(Bt)[:, None], owner].add(x)
        return x / jnp.take_along_axis(s, owner, axis=1)

    got = np.asarray(new(x, owner))
    ref = np.asarray(scatter(x, owner))
    real = owner != n_comm - 1
    np.testing.assert_array_equal(got[real], ref[real])
    np.testing.assert_array_equal(got[~real], x[~real])


def test_seg_norm_sequential_bit_exact_to_scatter_add():
    """One (P,) owner row with no dummy commodity: the helpers act on the
    last axis, so the same bits as the scatter-add."""
    import jax
    import jax.numpy as jnp

    from repro.core import flow

    owner, K = _seg_case("single_row")
    x = np.random.default_rng(2).random(owner.shape).astype(np.float32)
    seg_max = flow._seg_passes(owner)
    got = jax.jit(lambda x: flow._seg_norm(
        x, *flow._seg_layout(owner, seg_max), seg_max))(x)
    s = jnp.zeros((K,), jnp.float32).at[owner].add(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x / s[owner]))


def _runs_system(runs):
    """A hand-built canonical system: one single-hop path per row."""
    P, E = int(sum(runs)), 8
    return PathSystem(
        n_edges=E,
        path_edges=(np.arange(P) % (2 * E)).astype(np.int32)[:, None],
        path_len=np.ones(P, np.int32),
        path_owner=np.repeat(np.arange(len(runs)), runs).astype(np.int32),
        demands=np.ones(len(runs), np.float32),
        capacities=np.ones(2 * E, np.float32),
        n_commodities=len(runs),
    )


def test_window_compile_shared_within_seg_max_bucket():
    """seg_max is read from the batch and bucketed: longest runs of 5 and 7
    rows share one compiled window, 9 rows compiles anew."""
    from repro.core import flow

    a, b, c = (_runs_system(r) for r in ([1, 5, 2, 3], [7, 1, 3, 2],
                                          [9, 1, 2, 1]))
    assert [PathSystemBatch.from_systems([s]).seg_max
            for s in (a, b, c)] == [8, 8, 12]
    mw_concurrent_flow_batch([a], iters=20, backend="scatter")
    base = flow._mw_window_batch._cache_size()
    res = mw_concurrent_flow_batch([b], iters=20, backend="scatter")
    assert flow._mw_window_batch._cache_size() == base
    seq = mw_concurrent_flow(b, iters=20, backend="scatter")
    assert res[0].alpha == seq.alpha
    mw_concurrent_flow_batch([c], iters=20, backend="scatter")
    assert flow._mw_window_batch._cache_size() == base + 1


# --------------------------------------------------------------------------- #
# one routing under several demand vectors; the one backend decision
# --------------------------------------------------------------------------- #


def test_shared_batch_matches_sequential():
    """A sweep over demands on one routing is B copies of that system."""
    (ps,) = _systems((48,))
    rng = np.random.default_rng(0)
    copies = [
        dataclasses.replace(
            ps,
            demands=ps.demands
            * (0.5 + rng.random(ps.n_commodities).astype(np.float32)),
        )
        for _ in range(3)
    ]
    batch = PathSystemBatch.from_systems(copies)
    assert batch.path_edges.ndim == 3 and batch.slot_gather is not None
    bat = mw_concurrent_flow_batch(batch, iters=100)
    for c, b in zip(copies, bat):
        s = mw_concurrent_flow(c, iters=100, backend="scatter")
        assert s.alpha == b.alpha


@pytest.mark.parametrize("n_batch, requested, has_table, want", [
    (1, "auto", True, "gather"),  # the batch policy, even for B = 1
    (3, "auto", True, "gather"),
    (2, "gather", False, "scatter"),  # no fan-in table: scatter
    (2, "auto", False, "scatter"),
    (2, "scatter", True, "scatter"),  # explicit choices pass through
    (2, "dense", True, "dense"),
    (2, "pallas", True, "pallas"),
])
def test_congestion_backend_decision(n_batch, requested, has_table, want):
    batch = PathSystemBatch.from_systems(_systems((24, 40, 32)[:n_batch]))
    if not has_table:
        batch = dataclasses.replace(batch, slot_gather=None)
    backend, table = batch.congestion_backend(requested)
    assert backend == want
    if want == "gather":
        assert table is batch.slot_gather
    else:
        assert table is None


# --------------------------------------------------------------------------- #
# jit-churn fix: padded final window is a masked no-op
# --------------------------------------------------------------------------- #


def test_adaptive_window_padding_is_bit_exact():
    """iters not a multiple of check_every: the padded final window must
    reproduce the single-scan trajectory bit-exactly."""
    (ps,) = _systems((40,))
    full = mw_concurrent_flow(ps, iters=130)
    # never stops early (patience huge), so the windowed run covers the
    # same 130 live steps: 50 + 50 + (30 live + 20 masked no-ops)
    windowed = mw_concurrent_flow(
        ps, iters=130, early_stop=True, check_every=50, rel_tol=0.0,
        patience=10**9,
    )
    assert windowed.iters == 130
    assert windowed.alpha == full.alpha
    np.testing.assert_array_equal(windowed.rates, full.rates)


def test_adaptive_single_compilation_per_solve():
    """The short final window must reuse the full window's compilation."""
    from repro.core import flow

    (ps,) = _systems((32,))
    mw_concurrent_flow(ps, iters=130, early_stop=True, check_every=50,
                       rel_tol=0.0, patience=10**9)
    base = flow._mw_window_batch._cache_size()
    mw_concurrent_flow(ps, iters=130, early_stop=True, check_every=50,
                       rel_tol=0.0, patience=10**9)
    assert flow._mw_window_batch._cache_size() == base


# --------------------------------------------------------------------------- #
# speculative bisection
# --------------------------------------------------------------------------- #


def test_speculative_equals_sequential_monotone():
    for thresh in (0, 1, 137, 999, 1000):
        ok = lambda m: m <= thresh
        ok_b = lambda ms: [ok(m) for m in ms]
        for levels in (1, 2, 3, 5):
            assert speculative_max_feasible(0, 1000, ok_b, levels=levels) == \
                max_feasible(0, 1000, ok)


def test_speculative_equals_sequential_nonmonotone():
    """The wave replays the exact bisection descent, so even a noisy,
    NON-monotone predicate lands on the sequential answer."""
    rng = np.random.default_rng(5)
    table = rng.random(2049) < 0.5
    ok = lambda m: bool(table[m])
    ok_b = lambda ms: [ok(m) for m in ms]
    for lo, hi in [(0, 2048), (100, 1100), (7, 8), (3, 3)]:
        want = max_feasible(lo, hi, ok)
        for levels in (2, 4):
            assert speculative_max_feasible(lo, hi, ok_b, levels=levels) == want


def test_speculative_wave_rounds():
    calls = {"n": 0, "max_cands": 0}

    def ok_b(ms):
        calls["n"] += 1
        calls["max_cands"] = max(calls["max_cands"], len(ms))
        return [m <= 300 for m in ms]

    speculative_max_feasible(0, 1023, ok_b, levels=2)
    assert calls["n"] == 5  # ceil(10 levels / 2)
    assert calls["max_cands"] <= 3  # 2**2 - 1

    with pytest.raises(ValueError, match="levels"):
        speculative_max_feasible(0, 10, ok_b, levels=0)


def test_speculative_bisection_end_to_end_equal():
    """fig1c-style searches (MW probes) agree across drivers."""
    from repro.core import max_servers_at_full_capacity

    kw = dict(seeds=(0,), k=4, method="mw", n_matrices=2)
    seq = max_servers_at_full_capacity(12, 8, 10, 30, **kw)
    wave = max_servers_at_full_capacity(12, 8, 10, 30, wave_levels=2, **kw)
    assert seq == wave


# --------------------------------------------------------------------------- #
# REPRO_LP_PATH_LIMIT (import-time validation) and throughput dispatch
# --------------------------------------------------------------------------- #


def test_lp_path_limit_env_validated_at_import():
    import os
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    for bad in ("twenty", "-5"):
        env = dict(os.environ, REPRO_LP_PATH_LIMIT=bad)
        env["PYTHONPATH"] = str(root / "src")
        proc = subprocess.run(
            [sys.executable, "-c", "import repro.core.flow"],
            env=env, capture_output=True, text=True, cwd=str(root),
        )
        assert proc.returncode != 0
        assert "REPRO_LP_PATH_LIMIT" in proc.stderr


def test_lp_path_limit_steers_throughput(monkeypatch):
    from repro.core import flow, throughput

    (ps,) = _systems((24,))
    monkeypatch.setattr(flow, "LP_PATH_LIMIT", ps.n_paths)
    assert throughput(ps, iters=40).method == "lp"
    monkeypatch.setattr(flow, "LP_PATH_LIMIT", ps.n_paths - 1)
    assert throughput(ps, iters=40).method.startswith("mw")


# --------------------------------------------------------------------------- #
# MPTCP warm start
# --------------------------------------------------------------------------- #


def test_mptcp_warm_start_plumbing():
    from repro.core import fail_links, mptcp_throughput, update_path_system

    top = jellyfish(40, 10, 6, seed=2)
    comm = random_permutation_traffic(top, seed=1)
    ps = build_path_system(top, comm, k=4)
    base = mptcp_throughput(ps, iters=400)
    assert base.rates is not None and len(base.rates) == ps.n_paths
    delta = update_path_system(ps, top, fail_links(top, n_links=2, seed=3), comm)
    warm = mptcp_throughput(delta, iters=400, warm=base)
    cold = mptcp_throughput(delta, iters=400)
    # warm start changes the transient, not the equilibrium quality
    assert abs(warm.mean_throughput - cold.mean_throughput) < 0.05
    assert warm.rates is not None and len(warm.rates) == delta.n_paths
