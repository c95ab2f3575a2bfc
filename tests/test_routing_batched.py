"""Batched routing engine + kernel-backed flow solver backends.

Covers the PR-1 rebuild: the batched near-shortest-path enumerator against
networkx and the legacy DFS, PathSystem behavior with unrouted (disconnected)
commodities, the per-topology routing cache, and scatter/dense/pallas
congestion-backend parity of the MW solver.
"""

from itertools import islice

import numpy as np
import pytest

from repro.core import (
    Topology,
    build_path_system,
    jellyfish,
    k_shortest_paths,
    lp_concurrent_flow,
    mw_concurrent_flow,
    mptcp_throughput,
    random_permutation_traffic,
    throughput,
)
from repro.core.routing import (
    _k_shortest_paths_dfs,
    _topo_cache,
    _topo_key,
    clear_routing_cache,
)


# --------------------------------------------------------------------------- #
# batched enumerator correctness
# --------------------------------------------------------------------------- #


def test_batched_matches_networkx_simple_paths():
    import networkx as nx

    top = jellyfish(60, 10, 6, seed=5)
    g = nx.Graph(top.edges.tolist())
    pairs = [(0, 30), (1, 59), (10, 20), (5, 6), (42, 3)]
    ours = k_shortest_paths(top, pairs, k=6)
    for (s, t), mine in zip(pairs, ours):
        ref = [len(p) - 1 for p in islice(nx.shortest_simple_paths(g, s, t), 6)]
        assert sorted(len(p) - 1 for p in mine) == sorted(ref)
        for p in mine:  # simple, adjacent, correctly terminated
            assert len(set(p)) == len(p)
            assert p[0] == s and p[-1] == t
            assert all(g.has_edge(a, b) for a, b in zip(p, p[1:]))


def test_batched_matches_legacy_dfs_lengths():
    for seed in range(3):
        top = jellyfish(40, 9, 6, seed=seed)
        rng = np.random.default_rng(seed)
        pairs = [tuple(rng.choice(40, 2, replace=False)) for _ in range(50)]
        batched = k_shortest_paths(top, pairs, k=8)
        dfs = _k_shortest_paths_dfs(top, pairs, k=8)
        for (s, t), pa, pb in zip(pairs, batched, dfs):
            assert sorted(map(len, pa)) == sorted(map(len, pb)), (seed, s, t)


def test_batched_high_slack_sparse_graph():
    """Ring: k=2 needs the full way-around path (slack ~ N - 2*d)."""
    import networkx as nx

    ring = [(i, (i + 1) % 12) for i in range(12)]
    top = Topology.regular(12, 4, 2, ring)
    g = nx.Graph(top.edges.tolist())
    for s, t in [(0, 3), (0, 6), (1, 7)]:
        mine = k_shortest_paths(top, [(s, t)], k=2, max_slack=12)[0]
        ref = [len(p) - 1 for p in islice(nx.shortest_simple_paths(g, s, t), 2)]
        assert sorted(len(p) - 1 for p in mine) == sorted(ref)


def test_reversed_pairs_share_enumeration():
    top = jellyfish(30, 8, 5, seed=2)
    fwd, rev = k_shortest_paths(top, [(3, 17), (17, 3)], k=4)
    assert [p[::-1] for p in fwd] == rev


def test_degenerate_same_node_pair():
    top = jellyfish(20, 8, 5, seed=0)
    assert k_shortest_paths(top, [(4, 4)], k=3) == [[[4]]]


# --------------------------------------------------------------------------- #
# unrouted commodities (disconnected pairs)
# --------------------------------------------------------------------------- #


def _two_island_topology():
    # two K4-ish islands, no bridge; 2 server ports per switch
    edges = [(0, 1), (0, 2), (1, 3), (2, 3), (4, 5), (4, 6), (5, 7), (6, 7)]
    return Topology.regular(8, 5, 3, edges)


def test_path_system_with_unrouted_commodities():
    from repro.core.traffic import Commodities

    top = _two_island_topology()
    comm = Commodities(
        src=np.array([0, 1, 4, 2]),
        dst=np.array([3, 5, 7, 6]),  # 1->5 and 2->6 cross islands: unroutable
        demand=np.ones(4),
        n_flows=4,
    )
    ps = build_path_system(top, comm, k=4)
    assert ps.unrouted.tolist() == [False, True, False, True]
    assert ps.n_commodities == 2
    assert len(ps.demands) == 2
    assert ps.path_owner.max() == 1
    # solvers run on the routable remainder without blowing up
    for solver in (lp_concurrent_flow, lambda p: mw_concurrent_flow(p, 50)):
        res = solver(ps)
        assert np.isfinite(res.alpha) and res.alpha > 0
    res = mptcp_throughput(ps, iters=100)
    assert len(res.per_flow) == 2


def test_path_system_all_unrouted():
    from repro.core.traffic import Commodities

    top = _two_island_topology()
    comm = Commodities(
        src=np.array([0, 1]), dst=np.array([4, 6]), demand=np.ones(2), n_flows=2
    )
    ps = build_path_system(top, comm, k=4)
    assert ps.unrouted.all() and ps.n_paths == 0
    assert mw_concurrent_flow(ps).alpha == 0.0
    assert throughput(ps).alpha == 0.0


# --------------------------------------------------------------------------- #
# per-topology cache
# --------------------------------------------------------------------------- #


def test_routing_cache_reused_across_traffic_matrices():
    top = jellyfish(30, 8, 5, seed=7)
    clear_routing_cache()
    ps1 = build_path_system(top, random_permutation_traffic(top, seed=0), k=4)
    entry = _topo_cache[_topo_key(top)]
    dist_obj = entry["dist"]
    ps2 = build_path_system(top, random_permutation_traffic(top, seed=1), k=4)
    assert _topo_cache[_topo_key(top)]["dist"] is dist_obj  # no recompute
    assert ps1.n_edges == ps2.n_edges
    # cache=False must not touch the shared cache
    clear_routing_cache()
    build_path_system(top, random_permutation_traffic(top, seed=2), k=4,
                      cache=False)
    assert _topo_key(top) not in _topo_cache


def test_cache_distinguishes_topologies():
    a = jellyfish(30, 8, 5, seed=0)
    b = jellyfish(30, 8, 5, seed=1)
    clear_routing_cache()
    pa = build_path_system(a, random_permutation_traffic(a, seed=0), k=4)
    pb = build_path_system(b, random_permutation_traffic(b, seed=0), k=4)
    assert _topo_key(a) != _topo_key(b)
    assert len(_topo_cache) == 2
    assert pa.n_paths > 0 and pb.n_paths > 0


# --------------------------------------------------------------------------- #
# congestion backend parity (scatter vs dense vs pallas kernel)
# --------------------------------------------------------------------------- #


def _parity_system():
    top = jellyfish(40, 10, 6, seed=4)
    comm = random_permutation_traffic(top, seed=5)
    return build_path_system(top, comm, k=8)


def test_fused_kernel_products_match_scatter_math():
    """(B^T r, B w) from the fused pallas kernel == scatter/gather reference.

    This is the lag-free, chaos-free parity check of the primitive itself on
    a real path system's incidence.
    """
    import jax.numpy as jnp

    from repro.core.flow import make_congestion_fn_batch

    ps = _parity_system()
    pe = jnp.asarray(ps.path_edges)[None]
    rng = np.random.default_rng(0)
    rates = jnp.asarray(rng.uniform(size=ps.n_paths).astype(np.float32))
    prices = jnp.asarray(rng.uniform(size=ps.n_slots).astype(np.float32))
    scatter = make_congestion_fn_batch(pe, ps.n_slots, "scatter")
    pallas = make_congestion_fn_batch(pe, ps.n_slots, "pallas")
    ls, cs = (a[0] for a in scatter(rates[None], prices[None]))
    lp_, cp = (a[0] for a in pallas(rates[None], prices[None]))
    np.testing.assert_allclose(np.asarray(ls), np.asarray(lp_), atol=1e-5)
    np.testing.assert_allclose(np.asarray(cs), np.asarray(cp), atol=1e-5)
    # loads also agree with the numpy PathSystem oracle
    np.testing.assert_allclose(
        np.asarray(ls), ps.loads(np.asarray(rates)), atol=1e-4
    )


# NOTE: the annealed-softmax MW iteration is chaotic — float accumulation
# order differences between backends amplify with iteration count (1e-7-ish
# at 25 iterations, 1e-4-ish by 400).  The solver-level parity tests therefore
# run a short horizon, where identical math must agree to well under 1e-5;
# the primitive-level test above is exact at any scale.


def test_mw_dense_backend_matches_scatter():
    ps = _parity_system()
    a = mw_concurrent_flow(ps, iters=25, backend="scatter")
    b = mw_concurrent_flow(ps, iters=25, backend="dense")
    assert a.alpha == pytest.approx(b.alpha, abs=1e-5)


def test_mw_pallas_kernel_matches_scatter():
    """The fused congestion_pallas kernel (interpret mode on CPU) drives the
    MW solver to the same alpha as the scatter-add reference."""
    ps = _parity_system()
    a = mw_concurrent_flow(ps, iters=25, backend="scatter")
    b = mw_concurrent_flow(ps, iters=25, backend="pallas")
    assert b.method == "mw-batch-pallas"
    assert a.alpha == pytest.approx(b.alpha, abs=1e-5)
    # both feasible
    for res in (a, b):
        loads = ps.loads(res.rates)
        assert (loads <= ps.capacities * (1 + 1e-4)).all()


def test_mptcp_dense_backend_matches_scatter():
    ps = _parity_system()
    a = mptcp_throughput(ps, iters=200, backend="scatter")
    b = mptcp_throughput(ps, iters=200, backend="dense")
    np.testing.assert_allclose(a.per_flow, b.per_flow, atol=1e-4)


def test_preferred_backend_size_dispatch():
    from repro.kernels import ops

    # tiny instance: dense allowed on CPU; huge instance: scatter
    assert ops.preferred_congestion_backend(100, 200) == "dense"
    assert ops.preferred_congestion_backend(50_000, 80_000) == "scatter"
