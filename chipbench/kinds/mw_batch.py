"""Unit kind ``mw_batch``: a checked batched throughput answer.

Set-up makes ``fabrics`` RRGs and ``matrices_per_fabric`` permutation
matrices on each from the seed, and routes them with the program's
``build_path_system_batch`` (k shortest paths, as the configuration
states).  One unit is the operator's question "what throughput does each
of these traffic matrices get": ``mw_concurrent_flow_batch`` over the list
of path systems with the traffic's iteration count, so batch assembly,
transfer, the solve and the copy back are all inside the unit.  Every unit
asks the same question of the same systems.

The check (after the window, program state released):

* ``paths_invalid``: path rows and commodities of every routed system that
  break the routing contract (``ref.path_table_faults``), plus commodities
  the program left unrouted on a connected fabric.  Exact: limit 0.
* ``paths_mismatch``: of ``check_pairs`` commodities per system drawn from
  the seed, those whose path list differs from ``ref.k_shortest``.
  Exact: limit 0.
* ``cert_gap`` and ``overload``: of every instance of every unit, what its
  rates certify against the alpha it claims, in float64 (``ref.certify``).
* ``alpha_bias``: for one unit drawn from the seed, the program's alpha
  of every instance against the float64 reference MW (``ref.mw_solve``)
  on the same path table: the mean of the signed relative gaps.  One
  instance's gap (``alpha_ref_gap``, the largest) swings by up to half a
  percent with float32 rounding, as 400 annealed iterations amplify it,
  and does not separate a bfloat16 solve; their mean does (PERF.md).

The control (``control_unit``): the reference MW in bfloat16 in the
program's place.
"""

from __future__ import annotations

import numpy as np

from chipbench import gen, ref


class Cell:
    #: The jitted programs the window drives, for their device footprint.
    window_programs = ("repro.core.flow._mw_window_batch",)

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.core import Topology, build_path_system_batch
        from repro.core.traffic import Commodities

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        n, ports, r = cfg["switches"], cfg["ports"], cfg["network_ports"]
        self.k, self.max_slack = cfg["paths_k"], cfg["max_slack"]
        self.iters = traffic["iters"]
        self.edges, self.inst = [], []
        tops, comms = [], []
        for f in range(traffic["fabrics"]):
            e = gen.rrg_edges(n, r, gen.rng_for(seed, "fabric", f))
            self.edges.append(e)
            top = Topology.regular(n, ports, r, e)
            for m in range(traffic["matrices_per_fabric"]):
                src, dst, dem, nsrv = gen.permutation_pairs(
                    n, ports - r, gen.rng_for(seed, "matrix", f, m))
                self.inst.append((f, src, dst, dem))
                tops.append(top)
                comms.append(Commodities(src, dst, dem, nsrv))
        batch = build_path_system_batch(tops, comms, k=self.k,
                                        max_slack=self.max_slack)
        self.systems = list(batch.systems)
        del batch

    work_per_unit = 1

    def solve(self, systems):
        from repro.core import mw_concurrent_flow_batch

        return mw_concurrent_flow_batch(systems, iters=self.iters)

    def unit(self, i: int):
        res = self.solve(self.systems)
        return [(float(r.alpha), np.asarray(r.rates), r.method) for r in res]

    def control_unit(self, i: int):
        """A unit answered by the reference MW in bfloat16."""
        return [(*ref.mw_solve(ps.path_edges, ps.path_len, ps.path_owner,
                               ps.demands, ps.n_slots, self.iters, "bf16"),
                 "control-bf16") for ps in self.systems]

    def warm(self) -> None:
        """One unit: the window's exact programs, compiled and loaded."""
        self.unit(-1)

    def context(self, units) -> dict:
        return {"mw_iters": self.iters * len(units)}

    def release(self) -> None:
        """Nothing to free: the answers are host arrays."""

    def check(self, units) -> dict:
        n = self.cfg["switches"]
        faults = 0
        mismatch = 0
        rng = gen.rng_for(self.seed, "check")
        dists = [ref.bfs_hops(n, e) for e in self.edges]
        nbrs = [ref.neighbour_lists(n, e) for e in self.edges]
        for ps, (f, src, dst, dem) in zip(self.systems, self.inst):
            e = self.edges[f]
            K = len(src)
            unrouted = 0 if ps.unrouted is None else int(np.sum(ps.unrouted))
            faults += unrouted + abs(int(ps.n_commodities) - K)
            if unrouted or ps.n_commodities != K:
                continue
            faults += ref.path_table_faults(
                ps.path_edges, ps.path_len, ps.path_owner, src, dst,
                dists[f], len(e), e, self.k, self.max_slack)
            head, tail = ref.slot_ends(len(e), e)
            owner = np.asarray(ps.path_owner)
            lo = np.searchsorted(owner, np.arange(K))
            hi = np.searchsorted(owner, np.arange(K), side="right")
            for c in rng.choice(K, min(K, self.traffic["check_pairs"]),
                                replace=False):
                got = ref.decode_rows(ps.path_edges, ps.path_len,
                                      np.arange(lo[c], hi[c]), head, tail)
                want = ref.k_shortest(nbrs[f], dists[f], int(src[c]),
                                      int(dst[c]), self.k, self.max_slack)
                mismatch += got != want
        per_unit = []
        for res in units:
            worst = [0.0, 0.0]
            for ps, (f, src, dst, dem), (alpha, rates, _) in zip(
                    self.systems, self.inst, res):
                g, o = ref.certify(ps.path_edges, ps.path_len, ps.path_owner,
                                   dem, 2 * len(self.edges[f]), rates, alpha)
                worst = [max(worst[0], g), max(worst[1], o)]
            if len(res) != len(self.systems):
                worst = [float("inf"), float("inf")]
            per_unit.append({"cert_gap": worst[0], "overload": worst[1]})
        gaps = []
        if units:
            u = int(rng.integers(len(units)))
            for b, (ps, (f, src, dst, dem)) in enumerate(zip(self.systems,
                                                             self.inst)):
                a_ref, _ = ref.mw_solve(ps.path_edges, ps.path_len,
                                        ps.path_owner, dem,
                                        2 * len(self.edges[f]), self.iters)
                a = units[u][b][0] if b < len(units[u]) else float("nan")
                gaps.append(a / a_ref - 1.0)
        bias = abs(float(np.mean(gaps))) if gaps else float("nan")
        worst = float(np.max(np.abs(gaps))) if gaps else float("nan")
        return {"numbers": {"paths_invalid": faults,
                            "paths_mismatch": mismatch,
                            "alpha_bias": bias if np.isfinite(bias) else
                            float("inf"),
                            "alpha_ref_gap": worst if np.isfinite(worst) else
                            float("inf")},
                "per_unit": per_unit, "info": {"alpha_gaps": gaps}}
