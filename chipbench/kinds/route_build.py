"""Unit kind ``route_build``: routing tables for a fabric nobody has seen.

One unit is the "fresh fabric" question: an RRG made from the seed pair
(seed, unit index), so the program's per-topology routing cache misses,
and ``matrices`` permutation matrices on it, routed by the program's
``build_path_system_batch`` (APSP, then k-shortest-path enumeration and
slot assembly).  The fabric and its traffic are made inside the unit: they
cost a few percent of it (``gen`` is vectorised).

The check (after the window):

* ``apsp_mismatch``: entries of the program's all-pairs hop matrix for
  each unit's fabric that differ from a breadth-first search.  Exact.
* ``paths_invalid``: path rows and commodities of every routed system that
  break the routing contract (``ref.path_table_faults``), and commodities
  left unrouted on a connected fabric.  Exact.
* ``paths_mismatch``: of ``check_pairs`` commodities per system drawn
  from the seed, those whose path list differs from ``ref.k_shortest``.
  Exact.

The control (``control_unit``; routing states no precision to lower): the
reference k-shortest paths with ties broken in reverse lexicographic
order, which breaks the configuration's stated tie order.
"""

from __future__ import annotations

import numpy as np

from chipbench import gen, ref


class Cell:
    work_per_unit = 1

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.k, self.max_slack = cfg["paths_k"], cfg["max_slack"]

    def make(self, key):
        from repro.core import Topology
        from repro.core.traffic import Commodities

        n, ports, r = (self.cfg["switches"], self.cfg["ports"],
                       self.cfg["network_ports"])
        e = gen.rrg_edges(n, r, gen.rng_for(self.seed, "fabric", key))
        top = Topology.regular(n, ports, r, e)
        inst = [gen.permutation_pairs(n, ports - r,
                                      gen.rng_for(self.seed, "matrix", key, m))
                for m in range(self.traffic["matrices"])]
        comms = [Commodities(s, d, dem, ns) for s, d, dem, ns in inst]
        return e, top, inst, comms

    def build(self, tops, comms):
        from repro.core import build_path_system_batch

        return build_path_system_batch(tops, comms, k=self.k,
                                       max_slack=self.max_slack).systems

    def unit(self, i: int):
        return self._routed(i)

    def _routed(self, i: int):
        from repro.core import routing

        e, top, inst, comms = self.make(i)
        systems = self.build([top] * len(comms), comms)
        dist = routing._cached_dist(top, routing._topo_entry(top))
        return e, inst, list(systems), dist

    def control_unit(self, i: int):
        """A unit whose path tables are the control's."""
        e, inst, systems, dist = self._routed(i)
        n = self.cfg["switches"]
        dist_ref = ref.bfs_hops(n, e)
        nbrs = ref.neighbour_lists(n, e)
        E = len(e)
        sid = {(int(u), int(v)): j for j, (u, v) in enumerate(e)}
        for ps, (src, dst, _, _) in zip(systems, inst):
            rows, owner = [], []
            for j, (s, t) in enumerate(zip(src.tolist(), dst.tolist())):
                for p in ref.k_shortest(nbrs, dist_ref, s, t, self.k,
                                        self.max_slack, reverse_ties=True):
                    rows.append([sid[(a, b)] if a < b else sid[(b, a)] + E
                                 for a, b in zip(p[:-1], p[1:])])
                    owner.append(j)
            pe = np.full((len(rows), max(map(len, rows))), 2 * E, np.int32)
            for j, r in enumerate(rows):
                pe[j, : len(r)] = r
            ps.path_edges = pe
            ps.path_len = np.array([len(r) for r in rows], np.int32)
            ps.path_owner = np.array(owner, np.int32)
        return e, inst, systems, dist

    def warm(self) -> None:
        """One unit on a fabric of its own: every program the window runs."""
        e, top, inst, comms = self.make(1 << 40)
        self.build([top] * len(comms), comms)

    def context(self, units) -> dict:
        return {"builds": len(units)}

    def release(self) -> None:
        from repro.core import routing

        routing.clear_routing_cache()

    def check(self, units) -> dict:
        n = self.cfg["switches"]
        rng = gen.rng_for(self.seed, "check")
        per_unit = []
        for e, inst, systems, dist in units:
            want = ref.bfs_hops(n, e)
            got = np.asarray(dist, np.float64)
            got = np.where(got >= np.iinfo(np.int16).max, np.inf, got)
            apsp = int(np.sum(got != want)) if got.shape == want.shape else n * n
            faults, mismatch = 0, 0
            nbrs = ref.neighbour_lists(n, e)
            head, tail = ref.slot_ends(len(e), e)
            for ps, (src, dst, dem, _) in zip(systems, inst):
                K = len(src)
                unrouted = 0 if ps.unrouted is None else int(np.sum(ps.unrouted))
                faults += unrouted + abs(int(ps.n_commodities) - K)
                if unrouted or ps.n_commodities != K:
                    continue
                faults += ref.path_table_faults(
                    ps.path_edges, ps.path_len, ps.path_owner, src, dst, want,
                    len(e), e, self.k, self.max_slack)
                owner = np.asarray(ps.path_owner)
                lo = np.searchsorted(owner, np.arange(K))
                hi = np.searchsorted(owner, np.arange(K), side="right")
                for c in rng.choice(K, min(K, self.traffic["check_pairs"]),
                                    replace=False):
                    got_p = ref.decode_rows(ps.path_edges, ps.path_len,
                                            np.arange(lo[c], hi[c]), head, tail)
                    want_p = ref.k_shortest(nbrs, want, int(src[c]),
                                            int(dst[c]), self.k,
                                            self.max_slack)
                    mismatch += got_p != want_p
            if len(systems) != len(inst):
                faults += abs(len(inst) - len(systems))
            per_unit.append({"apsp_mismatch": apsp, "paths_invalid": faults,
                             "paths_mismatch": mismatch})
        return {"per_unit": per_unit}
