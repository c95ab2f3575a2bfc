"""Unit kind ``capacity_wave``: one speculative wave of the Fig 1(c) search.

The search (``repro.core.max_servers_at_full_capacity``) asks how many
servers a Jellyfish built from a fat-tree's switches carries at full
capacity; each of its speculative waves probes a few server counts at once.
Set-up makes one fabric per candidate count from the configuration's fixed
``deployment_seed`` (``gen_capacity``: the servers spread as the search
spreads them, the remaining ports wired by the configuration model) and
one random server permutation on each, and routes them all in one
``build_path_system_batch``.  The candidates are ``candidates`` ratios of
``switches * (ports - network_ports)`` servers.  One unit is the program's
``probe_wave`` over them: one batched MW solve with ``target_alpha=1.0``
in 50-iteration windows over an ``iters`` budget, folded into
per-candidate verdicts.  Every unit asks the same question.

The check (after the window, program state released):

* ``paths_invalid`` and ``paths_mismatch``: the routing contract and the
  reference enumeration, as in ``mw_batch``.  Exact: limit 0.
* ``verdict_mismatch``: of every unit, the candidates whose verdict
  differs from the float64 reference wave (``ref_capacity.wave``, the same
  windows and stop rule).  Exact: limit 0.
* ``cert_gap`` and ``overload``: of every instance of every unit, what its
  rates certify against the alpha it claims (``ref.certify``).

The deployment does not depend on ``--seed`` (which draws only the
commodities whose path lists are compared), so one reference wave serves
every unit and every cell built in one process.

The control (``control_unit``): the reference wave in bfloat16 in the
program's place.
"""

from __future__ import annotations

import json

import numpy as np

from chipbench import gen, gen_capacity, ref, ref_capacity

#: Reference waves already computed in this process, by deployment.
_REFERENCE: dict = {}


class Cell:
    #: The jitted programs the window drives, for their device footprint.
    window_programs = ("repro.core.flow._mw_window_batch",)
    work_per_unit = 1

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        # the entry point this cell times: a program without it fails here
        from repro.core import Topology, build_path_system_batch, probe_wave
        from repro.core.traffic import Commodities

        self._probe_wave = probe_wave
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        n, ports, r = cfg["switches"], cfg["ports"], cfg["network_ports"]
        self.k, self.max_slack = cfg["paths_k"], cfg["max_slack"]
        self.tol = cfg["accept_tol"]
        self.iters = traffic["iters"]
        base = n * (ports - r)
        self.servers = [int(round(f * base)) for f in traffic["candidates"]]
        self.key = json.dumps([cfg, traffic], sort_keys=True)
        dep = cfg["deployment_seed"]
        self.edges, self.inst = [], []
        tops, comms = [], []
        for c, m in enumerate(self.servers):
            srv = gen_capacity.spread(m, n)
            e = gen_capacity.degree_sequence_edges(
                ports - srv, gen.rng_for(dep, "fabric", c))
            self.edges.append(e)
            top = Topology(n_switches=n, edges=e,
                           ports=np.full(n, ports, dtype=np.int64),
                           net_degree=ports - srv, name=f"candidate-{m}")
            for mat in range(cfg["n_matrices"]):
                src, dst, dem, nsrv = gen_capacity.permutation_pairs(
                    srv, gen.rng_for(dep, "matrix", c, mat))
                self.inst.append((c, src, dst, dem))
                tops.append(top)
                comms.append(Commodities(src, dst, dem, nsrv))
        batch = build_path_system_batch(tops, comms, k=self.k,
                                        max_slack=self.max_slack)
        systems = list(batch.systems)
        del batch
        per = cfg["n_matrices"]
        self.groups = [systems[c * per:(c + 1) * per]
                       for c in range(len(self.servers))]
        self.systems = systems

    def solve(self, groups):
        return self._probe_wave(groups, iters=self.iters, tol=self.tol)

    def unit(self, i: int):
        verdicts, results = self.solve(self.groups)
        return (list(verdicts),
                [[(float(r.alpha), np.asarray(r.rates), int(r.iters))
                  for r in grp] for grp in results])

    def _tables(self):
        """Per candidate, its instances' path tables with the demands and
        slot counts of the fabric made here."""
        out = [[] for _ in self.groups]
        for ps, (c, src, dst, dem) in zip(self.systems, self.inst):
            out[c].append((ps.path_edges, ps.path_len, ps.path_owner, dem,
                           2 * len(self.edges[c])))
        return out

    def _reference(self, precision: str):
        key = (self.key, precision)
        if key not in _REFERENCE:
            _REFERENCE[key] = ref_capacity.wave(self._tables(), self.iters,
                                                self.tol, precision)
        return _REFERENCE[key]

    def control_unit(self, i: int):
        """A unit answered by the reference wave in bfloat16."""
        return self._reference("bf16")

    def warm(self) -> None:
        """The window's exact programs, compiled and loaded: the wave's
        solve with a target every instance meets after its first window."""
        from repro.core import mw_concurrent_flow_batch

        mw_concurrent_flow_batch(self.systems, iters=self.iters,
                                 target_alpha=0.0)

    def context(self, units) -> dict:
        return {}

    def release(self) -> None:
        """Nothing to free: the answers are host arrays."""

    def check(self, units) -> dict:
        n = self.cfg["switches"]
        faults = 0
        mismatch = 0
        rng = gen.rng_for(self.seed, "check")
        dists = [ref.bfs_hops(n, e) for e in self.edges]
        nbrs = [ref.neighbour_lists(n, e) for e in self.edges]
        for ps, (c, src, dst, dem) in zip(self.systems, self.inst):
            e = self.edges[c]
            K = len(src)
            unrouted = 0 if ps.unrouted is None else int(np.sum(ps.unrouted))
            faults += unrouted + abs(int(ps.n_commodities) - K)
            if unrouted or ps.n_commodities != K:
                continue
            faults += ref.path_table_faults(
                ps.path_edges, ps.path_len, ps.path_owner, src, dst,
                dists[c], len(e), e, self.k, self.max_slack)
            head, tail = ref.slot_ends(len(e), e)
            owner = np.asarray(ps.path_owner)
            lo = np.searchsorted(owner, np.arange(K))
            hi = np.searchsorted(owner, np.arange(K), side="right")
            for j in rng.choice(K, min(K, self.traffic["check_pairs"]),
                                replace=False):
                got = ref.decode_rows(ps.path_edges, ps.path_len,
                                      np.arange(lo[j], hi[j]), head, tail)
                want = ref.k_shortest(nbrs[c], dists[c], int(src[j]),
                                      int(dst[j]), self.k, self.max_slack)
                mismatch += got != want
        want_v, want_a = self._reference("f64")
        per_unit = []
        for verdicts, answers in units:
            worst = [0.0, 0.0]
            if (len(verdicts) != len(want_v) or len(answers) != len(want_v)
                    or any(len(a) != len(g)
                           for a, g in zip(answers, self.groups))):
                per_unit.append({"verdict_mismatch": float("inf"),
                                 "cert_gap": float("inf"),
                                 "overload": float("inf")})
                continue
            for group, got in zip(self._tables(), answers):
                for (pe, plen, owner, dem, slots), (alpha, rates, _) in zip(
                        group, got):
                    g, o = ref.certify(pe, plen, owner, dem, slots, rates,
                                       alpha)
                    worst = [max(worst[0], g), max(worst[1], o)]
            per_unit.append({
                "verdict_mismatch": sum(bool(a) != bool(b)
                                        for a, b in zip(verdicts, want_v)),
                "cert_gap": worst[0], "overload": worst[1]})
        info = {"servers": self.servers,
                "reference": {"verdicts": want_v,
                              "alpha": [[a for a, _, _ in g] for g in want_a],
                              "iters": [[t for _, _, t in g] for g in want_a]}}
        if units:
            verdicts, answers = units[-1]
            info["program"] = {
                "verdicts": list(map(bool, verdicts)),
                "alpha": [[a for a, _, _ in g] for g in answers],
                "iters": [[t for _, _, t in g] for g in answers]}
        return {"numbers": {"paths_invalid": faults,
                            "paths_mismatch": mismatch},
                "per_unit": per_unit, "info": info}
