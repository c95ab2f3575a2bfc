"""Unit kind ``sim_events``: a live fabric under a link failure and repair.

Set-up makes ``fabrics`` RRGs and one permutation matrix on each, and
routes them with the program's ECMP (``ecmp_path_system``, the up to
``ecmp_ways`` shortest paths).  Fabrics, matrices and the failed link are
one fixed deployment, drawn from the traffic's ``deployment_seed``: the
largest ECMP group sets the width of the simulator's compiled tables, so
fabrics drawn from ``--seed`` would often compile anew.  ``--seed``
draws the arrival streams.  One unit is the question "what
happens to these flows when a link fails and comes back":
``simulate_events`` over all fabrics at once, steady Poisson arrivals of
fixed-size flows, the traffic's fail/heal schedule, with a per-unit
simulation seed drawn from (seed, unit).  Waterfilling in the device scan
and the host's re-routing and flow migration at event boundaries do the
work; MW is bypassed.

The check (after the window):

* ``paths_invalid`` / ``paths_mismatch``: the set-up's ECMP tables against
  the routing contract and the reference enumeration (as ``mw_batch``).
* ``arrivals_mismatch``: for every instance of every unit, admitted plus
  dropped flows against the Poisson counts of the run's arrival stream,
  replayed with ``jax.random``.  Exact.
* ``offered_mismatch``: every instance's offered volume against the flow
  size times its admitted flows.  Exact.
* ``ledger_gap``: every instance's offered = delivered + blackholed +
  in flight, relative, in float64.
* ``commodity_gap``: for ``check_replays`` (unit, instance) pairs drawn
  from the seed, the float64 reference simulation (``ref_sim.simulate``)
  of the whole run, both events included, against the program: the volume
  delivered to each commodity over the run, summed absolute difference
  over the offered volume.  It holds completions, slot reuse, table-full
  drops and the re-routing at the failure and at the repair, which decide
  whose flows get through.
* Read by ``readings.py`` and not compared, as the control does not read
  three times what sound runs do (PERF.md): ``replay_gap``, the largest
  relative difference of a step's delivered volume before the reference's
  first fragile completion (``ref_sim``), after which float32 and float64
  runs part step by step; ``delivered_gap``, ``fct_gap`` and
  ``admitted_gap`` (whole-run totals); ``phase_gap`` (delivered volume
  between events).

The control (``control_unit``): the reference simulation in bfloat16 in
the program's place.
"""

from __future__ import annotations

import numpy as np

from chipbench import gen, ref, ref_sim


class Cell:
    work_per_unit = None  # set from the traffic: simulated steps per unit
    #: The jitted programs the window drives, for their device footprint.
    window_programs = ("repro.sim.engine._sim_scan",)
    #: The program's commodity envelope (its demand table's width less the
    #: dummy column), which the arrival streams are drawn over.
    k_pad = None

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.core import Topology
        from repro.core.traffic import Commodities
        from repro.sim import ecmp_path_system

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.work_per_unit = traffic["steps"]
        n, ports, r = cfg["switches"], cfg["ports"], cfg["network_ports"]
        dep = traffic["deployment_seed"]
        self.edges, self.inst, self.tops, self.comms = [], [], [], []
        for f in range(traffic["fabrics"]):
            e = gen.rrg_edges(n, r, gen.rng_for(dep, "fabric", f))
            src, dst, dem, nsrv = gen.permutation_pairs(
                n, ports - r, gen.rng_for(dep, "matrix", f, 0))
            self.edges.append(e)
            self.inst.append((src, dst, dem))
            self.tops.append(Topology.regular(n, ports, r, e))
            self.comms.append(Commodities(src, dst, dem, nsrv))
        self.systems = [ecmp_path_system(t, c, n_ways=traffic["ecmp_ways"])
                        for t, c in zip(self.tops, self.comms)]
        ev_seed = int(gen.rng_for(dep, "events").integers(2**31))
        self.schedule = [dict(ev, seed=ev_seed) if "seed" in ev else dict(ev)
                         for ev in traffic["schedule"]]

    def sim_seed(self, i) -> int:
        return int(gen.rng_for(self.seed, "sim", i).integers(2**31))

    def simulate(self, seed: int):
        from repro.sim import Event, SimConfig, simulate_events, steady_poisson

        tr = self.traffic
        sched = [Event(**ev) for ev in self.schedule]
        wl = steady_poisson(tr["steps"], rate=tr["rate"], size=tr["size"])
        cfg = SimConfig(max_flows=tr["max_flows"],
                        max_arrivals=tr["max_arrivals"],
                        wf_iters=tr["wf_iters"], wf_rule=tr["wf_rule"],
                        salt=tr["hash_salt"], bh_rate=tr["bh_rate"])
        return simulate_events(list(self.tops), self.comms, sched, wl,
                               systems=list(self.systems), policy="ecmp",
                               config=cfg, seed=seed, lag=tr["lag"]).result

    def unit(self, i):
        seed = self.sim_seed(i)
        res = self.simulate(seed)
        keys = ("admitted", "drops", "comm_offered", "comm_delivered",
                "blackholed_total", "inflight", "fct_count", "throughput",
                "demands")
        self.k_pad = res.demands.shape[1] - 1
        return seed, {k: np.asarray(getattr(res, k)) for k in keys}

    def control_unit(self, i):
        """A unit answered by the reference simulation in bfloat16, in the
        shape of the program's answer."""
        seed = self.sim_seed(i)
        if self.k_pad is None:
            self.k_pad = self.simulate(seed).demands.shape[1] - 1
        runs = [self.reference(b, seed, self.k_pad, "bf16")
                for b in range(len(self.edges))]
        col = lambda k: np.array([r[k] for r in runs])  # noqa: E731

        def ledger(k):
            return np.stack([np.pad(r[k], (0, self.k_pad + 1 - len(r[k])))
                             for r in runs])

        return seed, {
            "admitted": col("admitted"), "drops": col("drops"),
            "comm_offered": ledger("offered_by_commodity"),
            "comm_delivered": ledger("delivered_by_commodity"),
            "blackholed_total": col("blackholed"), "inflight": col("inflight"),
            "fct_count": col("fct_count"),
            "throughput": np.stack([r["throughput"] for r in runs], axis=1),
            "demands": np.zeros((len(runs), self.k_pad + 1), np.float32),
        }

    def reference(self, b: int, seed: int, k_pad: int, precision="f64"):
        """The reference simulation of instance b of a unit."""
        tr = self.traffic
        src, dst, dem = self.inst[b]
        return ref_sim.simulate(
            self.cfg["switches"], self.edges[b], src, dst, dem, b,
            len(self.edges), k_pad, self.schedule, tr["steps"], tr["rate"],
            tr["size"], self._ref_cfg(), seed, precision)

    def warm(self) -> None:
        """One unit of its own seed: every segment length and event the
        window runs."""
        self.unit("warm")

    def context(self, units) -> dict:
        return {"sim_steps": self.traffic["steps"] * len(units),
                "sims": len(units)}

    def release(self) -> None:
        """Nothing to free: the answers are host arrays."""

    def _ref_cfg(self) -> dict:
        tr = self.traffic
        return {k: tr[k] for k in ("ecmp_ways", "max_flows", "max_arrivals",
                                   "hash_salt", "wf_iters", "lag", "bh_rate")}

    def check(self, units) -> dict:
        tr = self.traffic
        n = self.cfg["switches"]
        B = len(self.tops)
        rng = gen.rng_for(self.seed, "check")
        faults = mismatch = 0
        for e, (src, dst, dem), ps in zip(self.edges, self.inst, self.systems):
            dist = ref.bfs_hops(n, e)
            K = len(src)
            unrouted = 0 if ps.unrouted is None else int(np.sum(ps.unrouted))
            faults += unrouted + abs(int(ps.n_commodities) - K)
            if unrouted or ps.n_commodities != K:
                continue
            faults += ref.path_table_faults(
                ps.path_edges, ps.path_len, ps.path_owner, src, dst, dist,
                len(e), e, tr["ecmp_ways"], 0)
            nbrs = ref.neighbour_lists(n, e)
            head, tail = ref.slot_ends(len(e), e)
            owner = np.asarray(ps.path_owner)
            lo = np.searchsorted(owner, np.arange(K))
            hi = np.searchsorted(owner, np.arange(K), side="right")
            for c in rng.choice(K, min(K, tr["check_pairs"]), replace=False):
                got = ref.decode_rows(ps.path_edges, ps.path_len,
                                      np.arange(lo[c], hi[c]), head, tail)
                want = ref.k_shortest(nbrs, dist, int(src[c]), int(dst[c]),
                                      tr["ecmp_ways"], 0)
                mismatch += got != want
        per_unit = []
        for seed, r in units:
            k_pad = r["demands"].shape[1] - 1
            logits = np.full((B, k_pad), -np.inf, np.float32)
            for b, (_, _, dem) in enumerate(self.inst):
                d32 = np.asarray(dem, np.float32)
                logits[b, : len(d32)] = np.log(np.maximum(d32, np.float32(1e-30)))
            npois, _ = ref_sim.arrival_streams(
                seed, np.full(tr["steps"], tr["rate"], np.float32), B,
                tr["max_arrivals"], logits)
            arrivals = int(np.abs(r["admitted"].astype(np.int64)
                                  + r["drops"] - npois.sum(axis=0)).sum()) \
                if r["admitted"].shape == (B,) else 1 << 30
            off = r["comm_offered"].astype(np.float64).sum(axis=1)
            offered = float(np.abs(off - tr["size"] * r["admitted"]).sum())
            dlv = r["comm_delivered"].astype(np.float64).sum(axis=1)
            led = np.abs(off - dlv - r["blackholed_total"] - r["inflight"])
            ledger = float(np.max(led / np.maximum(off, 1.0)))
            per_unit.append({"arrivals_mismatch": arrivals,
                             "offered_mismatch": offered,
                             "ledger_gap": ledger})
        replay, fragile = {}, []
        if units:
            picks = rng.choice(len(units) * B,
                               min(tr["check_replays"], len(units) * B),
                               replace=False)
            for p in picks.tolist():
                u, b = divmod(p, B)
                gaps, step = self.replay_gaps(units[u], b)
                fragile.append(step)
                for k, v in gaps.items():
                    replay[k] = max(replay.get(k, 0.0), v)
        return {"numbers": {"paths_invalid": faults,
                            "paths_mismatch": mismatch, **replay},
                "per_unit": per_unit,
                "info": {"fragile_steps": fragile}}

    def replay_gaps(self, unit, b: int) -> tuple[dict, int]:
        """Instance b of a unit against its float64 reference simulation:
        the gaps of the module doc, and the reference's fragile step."""
        seed, r = unit
        want = self.reference(b, seed, r["demands"].shape[1] - 1)
        thr = np.asarray(r["throughput"], np.float64)
        K = len(self.inst[b][0])
        if thr.shape[0] != len(want["throughput"]) or b >= thr.shape[1] \
                or r["comm_delivered"].shape[1] < K:
            return {k: float("inf") for k in GAPS}, 0
        thr = thr[:, b]
        ref_thr = want["throughput"]
        t = want["fragile_step"]
        rel = np.abs(thr[:t] - ref_thr[:t]) / np.maximum(ref_thr[:t], 1e-9)
        off = max(want["offered"], 1.0)
        dlv = np.asarray(r["comm_delivered"][b, :K], np.float64)
        cuts = sorted({0, len(thr)} | {int(ev["step"]) for ev in self.schedule})
        phase = [abs(thr[a:z].sum() / max(ref_thr[a:z].sum(), 1e-9) - 1.0)
                 for a, z in zip(cuts[:-1], cuts[1:]) if z > a]
        gaps = {
            "replay_gap": float(rel.max()) if t else 0.0,
            "commodity_gap": float(np.abs(
                dlv - want["delivered_by_commodity"]).sum() / off),
            "delivered_gap": abs(float(dlv.sum()) - want["delivered"]) / off,
            "fct_gap": abs(int(r["fct_count"][b]) - want["fct_count"])
            / max(want["fct_count"], 1),
            "admitted_gap": abs(int(r["admitted"][b]) - want["admitted"])
            / max(want["admitted"], 1),
            "phase_gap": float(max(phase)),
        }
        return gaps, int(t)


GAPS = ("replay_gap", "commodity_gap", "delivered_gap", "fct_gap",
        "admitted_gap", "phase_gap")
