#!/usr/bin/env python3
"""Run the routing, MW and sim paths once on one TPU, at fabric sizes
operators run, and check every answer.

    python chip_smoke.py

Phases, all in this one process (a child could not reach the chip):

  A  routing: ``build_path_system_batch`` over 4 permutation matrices on
     jellyfish(2048, 48, 36) (24,576 servers), k=8, slack 3.  APSP is
     ``auto``, which on a TPU is the tiled min-plus Pallas driver; its int16
     distances must equal the host BFS (``apsp_hops_blocked``) exactly.
  B  batched MW: ``mw_concurrent_flow_batch(iters=400)`` on A's 4 systems;
     every instance's rates must load no slot past its capacity.
  C  fused congestion kernel: 4 seeds of jellyfish(512, 24, 18), where
     ``auto`` runs the dense Pallas kernel.  Its fused products at the real
     stacked shapes must match a float64 host sum to 1e-5; solved by
     ``auto`` and by ``scatter``, per-instance alpha must agree within 1e-3
     relative; the full solve must be feasible.
  D  reference: MW on the same-equipment Jellyfish of a k=14 fat-tree (686
     servers) must lie at or below the LP optimum, within 2% of it.
  E  sim: ``simulate_events`` with ECMP on 8 seeds of RRG(512, 24, 18) with
     one link failure and its repair; offered = delivered + blackholed +
     inflight must hold per instance.

For each Pallas kernel a phase relies on, the compiled program at the
phase's shapes must hold a ``tpu_custom_call``: the kernel ran compiled,
not interpreted and not as its jnp oracle.  Earlier lines report per phase
the resolved backends, the checks, and wall time with XLA compile time
split out (host clock, informational).  The last line is one JSON object
naming the device.  With no TPU the script exits non-zero before any phase.
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

#: Real sizes (the driver's run).  Rehearsals import this module and pass
#: smaller dicts of the same keys to the phase functions.
FULL = {
    "route_n": 2048, "route_ports": 48, "route_r": 36, "route_seeds": 4,
    "k": 8, "max_slack": 3, "mw_iters": 400,
    "dense_n": 512, "dense_ports": 24, "dense_r": 18, "dense_seeds": 4,
    "dense_alpha_iters": 50,
    "ref_fattree_k": 14, "ref_iters": 1000,
    "sim_n": 512, "sim_ports": 24, "sim_r": 18, "sim_seeds": 8,
    "sim_steps": 160, "sim_fail_step": 48, "sim_heal_step": 112,
    "sim_rate": 24.0, "sim_size": 48.0, "sim_max_flows": 2048,
    "sim_max_arrivals": 32, "sim_wf_iters": 10,
}


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def assert_tpu_kernel(jitted, *shapes) -> None:
    """The compiled program of ``jitted`` for f32 arguments of these shapes
    holds a Mosaic kernel: the Pallas kernel runs compiled, not interpreted
    or as a ref.  Shapes, not arrays: nothing is allocated on the device."""
    import jax
    import jax.numpy as jnp

    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    text = jitted.lower(*args).compile().as_text()
    check("tpu_custom_call" in text,
          f"no tpu_custom_call in the compiled {getattr(jitted, '__name__', jitted)}")


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


class PhaseClock:
    """Wall time of a phase and the XLA compile seconds spent inside it."""

    def __init__(self) -> None:
        from repro.analysis.retrace import install_compile_listener
        from repro.obs import metrics

        install_compile_listener()
        self.compile_s = 0.0
        self.compiles = 0
        metrics.subscribe(self._on_event)

    def _on_event(self, name: str, **attrs) -> None:
        if name == "xla/backend_compile":
            self.compile_s += float(attrs.get("duration_s", 0.0))
            self.compiles += 1

    def run(self, label: str, fn, *args):
        c0, n0, t0 = self.compile_s, self.compiles, time.perf_counter()
        out = fn(*args)
        wall = time.perf_counter() - t0
        out.update(wall_s=wall, compile_s=self.compile_s - c0,
                   compiles=self.compiles - n0, check="passed")
        print(f"phase {label}: " + json.dumps(out), flush=True)
        return out


def phase_routing(sz: dict, kernel_check, state: dict) -> dict:
    """A: batched path-system build at real size; APSP parity with BFS."""
    import numpy as np

    from repro.core import (apsp_hops_blocked, build_path_system_batch,
                            jellyfish, random_permutation_traffic)
    from repro.core import routing
    from repro.kernels.minplus import minplus_pallas

    top = jellyfish(sz["route_n"], sz["route_ports"], sz["route_r"], seed=0)
    comms = [random_permutation_traffic(top, seed=s)
             for s in range(sz["route_seeds"])]
    kernel_calls = minplus_pallas._cache_size()
    t0 = time.perf_counter()
    batch = build_path_system_batch([top] * len(comms), comms,
                                    k=sz["k"], max_slack=sz["max_slack"])
    build_s = time.perf_counter() - t0
    ran_kernel = minplus_pallas._cache_size() > kernel_calls
    dist = routing._cached_dist(top, routing._topo_entry(top))
    want = apsp_hops_blocked(top.adjacency())
    check(dist.dtype == np.int16 and np.array_equal(dist, want),
          "APSP int16 distances differ from the host BFS")
    for ps in batch.systems:
        check(ps.unrouted is None or not ps.unrouted.any(),
              "a commodity of a connected fabric went unrouted")
    if _on_tpu():
        check(ran_kernel, "APSP did not run the min-plus Pallas kernel")
        kernel_check(minplus_pallas, (512, 512), (512, 512))
    state["route_batch"] = batch
    return {
        "fabric": f"jellyfish({sz['route_n']},{sz['route_ports']},{sz['route_r']})",
        "servers": int(top.servers_per_switch.sum()),
        "instances": batch.n_batch, "paths": [int(p) for p in batch.n_paths],
        "apsp": "minplus_pallas" if ran_kernel else "host BFS",
        "build_s": build_s,
        "diameter": int(dist[dist != np.iinfo(np.int16).max].max()),
    }


def _feasible(systems, results) -> float:
    """Largest slot load over capacity across the instances."""
    worst = 0.0
    for ps, r in zip(systems, results):
        over = ps.loads(r.rates) / ps.capacities
        worst = max(worst, float(over.max()))
    return worst


def phase_mw_batch(sz: dict, kernel_check, state: dict) -> dict:
    """B: batched MW on phase A's systems; every instance feasible."""
    import numpy as np

    from repro.core import mw_concurrent_flow_batch

    batch = state["route_batch"]
    res = mw_concurrent_flow_batch(batch, iters=sz["mw_iters"])
    alphas = [r.alpha for r in res]
    check(all(np.isfinite(a) and a > 0 for a in alphas),
          f"non-positive or non-finite alpha: {alphas}")
    worst = _feasible(batch.systems, res)
    check(worst <= 1.0 + 1e-6, f"MW rates overload a slot: {worst}")
    return {"backend": res[0].method, "iters": sz["mw_iters"],
            "alpha": alphas, "max_load_over_cap": worst}


def fused_parity(batch, congestion) -> float:
    """Normwise relative error of ``congestion(b3, rates, prices)``, the
    fused (loads, costs) over the batch's stacked dense incidence, against a
    float64 host sum over its path table, for random rates and prices.

    A loads block summed from a stale buffer is off by O(1), a single bf16
    MXU pass by ~1e-3; f32 accumulation stays near 1e-7.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.flow import dense_incidence

    pe = np.asarray(batch.path_edges)
    nb, _, hops = pe.shape
    slots = batch.s_max
    valid = np.asarray(batch.slot_valid)
    rng = np.random.default_rng(0)
    r = rng.uniform(size=pe.shape[:2]).astype(np.float32)
    # padding sentinels hit padded slots: zero price there, loads unchecked
    w = (rng.uniform(size=(nb, slots)) * valid).astype(np.float32)
    b3 = jax.jit(jax.vmap(lambda p: dense_incidence(p, slots)))(pe)
    loads, costs = (np.asarray(x, np.float64)
                    for x in congestion(b3, jnp.asarray(r), jnp.asarray(w)))
    del b3
    err = 0.0
    for b in range(nb):
        want_l = np.bincount(pe[b].ravel(), minlength=slots + 1,
                             weights=np.repeat(r[b].astype(np.float64),
                                               hops))[:slots][valid[b]]
        want_c = np.append(w[b].astype(np.float64), 0.0)[pe[b]].sum(axis=1)
        err = max(err,
                  np.abs(loads[b][valid[b]] - want_l).max() / want_l.max(),
                  np.abs(costs[b] - want_c).max() / want_c.max())
    return float(err)


def phase_dense(sz: dict, kernel_check, state: dict) -> dict:
    """C: the fused congestion kernel in MW against the scatter backend.

    Three checks: the fused products at the real stacked shapes against a
    float64 host sum; α of dense and scatter solves of ``dense_alpha_iters``
    iterations within 1e-3; and feasibility of the full ``mw_iters`` dense
    solve.  α is compared over the short solve because the MW anneal
    amplifies ulp-level differences between the two backends' sums: over
    hundreds of iterations their trajectories part by ~1e-3 in α on any
    platform (``tests/test_flow_batch.py``).
    """
    import numpy as np

    from repro.core import (build_path_system_batch, jellyfish,
                            mw_concurrent_flow_batch,
                            random_permutation_traffic)
    from repro.kernels import ops
    from repro.kernels.congestion import congestion_pallas

    tops = [jellyfish(sz["dense_n"], sz["dense_ports"], sz["dense_r"], seed=s)
            for s in range(sz["dense_seeds"])]
    comms = [random_permutation_traffic(t, seed=s) for s, t in enumerate(tops)]
    batch = build_path_system_batch(tops, comms, k=sz["k"],
                                    max_slack=sz["max_slack"])
    parity = fused_parity(batch, ops.congestion)
    check(parity <= 1e-5, f"fused congestion products off by {parity}")
    short = sz["dense_alpha_iters"]
    auto = mw_concurrent_flow_batch(batch, iters=short)
    scat = mw_concurrent_flow_batch(batch, iters=short, backend="scatter")
    a = np.array([r.alpha for r in auto])
    s = np.array([r.alpha for r in scat])
    rel = np.abs(a - s) / np.maximum(np.abs(s), 1e-12)
    check(bool(np.all(rel <= 1e-3)), f"dense vs scatter alpha differ: {rel}")
    full = mw_concurrent_flow_batch(batch, iters=sz["mw_iters"])
    worst = _feasible(batch.systems, full)
    check(worst <= 1.0 + 1e-6, f"dense MW rates overload a slot: {worst}")
    shape = (batch.n_batch, batch.p_max, batch.s_max)
    if _on_tpu():
        check(full[0].method == "mw-batch-dense",
              f"auto ran {full[0].method}, not the dense kernel")
        kernel_check(congestion_pallas, shape, shape[:2],
                     (shape[0], shape[2]))
    return {"backend_auto": full[0].method, "backend_ref": scat[0].method,
            "incidence": list(shape),
            "incidence_gib": 4 * int(np.prod(shape)) / 2**30,
            "fused_rel_err": parity, "alpha_iters": short,
            "alpha_auto": a.tolist(), "alpha_scatter": s.tolist(),
            "max_rel_diff": float(rel.max()), "iters": sz["mw_iters"],
            "alpha": [r.alpha for r in full], "max_load_over_cap": worst}


def phase_reference(sz: dict, kernel_check, state: dict) -> dict:
    """D: MW against the LP on the same-equipment Jellyfish of a fat-tree."""
    import numpy as np

    from repro.core import (build_path_system, fattree_equipment,
                            jellyfish_heterogeneous, lp_concurrent_flow,
                            mw_concurrent_flow, random_permutation_traffic)
    from repro.kernels.congestion import congestion_pallas

    eq = fattree_equipment(sz["ref_fattree_k"])
    servers = np.full(eq["switches"], eq["servers"] // eq["switches"])
    servers[: eq["servers"] - servers.sum()] += 1
    top = jellyfish_heterogeneous(
        np.full(eq["switches"], eq["ports_per_switch"]), servers, seed=0)
    comm = random_permutation_traffic(top, seed=0)
    ps = build_path_system(top, comm, k=sz["k"])
    mw = mw_concurrent_flow(ps, iters=sz["ref_iters"])
    lp = lp_concurrent_flow(ps)
    check(mw.alpha <= lp.alpha * (1 + 1e-6),
          f"MW alpha {mw.alpha} above the LP optimum {lp.alpha}")
    check(mw.alpha >= 0.98 * lp.alpha,
          f"MW alpha {mw.alpha} more than 2% below the LP {lp.alpha}")
    if mw.method == "mw-dense" and _on_tpu():
        shape = (ps.n_paths, ps.capacities.shape[0])
        kernel_check(congestion_pallas, shape, shape[:1], shape[1:])
    return {"servers": int(servers.sum()), "switches": eq["switches"],
            "paths": ps.n_paths, "backend": mw.method,
            "alpha_mw": mw.alpha, "alpha_lp": lp.alpha,
            "gap": 1.0 - mw.alpha / lp.alpha}


def phase_sim(sz: dict, kernel_check, state: dict) -> dict:
    """E: ECMP sim with a link failure and its repair; volume conserved."""
    import numpy as np

    from repro.core import jellyfish, random_permutation_traffic
    from repro.core.flow import PathSystemBatch
    from repro.kernels.congestion import congestion_pallas
    from repro.sim import (Event, SimConfig, ecmp_path_system,
                           simulate_events, steady_poisson)

    tops = [jellyfish(sz["sim_n"], sz["sim_ports"], sz["sim_r"], seed=s)
            for s in range(sz["sim_seeds"])]
    comms = [random_permutation_traffic(t, seed=s) for s, t in enumerate(tops)]
    systems = [ecmp_path_system(t, c) for t, c in zip(tops, comms)]
    sched = [
        Event(step=sz["sim_fail_step"], kind="fail_links", n_links=1, seed=1,
              tag="link"),
        Event(step=sz["sim_heal_step"], kind="heal_links", heal_of="link"),
    ]
    wl = steady_poisson(sz["sim_steps"], rate=sz["sim_rate"],
                        size=sz["sim_size"])
    cfg = SimConfig(max_flows=sz["sim_max_flows"],
                    max_arrivals=sz["sim_max_arrivals"],
                    wf_iters=sz["sim_wf_iters"])
    ev = simulate_events(tops, comms, sched, wl, systems=systems,
                         policy="ecmp", config=cfg, seed=0)
    res = ev.result
    off = res.comm_offered.sum(axis=1, dtype=np.float64)
    dlv = res.comm_delivered.sum(axis=1, dtype=np.float64)
    err = np.abs(off - (dlv + res.blackholed_total + res.inflight))
    check(bool(np.all(err <= 1e-3 * np.maximum(off, 1.0))),
          f"conservation ledger broken: {err.tolist()}")
    check(bool(np.all(dlv > 0)), "an instance delivered nothing")
    check([r["step"] for r in ev.events] ==
          [sz["sim_fail_step"], sz["sim_heal_step"]],
          f"events applied at {[r['step'] for r in ev.events]}")
    if res.backend in ("dense", "pallas"):
        b = PathSystemBatch.from_systems(systems)
        shape = (b.n_batch, b.p_max, b.s_max)
        kernel_check(congestion_pallas, shape, shape[:2],
                     (shape[0], shape[2]))
    return {"instances": len(tops), "steps": sz["sim_steps"],
            "backend": res.backend,
            "offered": off.tolist(), "delivered": dlv.tolist(),
            "blackholed": np.asarray(res.blackholed_total).tolist(),
            "inflight": np.asarray(res.inflight).tolist(),
            "max_ledger_err": float(err.max())}


PHASES = [
    ("A routing", phase_routing),
    ("B mw_batch", phase_mw_batch),
    ("C dense_kernel", phase_dense),
    ("D mw_vs_lp", phase_reference),
    ("E sim_events", phase_sim),
]


def run_phases(sz: dict, kernel_check) -> list[dict]:
    clock = PhaseClock()
    state: dict = {}
    return [clock.run(label, fn, sz, kernel_check, state)
            for label, fn in PHASES]


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache}", flush=True)
    t0 = time.perf_counter()
    phases = run_phases(FULL, assert_tpu_kernel)
    total_compile = sum(p["compile_s"] for p in phases)
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s "
          f"({total_compile:.1f}s compiling)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
