"""Fig 1a/1b (bisection-bound curves) + Fig 1c (servers at full capacity).

1a/1b are closed-form (Bollobás bound): equal-cost curves and equipment cost
vs servers at full bisection for commodity port counts.
1c is the measured headline: same switching equipment as a k-ary fat-tree,
binary-search the server count Jellyfish supports at full capacity under
random-permutation traffic with optimal (LP) routing.  The search is
``repro.core.capacity.max_servers_at_full_capacity``.
"""

from __future__ import annotations

import numpy as np

from repro.core import (
    bollobas_bound,
    fattree_equipment,
    max_servers_at_full_capacity,
    set_build_pipeline,
)
from repro.core.routing import clear_routing_cache

from .common import FULL, Timer, csv_row, save


def fig1ab() -> dict:
    curves = {}
    for ports in (24, 32, 48, 64):
        # smallest r with B >= 1 (full bisection) -> server capacity per switch
        for r in range(ports - 1, 0, -1):
            if bollobas_bound(ports, r) >= 1.0:
                break
        curves[ports] = {
            "r_full_bisection": r,
            "servers_per_switch": ports - r,
            # cost curve: switches needed for N servers = N / (k - r)
            "switches_per_1000_servers": 1000.0 / max(ports - r, 1),
            "fattree_switches_per_1000_servers": 1000.0
            * fattree_equipment(ports)["switches"]
            / fattree_equipment(ports)["servers"],
        }
    return curves


def fig1c() -> list[dict]:
    # Each binary-search probe evaluates 3 traffic matrices on one topology;
    # build_path_system's per-topology cache amortizes the APSP/walk-count
    # precompute across them (the batched routing engine is what makes the
    # k = 12/14 fat-tree equivalents — 180-245 switches, reachable only in
    # FULL mode before — routine).  Probes route through the batched-solver
    # bisection driver; at these LP-sized instances the searches stay
    # sequential (wave_levels=1 — speculative waves pay off where MW probes
    # dominate, see kernels_bench mw_batch_* / fig1c_speculative rows).
    rows = []
    ks = (4, 6, 8, 10, 12, 14) if FULL else (4, 6, 8, 10)
    for k in ks:
        eq = fattree_equipment(k)
        with Timer() as t:
            best = max_servers_at_full_capacity(
                eq["switches"], eq["ports_per_switch"],
                lo=eq["servers"] // 2, hi=2 * eq["servers"], seeds=(0,),
            )
        clear_routing_cache()  # probes are done with these topologies
        rows.append(
            {
                "fattree_k": k,
                "fattree_servers": eq["servers"],
                "jellyfish_servers": best,
                "ratio": best / eq["servers"],
                "seconds": round(t.dt, 2),
            }
        )
    return rows


def fig1c_speculative_parity() -> dict:
    """Speculative-wave bisection must land on the sequential search's exact
    server count (the wave only precomputes the probes bisection would
    make); record both answers and wall-clocks for the k=4 equivalent."""
    eq = fattree_equipment(4)
    args = dict(lo=eq["servers"] // 2, hi=2 * eq["servers"], seeds=(0,))
    # both legs rebuild content-identical topologies, so each must start
    # cold — the routing cache is keyed by edge fingerprint and would serve
    # the second leg the first leg's path systems, biasing its wall-clock.
    # An untimed warmup absorbs the process one-time costs (first HiGHS
    # solve, scipy imports) that would otherwise all land on the first leg.
    max_servers_at_full_capacity(eq["switches"], eq["ports_per_switch"], **args)
    clear_routing_cache()
    with Timer() as t_seq:
        seq = max_servers_at_full_capacity(
            eq["switches"], eq["ports_per_switch"], **args
        )
    clear_routing_cache()
    with Timer() as t_wave:
        wave = max_servers_at_full_capacity(
            eq["switches"], eq["ports_per_switch"], wave_levels=2, **args
        )
    clear_routing_cache()
    return {
        "sequential_servers": seq,
        "speculative_servers": wave,
        "identical": seq == wave,
        "sequential_s": round(t_seq.dt, 2),
        "speculative_s": round(t_wave.dt, 2),
    }


def fig1c_pipeline_parity() -> dict:
    """Pipelined/batched builds must land on the sequential-build driver's
    exact server count — the batch builder's bit-exactness contract
    (INVARIANTS.md CT-build) means every probe sees byte-identical path
    systems, so any divergence here is a real defect, not noise.  Records
    both answers and wall-clocks for the k=4 equivalent; the bench ASSERTS
    the identity rather than just reporting it."""
    eq = fattree_equipment(4)
    args = dict(lo=eq["servers"] // 2, hi=2 * eq["servers"], seeds=(0,))
    # cold start per leg, same discipline as fig1c_speculative_parity
    max_servers_at_full_capacity(eq["switches"], eq["ports_per_switch"], **args)
    clear_routing_cache()
    prev = set_build_pipeline(False)
    try:
        with Timer() as t_seq:
            seq = max_servers_at_full_capacity(
                eq["switches"], eq["ports_per_switch"], **args
            )
        clear_routing_cache()
        set_build_pipeline(True)
        with Timer() as t_pipe:
            pipe = max_servers_at_full_capacity(
                eq["switches"], eq["ports_per_switch"], **args
            )
        clear_routing_cache()
    finally:
        set_build_pipeline(prev)
    assert pipe == seq, (
        f"pipelined build driver found {pipe} servers, sequential {seq}"
    )
    return {
        "sequential_servers": seq,
        "pipelined_servers": pipe,
        "identical": seq == pipe,
        "sequential_s": round(t_seq.dt, 2),
        "pipelined_s": round(t_pipe.dt, 2),
    }


def run() -> list[str]:
    ab = fig1ab()
    rows = fig1c()
    spec = fig1c_speculative_parity()
    pipe = fig1c_pipeline_parity()
    save("fig1ab_bisection_curves", ab)
    save("fig1c_servers_at_capacity",
         {"rows": rows, "speculative": spec, "pipeline": pipe})
    out = []
    for r in rows:
        out.append(
            csv_row(
                f"fig1c_k{r['fattree_k']}",
                r["seconds"] * 1e6,
                f"jf={r['jellyfish_servers']}/ft={r['fattree_servers']}"
                f"(x{r['ratio']:.2f})",
            )
        )
    out.append(
        csv_row(
            "fig1c_speculative_parity",
            spec["speculative_s"] * 1e6,
            f"seq={spec['sequential_servers']}"
            f";wave={spec['speculative_servers']}"
            f";identical={spec['identical']}",
        )
    )
    out.append(
        csv_row(
            "fig1c_pipeline_parity",
            pipe["pipelined_s"] * 1e6,
            f"seq={pipe['sequential_servers']}"
            f";pipe={pipe['pipelined_servers']}"
            f";identical={pipe['identical']}"
            f";seq_s={pipe['sequential_s']}",
        )
    )
    return out


if __name__ == "__main__":
    print("\n".join(run()))
