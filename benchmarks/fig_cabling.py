"""Paper §6.1 cabling claims for small data centers (~1000 servers):
Jellyfish carries the same server pool with fewer switches (Fig 1c inverse),
hence ~15% fewer cables; the switch-cluster layout keeps runs short.

Verified constructively: a 1024-server Jellyfish on 82% of the fat-tree's
switches still clears full capacity (MW solver alpha >= 1, a LOWER
bound on the LP optimum), with 15% fewer total cables."""

from __future__ import annotations

import numpy as np

from repro.core import (
    build_path_system,
    fattree,
    fattree_equipment,
    mw_concurrent_flow,
    plan_cables,
    random_permutation_traffic,
    same_equipment_jellyfish,
)

from .common import Timer, csv_row, save


def run() -> list[str]:
    out = []
    with Timer() as t:
        k = 16
        ft = fattree(k)
        eq = fattree_equipment(k)  # 1024 servers, 320 switches
        n_sw = int(eq["switches"] * 0.82)
        jf = same_equipment_jellyfish(n_sw, k, eq["servers"], seed=0)
        comm = random_permutation_traffic(jf, seed=0)
        alpha = mw_concurrent_flow(
            build_path_system(jf, comm, k=8), iters=400
        ).alpha
        pf, pj = plan_cables(ft), plan_cables(jf)
        total_ft = pf.n_cables + pf.n_server_cables
        total_jf = pj.n_cables + pj.n_server_cables
    fewer = 1 - total_jf / total_ft
    save("fig_cabling", {
        "fattree": vars(pf), "jellyfish": vars(pj),
        "jf_switches": n_sw, "ft_switches": eq["switches"],
        "jf_alpha_mw_lower_bound": float(alpha),
        "cable_reduction": fewer, "servers": eq["servers"],
        "seconds": round(t.dt, 2),
    })
    ft_switches = eq["switches"]
    out.append(
        csv_row(
            "cabling_1024srv", t.dt * 1e6,
            f"jf_cables={total_jf}/ft={total_ft}(-{fewer:.0%});"
            f"alpha={alpha:.3f};jf_switches={n_sw}/{ft_switches}",
        )
    )
    return out


if __name__ == "__main__":
    print("\n".join(run()))
