"""Table 1 / Fig 9 (paper §3): ECMP path diversity vs 8-shortest-path routing.

The paper counts, on a 686-server Jellyfish built from the same equipment
as a k=14 fat-tree, the number of distinct paths each link belongs to:
ECMP (one hash-selected path per TCP flow) leaves a large share of links
carrying little or nothing, while 8-shortest-path routing covers
essentially every link.  The fat-tree control shows the expected analytic
equal-cost count — ``(k/2)^2`` paths for every inter-pod edge-switch pair —
so ECMP's failure is a property of the random graph, not of ECMP.

Emitted JSON carries the ranked per-link path counts for both routings
(the paper's Fig 9 axes) plus coverage summaries; the CSV rows are the
bench-smoke tripwire for the diversity claim.
"""

from __future__ import annotations

import numpy as np

from repro.core import (
    build_path_system,
    fattree,
    fattree_equipment,
    random_permutation_traffic,
    same_equipment_jellyfish,
)
from repro.sim import (
    ecmp_path_system,
    fattree_ecmp_check,
    hash_select_rows,
    path_diversity,
)

from .common import Timer, csv_row, save

#: The paper's instance: same switching equipment as a k=14 fat-tree
#: (245 switches x 14 ports), 686 servers.
FT_K = 14


def _hashed_link_counts(ps, salt: int = 0) -> np.ndarray:
    """(E,) distinct hash-selected flow paths crossing each physical link.

    Distinct PATHS, not flows — two flows of one commodity hashing onto the
    same path row add 1, matching the units of ``path_diversity``'s ksp8
    counts this figure compares against.
    """
    rows = np.unique(hash_select_rows(ps, salt=salt))
    E = ps.n_edges
    slots = ps.path_edges[rows]
    hops = slots[slots < 2 * E] % E
    return np.bincount(hops, minlength=E)


def jellyfish_diversity(seed: int = 0) -> dict:
    eq = fattree_equipment(FT_K)
    top = same_equipment_jellyfish(eq["switches"], FT_K, eq["servers"], seed=seed)
    comm = random_permutation_traffic(top, seed=seed)
    ecmp64 = ecmp_path_system(top, comm, n_ways=64)
    ksp8 = build_path_system(top, comm, k=8)
    d64 = path_diversity(ecmp64)
    d8 = path_diversity(ksp8)
    hashed = _hashed_link_counts(ecmp64)
    ksp_counts = d8["paths_per_link_ranked"]
    return {
        "servers": eq["servers"],
        "switches": eq["switches"],
        "links": d8["links_total"],
        # ECMP as deployed: one hash-selected path per server flow
        "ecmp_hashed_coverage": float((hashed > 0).mean()),
        "ecmp_hashed_frac_leq2": float((hashed <= 2).mean()),
        "ecmp_hashed_ranked": np.sort(hashed)[::-1].tolist(),
        # the full equal-cost sets (upper bound on what ECMP could use)
        "ecmp64_set_coverage": d64["coverage"],
        "ecmp64_mean_group": d64["mean_paths_per_commodity"],
        # 8-shortest-path routing (MPTCP uses all of them)
        "ksp8_coverage": d8["coverage"],
        "ksp8_frac_leq2": float((ksp_counts <= 2).mean()),
        "ksp8_ranked": ksp_counts.tolist(),
    }


def fattree_control() -> dict:
    """ECMP group sizes on the fat-tree: the analytic equal-path count."""
    ft = fattree(FT_K)
    comm = random_permutation_traffic(ft, seed=0)
    eps = ecmp_path_system(ft, comm, n_ways=64)
    chk = fattree_ecmp_check(eps, FT_K)
    return {
        "k": FT_K,
        "expected_inter_pod": chk["expected_inter_pod"],
        "inter_pod_groups_exact": chk["inter_pod_groups_exact"],
        "expected_same_pod": chk["expected_same_pod"],
        "same_pod_groups_exact": chk["same_pod_groups_exact"],
    }


def run() -> list[str]:
    out = []
    with Timer() as t:
        jf = jellyfish_diversity()
        ft = fattree_control()
    assert jf["ecmp_hashed_coverage"] < 0.9 * jf["ksp8_coverage"], (
        "diversity claim regressed: ECMP covers "
        f"{jf['ecmp_hashed_coverage']:.3f} of links vs ksp8 "
        f"{jf['ksp8_coverage']:.3f}"
    )
    assert ft["inter_pod_groups_exact"] and ft["same_pod_groups_exact"], (
        "fat-tree ECMP group sizes deviate from the analytic counts"
    )
    out.append(
        csv_row(
            "table1_diversity", t.dt * 1e6,
            f"ecmp_cov={jf['ecmp_hashed_coverage']:.3f} "
            f"ksp8_cov={jf['ksp8_coverage']:.3f} "
            f"ecmp_leq2={jf['ecmp_hashed_frac_leq2']:.3f} "
            f"ft_equal={ft['expected_inter_pod']}",
        )
    )
    save("table1_diversity", {"jellyfish": jf, "fattree": ft,
                              "seconds": round(t.dt, 2)})
    return out


if __name__ == "__main__":
    print("\n".join(run()))
