"""Fig 8-10: routing + congestion control efficiency.

Fig 8: fluid-MPTCP over k=8 shortest paths vs optimal routing on the SAME
slightly-oversubscribed Jellyfish (paper: 86-90% of optimal; our fluid model
excludes packet-level losses, so we report both the fluid ratio and the
k-restriction-only ratio).
Fig 9/10: servers supported at the fat-tree's per-server throughput
(paper: +25% at the largest scale, with the same MPTCP stack on both)."""

from __future__ import annotations

import numpy as np

from repro.core import (
    build_path_system,
    ecmp_path_system,
    fattree,
    fattree_equipment,
    lp_concurrent_flow,
    mptcp_throughput,
    random_permutation_traffic,
    same_equipment_jellyfish,
)
from repro.sim import fattree_ecmp_check

from .common import FULL, Timer, csv_row, save


def _mptcp_mean(top, seed, k=16):
    # jellyfish side of Fig 9: MPTCP subflows over the k shortest paths
    # (k=16 is deliberately generous so the comparison is not limited by
    # the jellyfish path budget)
    comm = random_permutation_traffic(top, seed=seed)
    return mptcp_throughput(build_path_system(top, comm, k=k), iters=1500).mean_throughput


def _mptcp_mean_fattree(top, ft_k, seed):
    """Fat-tree side of Fig 9: MPTCP over the TRUE ECMP equal-cost sets.

    A k-ary fat-tree offers exactly ``(k/2)^2`` equal-cost paths per
    inter-pod edge-switch pair and ``k/2`` per same-pod pair — asserted
    here from the enumerated ``ecmp_path_system`` rather than assumed by a
    hard-coded ``k=16`` path budget (which was only right for k=8 and
    padded same-pod pairs with longer detour paths ECMP would never use).
    """
    comm = random_permutation_traffic(top, seed=seed)
    ps = ecmp_path_system(top, comm, n_ways=max((ft_k // 2) ** 2, ft_k))
    chk = fattree_ecmp_check(ps, ft_k)
    assert chk["inter_pod_groups_exact"], (
        f"inter-pod ECMP groups {chk['inter_pod_groups']} != "
        f"{chk['expected_inter_pod']}"
    )
    assert chk["same_pod_groups_exact"], (
        f"same-pod ECMP groups {chk['same_pod_groups']} != "
        f"{chk['expected_same_pod']}"
    )
    return mptcp_throughput(ps, iters=1500).mean_throughput


def fig8() -> list[dict]:
    rows = []
    for n_sw, ports, sps in ((40, 10, 4), (80, 12, 4), (120, 14, 5)):
        a_opt, a_mp = [], []
        for seed in range(3):
            top = same_equipment_jellyfish(n_sw, ports, n_sw * sps, seed=seed)
            comm = random_permutation_traffic(top, seed=seed)
            opt = lp_concurrent_flow(
                build_path_system(top, comm, k=24, max_slack=4)
            ).normalized_throughput()
            mp = mptcp_throughput(
                build_path_system(top, comm, k=8), iters=1500
            ).mean_throughput
            a_opt.append(opt)
            a_mp.append(mp)
        rows.append(
            {"n_switches": n_sw, "optimal": float(np.mean(a_opt)),
             "mptcp8": float(np.mean(a_mp)),
             "fraction": float(np.mean(a_mp) / np.mean(a_opt))}
        )
    return rows


def fig9() -> list[dict]:
    rows = []
    ks = (6, 8, 10) if FULL else (6, 8)
    for k in ks:
        eq = fattree_equipment(k)
        ft = fattree(k)
        ft_tp = np.mean([_mptcp_mean_fattree(ft, k, s) for s in range(2)])
        # binary search server count with jf mptcp throughput >= ft's
        lo, hi = eq["servers"] // 2, 2 * eq["servers"]
        while lo < hi:
            mid = (lo + hi + 1) // 2
            top = same_equipment_jellyfish(eq["switches"], k, mid, seed=0)
            tp = np.mean([_mptcp_mean(top, s) for s in range(2)])
            if tp >= ft_tp - 1e-3:
                lo = mid
            else:
                hi = mid - 1
        rows.append(
            {"fattree_k": k, "ft_servers": eq["servers"], "ft_throughput":
             float(ft_tp), "jf_servers": lo, "ratio": lo / eq["servers"]}
        )
    return rows


def run() -> list[str]:
    out = []
    with Timer() as t:
        r8 = fig8()
    for r in r8:
        out.append(
            csv_row(f"fig8_n{r['n_switches']}", 0.0,
                    f"mptcp/opt={r['fraction']:.3f}")
        )
    with Timer() as t9:
        r9 = fig9()
    for r in r9:
        out.append(
            csv_row(f"fig9_k{r['fattree_k']}", 0.0,
                    f"jf={r['jf_servers']}/ft={r['ft_servers']}(x{r['ratio']:.2f})")
        )
    save("fig8_mptcp", {"fig8": r8, "fig9": r9,
                        "seconds": round(t.dt + t9.dt, 2)})
    return out


if __name__ == "__main__":
    print("\n".join(run()))
