"""Fig 3: Jellyfish vs Small-World Datacenter lattices (ring / 2D torus /
3D hex torus), same equipment, 2 servers per switch (paper methodology:
1 server saturates nobody, 2 separates the designs)."""

from __future__ import annotations

import numpy as np

from repro.core import (
    jellyfish_heterogeneous,
    spread_servers,
    swdc_hex3d,
    swdc_ring,
    swdc_torus2d,
)

from .common import FULL, Timer, alpha_of, csv_row, save

SIDE = 22 if FULL else 14  # torus side; ring/jf sized to match (N = side^2)


def run() -> list[str]:
    n = SIDE * SIDE
    sps = 2
    ports = 6 + sps
    builders = {
        "swdc-ring": lambda s: swdc_ring(n, ports, seed=s),
        "swdc-torus2d": lambda s: swdc_torus2d(SIDE, ports, seed=s),
        "swdc-hex3d": lambda s: swdc_hex3d(
            6, max(n // 36, 1), ports, seed=s
        ),
        "jellyfish": lambda s: jellyfish_heterogeneous(
            np.full(n, ports), spread_servers(n * sps, n), seed=s
        ),
    }
    rows, out = {}, []
    for name, build in builders.items():
        with Timer() as t:
            tops = [build(s) for s in range(3)]
            # hex3d may have a different N (closest well-formed size, like the
            # paper's 450-node hex vs 484 others)
            a = float(np.mean([alpha_of(tp, seed=s) for s, tp in enumerate(tops)]))
        rows[name] = {"alpha": a, "n": tops[0].n_switches,
                      "seconds": round(t.dt, 2)}
        out.append(csv_row(f"fig3_{name}", t.dt * 1e6, f"alpha={a:.3f}"))
    best_swdc = max(v["alpha"] for k, v in rows.items() if k != "jellyfish")
    rows["jellyfish_vs_best_swdc"] = rows["jellyfish"]["alpha"] / best_swdc
    out.append(
        csv_row("fig3_ratio", 0.0,
                f"jf/best_swdc={rows['jellyfish_vs_best_swdc']:.3f}")
    )
    save("fig3_swdc", rows)
    return out


if __name__ == "__main__":
    print("\n".join(run()))
