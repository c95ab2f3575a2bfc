"""Shared helpers for the paper-figure benchmarks.

The Fig 1c capacity search they call lives in ``repro.core.capacity``.
"""

from __future__ import annotations

import json
import pathlib

from repro.core import (
    build_path_system,
    lp_concurrent_flow,
    mw_concurrent_flow,
    mw_concurrent_flow_batch,
    random_permutation_traffic,
)
from repro import env
from repro.core.capacity import wants_mw
from repro.obs.bench import Timer  # noqa: F401 — the one shared bench timer

ART = pathlib.Path(env.read("REPRO_BENCH_OUT"))
FULL = env.read("REPRO_BENCH_FULL")  # bigger sizes
# CI bench-smoke lane: tiny configs (2 sweep sizes, 1 run) so delta-vs-rebuild
# speedup and alpha parity are tracked per PR in minutes, not hours
SMOKE = env.read("REPRO_BENCH_SMOKE")


def save(name: str, payload: dict) -> None:
    ART.mkdir(parents=True, exist_ok=True)
    (ART / f"{name}.json").write_text(json.dumps(payload, indent=1))


def alpha_of(top, seed=0, k=8, slack=3, method="auto", iters=500,
             mw_backend="auto", early_stop=False, target_alpha=None) -> float:
    """Max concurrent flow alpha for a random permutation matrix.

    ``build_path_system`` keeps a per-topology routing cache, so sweeping
    traffic seeds over one topology (``supports_full_capacity``) pays for the
    APSP/walk-count precompute once.  ``mw_backend`` selects the MW solver's
    congestion backend (see repro.kernels.ops.preferred_congestion_backend).

    ``target_alpha`` stops a probe as soon as the exactly-evaluated alpha
    reaches it — what the ``repro.core.max_servers_at_full_capacity``
    bisection passes so "clearly feasible" probes cost a fraction of the
    full iteration budget.  Figure sweeps keep ``early_stop=False`` (the default) so
    reported alphas stay at the fixed-budget quality; only stopping *after*
    the decision threshold is reached can never change a probe's verdict.
    """
    comm = random_permutation_traffic(top, seed=seed)
    ps = build_path_system(top, comm, k=k, max_slack=slack)
    if wants_mw(ps, method):
        return mw_concurrent_flow(
            ps, iters=iters, backend=mw_backend, early_stop=early_stop,
            target_alpha=target_alpha,
        ).alpha
    return lp_concurrent_flow(ps).alpha


def batch_alphas(ps_list, method="auto", iters=500, mw_backend="auto",
                 early_stop=False, target_alpha=None) -> list[float]:
    """Per-instance alpha for many independent path systems.

    Solver selection is PER INSTANCE and identical to ``alpha_of`` (exact
    LP at or below ``repro.core.capacity.MW_MIN_PATHS`` path variables, MW
    above), so the returned alphas match a sequential loop; the MW instances
    are solved in ONE ``mw_concurrent_flow_batch`` call — the sweep
    drivers' way onto the batched solver.
    """
    out = [0.0] * len(ps_list)
    mw_ids = [i for i, ps in enumerate(ps_list) if wants_mw(ps, method)]
    if mw_ids:
        res = mw_concurrent_flow_batch(
            [ps_list[i] for i in mw_ids], iters=iters, backend=mw_backend,
            early_stop=early_stop, target_alpha=target_alpha,
        )
        for i, r in zip(mw_ids, res):
            out[i] = r.alpha
    lp_ids = set(range(len(ps_list))) - set(mw_ids)
    for i in sorted(lp_ids):
        out[i] = lp_concurrent_flow(ps_list[i]).alpha
    return out


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"
