"""Servers at full capacity on fixed equipment (paper §4.1, Fig 1c).

The paper's headline question: how many servers does a Jellyfish built
from a given pool of switches carry at full capacity under random
permutation traffic?  ``max_servers_at_full_capacity`` binary-searches the
server count; each probe spreads m servers over the switches
(``same_equipment_jellyfish``: floor per switch, one more on the lowest
ids, the remaining ports wired as a random graph), routes random server
permutations over k shortest paths within 3 hops of slack, and accepts m
when every matrix reaches throughput alpha >= 1 - tol.

Each matrix is decided by the exact LP (``lp_concurrent_flow``) while its
path system has at most ``MW_MIN_PATHS`` path variables, short-circuiting
at the first infeasible one, and by the batched MW solver beyond.  The MW
matrices of a probe, or of every probe of a speculative wave
(``wave_levels > 1``, ``core.bisection.speculative_max_feasible``), go
through ONE ``probe_wave`` call: one ``mw_concurrent_flow_batch`` with
``target_alpha=1.0``, so a probe that already carries full load stops at
the next window check while the rest run the whole budget.

Traced (``repro.obs``), each wave is a ``capacity/wave`` span (attributes
``candidates``, ``instances``, ``accepted``), and the counters
``capacity/accepted`` and ``capacity/rejected`` count the wave verdicts.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from .. import env
from .. import obs
from .bisection import max_feasible, speculative_max_feasible
from .buildpipe import pipeline_enabled, stream_builds
from .flow import (
    LP_PATH_LIMIT,
    FlowResult,
    lp_concurrent_flow,
    mw_concurrent_flow_batch,
)
from .jellyfish import jellyfish_heterogeneous
from .routing import PathSystem, build_path_system, build_path_system_batch
from .topology import Topology
from .traffic import random_permutation_traffic

__all__ = [
    "MW_MIN_PATHS",
    "wants_mw",
    "spread_servers",
    "same_equipment_jellyfish",
    "probe_wave",
    "supports_full_capacity",
    "max_servers_at_full_capacity",
]

#: LP-vs-MW dispatch of the probes (and of the figure sweeps' alphas): the
#: exact LP at or below this many path variables, the MW solver beyond
#: (single-core HiGHS needs minutes much past ~10k variables).  It sits
#: deliberately ABOVE flow.LP_PATH_LIMIT's 20000: sweep alphas are reported
#: figure numbers, so they hold onto the exact LP a bit longer than
#: interactive ``throughput()`` callers would tolerate.  Setting
#: REPRO_LP_PATH_LIMIT (validated at flow import) steers BOTH cutoffs to
#: the same value.
MW_MIN_PATHS = (
    LP_PATH_LIMIT if env.is_set("REPRO_LP_PATH_LIMIT") else 30000
)


def wants_mw(ps: PathSystem, method: str) -> bool:
    """The single LP-vs-MW dispatch predicate every probe and sweep shares."""
    return method == "mw" or (method == "auto" and ps.n_paths > MW_MIN_PATHS)


def spread_servers(total: int, n_switches: int) -> np.ndarray:
    """Servers per switch: ``total // n`` each, one more on the lowest ids."""
    per = total // n_switches
    extra = total - per * n_switches
    servers = np.full(n_switches, per, dtype=np.int64)
    servers[:extra] += 1
    return servers


def same_equipment_jellyfish(n_switches: int, ports: int, n_servers: int,
                             seed=0) -> Topology:
    """Jellyfish on ``n_switches`` identical ``ports``-port switches hosting
    ``n_servers`` (``spread_servers``); the remaining ports are links."""
    return jellyfish_heterogeneous(
        np.full(n_switches, ports), spread_servers(n_servers, n_switches),
        seed=seed,
    )


def _probe_systems(top, n_matrices, k):
    """One probe's path systems, traffic seeds 0..n_matrices-1, slack=3.

    With the build pipeline enabled (``REPRO_BUILD_PIPELINE``, default on)
    all of a probe's matrices build as ONE ``build_path_system_batch`` —
    one combined frontier pass instead of n_matrices separate ones.  The
    batch builder's bit-exactness contract (INVARIANTS.md CT-build) makes
    the returned systems byte-identical to the sequential loop, so every
    downstream verdict is unchanged.
    """
    if pipeline_enabled():
        comms = [
            random_permutation_traffic(top, seed=s) for s in range(n_matrices)
        ]
        batch = build_path_system_batch(
            [top] * n_matrices, comms, k=k, max_slack=3
        )
        return list(batch.systems)
    # lazy fallback: the LP short-circuit in _probe_verdict stops building
    # the moment a matrix rejects the probe, exactly as the pre-pipeline
    # driver did
    return (
        build_path_system(
            top, random_permutation_traffic(top, seed=s), k=k, max_slack=3
        )
        for s in range(n_matrices)
    )


def _probe_verdict(systems, tol, method):
    """The probe body shared by the sequential and wave drivers — ONE copy,
    so their per-(candidate, seed, matrix) decisions cannot drift apart
    (the speculative search's "identical server count" contract rides on
    that).

    LP-sized matrices verdict sequentially with a short-circuit (the first
    infeasible one settles the probe); MW-sized ones are returned for the
    caller to fold into one ``probe_wave``.  Returns ``(lp_ok, mw_systems)``.
    """
    mw_systems = []
    for ps in systems:
        if wants_mw(ps, method):
            mw_systems.append(ps)
        elif lp_concurrent_flow(ps).alpha < 1.0 - tol:
            return False, mw_systems
    return True, mw_systems


def probe_wave(
    candidates: Sequence[Sequence[PathSystem]], iters: int = 500,
    tol: float = 1e-6, mw_backend: str = "auto",
) -> tuple[list[bool], list[list[FlowResult]]]:
    """MW verdicts of a wave of probes, in ONE batched solve.

    ``candidates[c]`` holds candidate c's routed path systems (one per
    topology seed and traffic matrix).  All of them run as one
    ``mw_concurrent_flow_batch(..., target_alpha=1.0)`` call: an instance
    freezes at the first window check (every 50 iterations) where its best
    alpha reaches 1.0, the rest run the whole ``iters`` budget (no plateau
    stop, so a probe crawling toward 1.0 is not cut short).  Candidate c is
    accepted when every one of its instances reaches alpha >= 1 - tol.

    Returns ``(verdicts, results)``: one bool per candidate and, per
    candidate, the ``FlowResult`` of each of its systems.
    """
    flat = [ps for group in candidates for ps in group]
    with obs.span("capacity/wave", candidates=len(candidates),
                  instances=len(flat)) as sp:
        res = (mw_concurrent_flow_batch(flat, iters=iters, target_alpha=1.0,
                                        backend=mw_backend) if flat else [])
        verdicts, results, i = [], [], 0
        for group in candidates:
            mine = res[i:i + len(group)]
            i += len(group)
            results.append(mine)
            verdicts.append(all(r.alpha >= 1.0 - tol for r in mine))
        n_ok = sum(verdicts)
        sp.set(accepted=n_ok)
    obs.counter("capacity/accepted").inc(n_ok)
    obs.counter("capacity/rejected").inc(len(verdicts) - n_ok)
    return verdicts, results


def supports_full_capacity(top, n_matrices=3, k=8, tol=1e-6,
                           method="auto", iters=500,
                           mw_backend="auto") -> bool:
    """One probe: does ``top`` carry every server at full rate under
    ``n_matrices`` random permutations (traffic seeds 0..n_matrices-1)?"""
    lp_ok, mw_systems = _probe_verdict(_probe_systems(top, n_matrices, k),
                                       tol, method)
    if not lp_ok:
        return False
    if mw_systems:
        return probe_wave([mw_systems], iters=iters, tol=tol,
                          mw_backend=mw_backend)[0][0]
    return True


def max_servers_at_full_capacity(
    n_switches: int, ports: int, lo: int, hi: int, seeds=(0,), k=8,
    wave_levels: int = 1, method: str = "auto", n_matrices: int = 3,
    tol: float = 1e-6, iters: int = 500, mw_backend: str = "auto",
) -> int:
    """Binary search (paper §4 methodology) for the largest server count the
    equipment supports at full capacity, validated across topology seeds.

    ``lo`` must be a count the equipment supports; everything above ``hi``
    is taken as rejected.

    ``wave_levels > 1`` probes speculatively: each wave evaluates every
    candidate the next ``wave_levels`` bisection steps could ask about
    (``core.bisection.speculative_max_feasible``), batching all of the
    wave's MW-sized (candidate x topology seed x traffic matrix) solves
    into one ``probe_wave``.  The per-candidate verdict is the same
    conjunction over the same per-instance solvers (``_probe_verdict`` is
    the shared probe body), so the final server count is identical to the
    sequential search; only the wall-clock shrinks (by ~2x at
    ``wave_levels=2`` where MW probes dominate).  LP-sized probes keep the
    sequential short-circuit inside each candidate.

    Caveat: the identity is exact under the order-preserving congestion
    backends (gather/scatter — every CPU batch).  On TPU, ``auto`` sizes
    the dense-kernel budget by the WHOLE stack, and the wave's larger
    batches can resolve a different backend than the sequential probes'
    smaller ones; dense reassociates (~1e-4 alpha drift), so a probe
    sitting within that of the 1.0 threshold could flip.  Pass an explicit
    ``mw_backend`` ("scatter") there if strict wave==sequential identity
    matters more than the fused-kernel speed.
    """

    def ok(m: int) -> bool:
        for seed in seeds:
            top = same_equipment_jellyfish(n_switches, ports, m, seed=seed)
            if not supports_full_capacity(top, n_matrices=n_matrices, k=k,
                                          tol=tol, method=method, iters=iters,
                                          mw_backend=mw_backend):
                return False
        return True

    if wave_levels <= 1:
        return max_feasible(lo, hi, ok)

    def ok_batch(candidates):
        verdicts = [True] * len(candidates)
        mw_systems = [[] for _ in candidates]
        # one build unit per (candidate, seed); with the pipeline enabled
        # stream_builds prefetches unit i+1 on the background worker while
        # the consumer runs unit i's LP verdicts, so host enumeration
        # overlaps the probe solves.  Results arrive in submission order,
        # so the verdict fold below is the sequential loop verbatim.
        tasks = [(ci, m, seed) for ci, m in enumerate(candidates)
                 for seed in seeds]

        def build_thunk(m, seed):
            def thunk():
                top = same_equipment_jellyfish(n_switches, ports, m,
                                               seed=seed)
                return _probe_systems(top, n_matrices, k)
            return thunk

        stream = stream_builds(build_thunk(m, seed) for _, m, seed in tasks)
        for (ci, m, seed), systems in zip(tasks, stream):
            if not verdicts[ci]:
                continue  # an earlier LP matrix rejected this candidate
            lp_ok, mws = _probe_verdict(systems, tol, method)
            mw_systems[ci].extend(mws)
            if not lp_ok:
                verdicts[ci] = False
        # LP-rejected candidates' MW systems are dead weight: solving them
        # burns a full target_alpha=1.0 budget and inflates the batch's
        # common padding envelope for the surviving probes
        live = [ci for ci in range(len(candidates))
                if verdicts[ci] and mw_systems[ci]]
        if live:
            wave, _ = probe_wave([mw_systems[ci] for ci in live], iters=iters,
                                 tol=tol, mw_backend=mw_backend)
            for ci, v in zip(live, wave):
                verdicts[ci] = v
        return verdicts

    return speculative_max_feasible(lo, hi, ok_batch, levels=wave_levels)
