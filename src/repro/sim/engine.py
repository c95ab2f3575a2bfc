"""Batched fluid flow-level simulator (the paper's §3/Fig 9 time domain).

One jitted ``lax.scan`` advances B independent network instances —
different topology seeds, different routings, ragged shapes padded through
``core.flow.PathSystemBatch``'s masked envelope — through discrete time:

1. **Arrivals** (open loop): per step and instance, ``Poisson(rate_t)`` new
   flows (capped at ``SimConfig.max_arrivals``) sample a commodity from the
   demand distribution and a size from the elephant/mice mixture, then pick
   a path by policy — ``ecmp`` (the deterministic integer-mixing
   ``sim.ecmp.flow_hash`` over the commodity's equal-cost set), ``ksp_lc``
   (least-congested of the k candidate paths under the previous step's link
   loads — flow-level adaptive routing), or ``mptcp`` (one subflow per
   candidate path, size split evenly).
2. **Rate allocation**: iterative max-min waterfilling over path rows with
   flow multiplicities.  Flows sharing a path row are symmetric, so the
   allocator works on (B, P) per-path-row flow counts, and its link-load
   inner loop is the MW solver's congestion primitive's load half — via
   ``core.flow.make_loads_fn_batch``: transposed ``gather`` fan-in tables
   on CPU, ``kernels.ops.congestion_loads`` (the fused rank-3
   ``congestion_pallas`` pass) on TPU.  Each round freezes the flows
   bottlenecked at the minimum fair share (``SimConfig.wf_rule``:
   ``"fast"`` = global minimum, ``"exact"`` = every locally-minimal link —
   see ``_waterfill_core``), so at convergence every flow is limited by a
   saturated link (the max-min certificate the tests assert).
3. **Departures**: flows drain ``rate * dt`` of their remaining size;
   completions record FCT (log2-binned histogram + exact sum/count),
   per-commodity delivered volume, and free their slot.

The whole horizon is ONE ``lax.scan`` — no per-seed or per-step Python in
the hot path — so simulating 8+ seeds of RRG(512, 24, 18) concurrently is a
single XLA computation (see ``benchmarks/fig9_ecmp.py``'s ``ecmp_sim_512``
row for the measured steady-state step cost).

``REPRO_SIM_MAX_STEPS`` / ``REPRO_SIM_MAX_BATCH`` cap the scan length and
batch width (guarding against accidental multi-hour compiles); both are
validated at import with clear ``ValueError``s, mirroring
``REPRO_APSP_BACKEND`` / ``REPRO_LP_PATH_LIMIT``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .. import env
from ..analysis.contracts import check_sim_state, checks_enabled
from ..analysis.registry import AuditCase, solver_jit
from ..core.flow import (
    PathSystem,
    PathSystemBatch,
    _fold_sum,
    make_loads_fn_batch,
)
from .ecmp import flow_hash

__all__ = [
    "POLICIES",
    "SIM_MAX_STEPS",
    "SIM_MAX_BATCH",
    "SimConfig",
    "SimResult",
    "simulate",
    "waterfill_rates",
]


#: Hard cap on a single scan's step count (compile + unrolled-carry guard).
#: Validated ONCE at import through the repro.env registry: a typo must
#: fail loudly at startup, not silently fall back mid-sweep.
SIM_MAX_STEPS = env.read("REPRO_SIM_MAX_STEPS")
#: Hard cap on the instance batch width of one scan.
SIM_MAX_BATCH = env.read("REPRO_SIM_MAX_BATCH")

POLICIES = ("ecmp", "ksp_lc", "mptcp")

#: Per-flow rate ceiling.  Zero-hop paths (src == dst commodities, which
#: regular traffic never produces) would otherwise waterfill to +inf and
#: NaN-poison the padded-slot shares (inf - inf) on the next round.
_RATE_CAP = 1e6


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Static engine knobs (each distinct combination compiles one scan)."""

    dt: float = 1.0  # step length in units of size / line-rate
    wf_iters: int = 12  # waterfilling rounds per step (each >= 1 bottleneck)
    wf_rule: str = "fast"  # per-step freeze rule ("fast" | "exact")
    max_flows: int = 1024  # concurrent flow slots per instance
    max_arrivals: int = 32  # Poisson arrival cap per step per instance
    nbins: int = 24  # log2-spaced FCT histogram bins
    salt: int = 0x5EED  # ECMP hash salt
    bh_rate: float = 1.0  # blackhole drain rate of a held flow (volume/step)


@dataclasses.dataclass
class SimResult:
    """Raw accumulators of one sim run (reduced by ``sim.telemetry``)."""

    throughput: np.ndarray  # (T, B) volume delivered per step
    active: np.ndarray  # (T, B) active flows after each step
    fct_hist: np.ndarray  # (B, nbins) completions per log2(FCT / dt) bin
    fct_sum: np.ndarray  # (B,) sum of completed-flow FCTs
    fct_count: np.ndarray  # (B,) completed flows
    comm_delivered: np.ndarray  # (B, K [+1]) volume delivered per commodity
    comm_offered: np.ndarray  # (B, K [+1]) volume admitted per commodity
    util_sum: np.ndarray  # (B, S) per-step relative link loads, summed
    drops: np.ndarray  # (B,) arrivals lost (slot table full / per-step cap)
    admitted: np.ndarray  # (B,) arrivals placed into a slot
    blackholed: np.ndarray  # (T, B) volume blackholed per step (held flows)
    blackholed_total: np.ndarray  # (B,) total blackholed incl. event kills
    inflight: np.ndarray  # (B,) admitted volume still undelivered at the end
    demands: np.ndarray  # (B, K [+1]) the batch's demand vectors
    slot_valid: np.ndarray  # (B, S) real-slot mask
    n_steps: int
    dt: float
    policy: str
    backend: str


# --------------------------------------------------------------------------- #
# max-min waterfilling over path rows with flow multiplicities
# --------------------------------------------------------------------------- #


def _path_min_gather(share_pad: jnp.ndarray, pe: jnp.ndarray) -> jnp.ndarray:
    """(B, P) min over each path's hop slots of a padded (B, S+1) table.

    Accumulated hop column by hop column (trace-time unroll over L) — one
    flattened (B, P*L) take_along_axis materializes the (B, P, L)
    intermediate and runs several-fold slower on XLA:CPU, which only stays
    on the vectorized row-gather path for the narrow per-column form.  Min
    accumulates exactly in any order; the ordered-sum sibling
    (``core.flow._path_cost_gather``) needs a positional halving tree over
    the columns to keep the same association as ``_fold_sum``.
    """
    B, P, L = pe.shape
    acc = jnp.full((B, P), jnp.inf, jnp.float32)
    for j in range(L):
        acc = jnp.minimum(
            acc, jnp.take_along_axis(share_pad, pe[:, :, j], axis=1)
        )
    return acc


def _slot_min_gather(
    per_path: jnp.ndarray, pe: jnp.ndarray, n_slots: int, slot_gather
) -> jnp.ndarray:
    """(B, S) min over each slot's crossing paths of a (B, P) per-path value.

    The transposed sibling of ``_path_min_gather`` — the same fan-in tables
    that back the ``gather`` congestion path (positions per slot), with min
    in place of the ordered sum; falls back to an XLA scatter-min when the
    batch carries no tables.
    """
    B, P = per_path.shape
    L = pe.shape[-1]
    if slot_gather is not None:
        fr = jnp.concatenate(
            [
                jnp.repeat(per_path, L, axis=1),
                jnp.full((B, 1), jnp.inf, jnp.float32),
            ],
            axis=1,
        )
        d = slot_gather.shape[-1]
        acc = jnp.full((B, n_slots), jnp.inf, jnp.float32)
        for j in range(d):
            acc = jnp.minimum(
                acc,
                jnp.take_along_axis(fr, slot_gather[:, :, j], axis=1),
            )
        return acc
    vals = jnp.repeat(per_path, L, axis=1)  # (B, P*L)
    flat = pe.reshape(B, P * L)
    out = jnp.full((B, n_slots + 1), jnp.inf, jnp.float32)
    out = out.at[jnp.arange(B)[:, None], flat].min(vals)
    return out[:, :n_slots]


def _waterfill_core(loads_of, pe, nflow, cap, sval, wf_iters: int,
                    slot_gather=None, rule: str = "exact"):
    """Progressive-filling max-min rates for ``nflow`` flows per path row.

    Flows on the same path row are symmetric, so state is per ROW: the
    per-flow rate of that row's flows plus a frozen mask.  Each round
    computes every link's fair share of its remaining capacity among its
    unfrozen flows (the two link-load products go through ``loads_of`` —
    the MW congestion backends' load half) and every flow's limit (min
    share along its path), then freezes flows by ``rule``:

    * ``"exact"`` — every link that is **locally minimal** (all its
      unfrozen flows are limited by it: min over its flows of limit ==
      its share) is a true max-min bottleneck — none of its flows can be
      raised past its share by any allocation — so ALL of them freeze.
      Freezing every locally-minimal link per round resolves whole
      antichains of bottleneck levels at once: convergence takes
      O(longest dependency chain) rounds (~30 covers the test instances)
      instead of one round per distinct level.
    * ``"fast"`` — the textbook rule: freeze only the flows bottlenecked
      at the global minimum share.  One level per round, but each round
      costs ~4x less than ``"exact"`` on XLA:CPU (two fewer min-gather
      stages) — the right trade inside the sim's per-step loop, where the
      allocation is recomputed every step anyway and the truncation
      fallback below keeps it feasible.

    Rows left unfrozen after ``wf_iters`` rounds take their final
    bottleneck share, which keeps the allocation feasible (each link:
    frozen load + unfrozen count * share <= capacity).  Returns
    ``(per-flow rate (B, P), loads (B, S))``.

    Flow multiplicities may be FRACTIONAL (a fluid flow split across its
    commodity's paths), so presence tests use a tiny epsilon.
    """
    if rule not in ("exact", "fast"):
        raise ValueError(f"unknown waterfill rule {rule!r}")
    B, S = cap.shape[0], cap.shape[-1]
    inf_col = jnp.full((B, 1), jnp.inf, jnp.float32)
    present = nflow > 1e-6

    def share_limit(fixed, rate):
        load_fixed = loads_of(rate * nflow * fixed)
        cnt = loads_of(nflow * (1.0 - fixed))
        avail = jnp.maximum(cap - load_fixed, 0.0)
        share = jnp.where(cnt > 1e-6, avail / jnp.maximum(cnt, 1e-9), jnp.inf)
        limit = _path_min_gather(
            jnp.concatenate([share, inf_col], axis=1), pe
        )
        limit = jnp.minimum(limit, _RATE_CAP)
        binding = (cnt > 1e-6) & sval & jnp.isfinite(cap)
        return share, limit, binding

    def body(state, _):
        fixed, rate = state
        share, limit, binding = share_limit(fixed, rate)
        unfixed = present & (fixed < 0.5)
        if rule == "exact":
            lim_or_inf = jnp.where(unfixed, limit, jnp.inf)
            minlim = _slot_min_gather(lim_or_inf, pe, S, slot_gather)
            bneck = binding & (minlim >= share * (1.0 - 1e-5))
            bshare = jnp.where(bneck, share, jnp.inf)
            near = _path_min_gather(
                jnp.concatenate([bshare, inf_col], axis=1), pe
            )
            newly = (
                unfixed & jnp.isfinite(near) & (limit >= near * (1.0 - 1e-5))
            )
        else:
            theta = jnp.minimum(
                jnp.min(jnp.where(binding, share, jnp.inf), axis=1),
                _RATE_CAP,
            )
            newly = unfixed & (limit <= theta[:, None] * (1.0 + 1e-6))
        rate = jnp.where(newly, limit, rate)
        fixed = jnp.where(newly, 1.0, fixed)
        return (fixed, rate), None

    state = (jnp.zeros_like(nflow), jnp.zeros_like(nflow))
    state, _ = jax.lax.scan(body, state, None, length=wf_iters)
    fixed, rate = state
    _, limit, _ = share_limit(fixed, rate)
    rate = jnp.where(fixed > 0.5, rate, limit)
    rate = jnp.where(present, rate, 0.0)
    return rate, loads_of(rate * nflow)


@solver_jit(spec="_ir_cases_waterfill")
@functools.partial(jax.jit, static_argnames=("wf_iters", "backend", "rule"))
def _waterfill_jit(pe, nflow, cap, sval, slot_gather, *, wf_iters,
                   backend, rule="exact"):
    S = cap.shape[1]
    loads_of = make_loads_fn_batch(pe, S, backend, slot_gather)
    return _waterfill_core(loads_of, pe, nflow, cap, sval, wf_iters,
                           slot_gather, rule=rule)


def waterfill_rates(
    systems: "PathSystemBatch | Sequence[PathSystem]",
    n_flows_per_path: np.ndarray | None = None,
    wf_iters: int = 48,
    backend: str = "auto",
    rule: str = "exact",
) -> tuple[np.ndarray, np.ndarray]:
    """Max-min fair rates for a *static* flow population (no time loop).

    ``n_flows_per_path`` is a (B, <= p_max) array of persistent flows per
    path row — counts may be FRACTIONAL (a fluid flow split across its
    commodity's paths).  The default puts each commodity's demand's worth
    of flows on every one of its paths (the MPTCP-subflow saturation
    population).  Note the max-min water level depends on the split: equal
    spreading burns hop capacity on the slack paths, while seeding the
    split from ``mw_concurrent_flow``'s optimal rates makes the minimum
    demand-normalized commodity throughput reproduce the MW concurrent
    alpha (within 2% on RRG(256, 24, 18) — the steady-state parity test in
    ``tests/test_sim.py``, cross-validating the allocator's capacity
    accounting against the MW loads model on the same congestion
    backends).

    Returns ``(rates, loads)``: per-flow rate per path row (B, p_max) and
    per-directed-slot loads (B, s_max), as numpy arrays.
    """
    batch = _as_batch(systems)
    B, P = batch.n_batch, batch.p_max
    if n_flows_per_path is None:
        n_flows_per_path = np.zeros((B, P), np.float32)
        for i, ps in enumerate(batch.systems):
            if ps.n_paths:
                n_flows_per_path[i, : ps.n_paths] = ps.demands[
                    np.asarray(ps.path_owner)
                ]
    nflow = np.asarray(n_flows_per_path, dtype=np.float32)
    if nflow.ndim != 2 or nflow.shape[0] != B or nflow.shape[1] > P:
        raise ValueError(
            f"n_flows_per_path must be ({B}, <= {P}); got {nflow.shape}"
        )
    if nflow.shape[1] < P:  # instance rows sit at the front of the envelope
        nflow = np.pad(nflow, ((0, 0), (0, P - nflow.shape[1])))
    backend, slot_tab = batch.congestion_backend(backend)
    cap, _, sval = _cap_arrays(batch)
    rate, loads = _waterfill_jit(
        jnp.asarray(batch.path_edges), jnp.asarray(nflow), cap, sval,
        None if slot_tab is None else jnp.asarray(slot_tab),
        wf_iters=wf_iters, backend=backend, rule=rule,
    )
    return np.asarray(rate), np.asarray(loads)


# --------------------------------------------------------------------------- #
# host-side setup helpers
# --------------------------------------------------------------------------- #


def _as_batch(systems) -> PathSystemBatch:
    if isinstance(systems, PathSystemBatch):
        return systems
    return PathSystemBatch.from_systems(list(systems))


def _cap_arrays(batch: PathSystemBatch):
    """(cap, inv_cap, slot_valid) as (B, S) jnp arrays (padded slots: inf
    capacity, zero inverse — they can never bind a fair share)."""
    inv = np.asarray(batch.inv_cap, np.float32)
    sval = np.asarray(batch.slot_valid)
    cap = np.where(inv > 0, 1.0 / np.maximum(inv, 1e-30), np.inf).astype(
        np.float32
    )
    return jnp.asarray(cap), jnp.asarray(inv), jnp.asarray(sval)


def _commodity_tables(batch: PathSystemBatch, n_comm: int):
    """Per-instance commodity state for path selection, padded to the env:

    * ``rows``   (B, K, D) int32 — candidate path rows per commodity,
      padded with ``p_max`` (the engine's empty-slot sentinel);
    * ``counts`` (B, K) int32 — candidate count (ECMP group size / k);
    * ``src``/``dst`` (B, K) int32 — kept commodities' endpoint switches
      (hash inputs; commodity-index fallback when a hand-built system lacks
      pedigree).
    """
    B, P, K = batch.n_batch, batch.p_max, n_comm
    per: dict[int, tuple] = {}
    tabs, cnts, srcs, dsts = [], [], [], []
    for ps in batch.systems:
        got = per.get(id(ps))
        if got is None:
            owner = np.asarray(ps.path_owner)
            cnt = np.zeros(K, np.int32)
            if ps.n_paths:
                bc = np.bincount(owner, minlength=K)[:K]
                cnt[: len(bc)] = bc
                tab = PathSystemBatch._owner_table(owner, K, P).astype(
                    np.int32
                )
            else:
                tab = np.full((K, 1), P, np.int32)
            src = np.zeros(K, np.int32)
            dst = np.zeros(K, np.int32)
            if ps.src is not None and ps.unrouted is not None:
                kept = ~np.asarray(ps.unrouted)
                s, d = np.asarray(ps.src)[kept], np.asarray(ps.dst)[kept]
                src[: len(s)] = s.astype(np.int32)
                dst[: len(d)] = d.astype(np.int32)
            else:
                src[: ps.n_commodities] = np.arange(
                    ps.n_commodities, dtype=np.int32
                )
            got = (tab, cnt, src, dst)
            per[id(ps)] = got
        tabs.append(got[0])
        cnts.append(got[1])
        srcs.append(got[2])
        dsts.append(got[3])
    D = max(t.shape[1] for t in tabs)
    rows = np.full((B, K, D), P, np.int32)
    for i, t in enumerate(tabs):
        rows[i, :, : t.shape[1]] = t
    return (
        rows,
        np.stack(cnts),
        np.stack(srcs),
        np.stack(dsts),
    )


def _owner_padded(batch: PathSystemBatch, n_comm: int) -> np.ndarray:
    """(B, P+1) commodity of each path row; empty sentinel row -> K."""
    owner = np.asarray(batch.path_owner, np.int32)
    pad = np.full((batch.n_batch, 1), n_comm, np.int32)
    return np.concatenate([owner, pad], axis=1)


# --------------------------------------------------------------------------- #
# the jitted scan
# --------------------------------------------------------------------------- #


def _init_carry(
    n_batch: int, n_flows: int, p_max: int, s_max: int, n_comm: int,
    nbins: int,
):
    """Fresh scan carry for a cold start (every slot empty).

    The carry is the unit of state the segmented driver
    (``repro.sim.events``) migrates across topology deltas, so its layout
    is a contract: ``(row, rem, age, fid, hold, next_id, rel_prev,
    fct_hist, fct_sum, fct_cnt, comm_del, comm_off, util_sum, drops,
    admitted, bh_sum)``.  ``fid`` records each slot's flow id (the ECMP
    hash input, needed to re-select paths deterministically after a
    failure); ``hold`` counts down the detection/reconvergence lag during
    which a slot's traffic is blackholed; ``bh_sum`` accumulates the
    blackholed volume.  All three are exact no-ops while no event has set
    ``hold`` — plain ``simulate`` results are bit-identical to the
    pre-event engine.
    """
    B, F = n_batch, n_flows
    return (
        jnp.full((B, F), p_max, jnp.int32),  # row: empty sentinel
        jnp.zeros((B, F), jnp.float32),  # rem
        jnp.zeros((B, F), jnp.float32),  # age
        jnp.zeros((B, F), jnp.uint32),  # fid
        jnp.zeros((B, F), jnp.int32),  # hold (blackhole countdown)
        (jnp.arange(B, dtype=jnp.uint32) << 20),  # next_id: decorrelated
        jnp.zeros((B, s_max), jnp.float32),  # rel_prev
        jnp.zeros((B, nbins + 1), jnp.float32),  # fct_hist (+ garbage col)
        jnp.zeros((B,), jnp.float32),  # fct_sum
        jnp.zeros((B,), jnp.int32),  # fct_cnt
        jnp.zeros((B, n_comm + 1), jnp.float32),  # comm_del (+ dummy col)
        jnp.zeros((B, n_comm + 1), jnp.float32),  # comm_off (+ dummy col)
        jnp.zeros((B, s_max), jnp.float32),  # util_sum
        jnp.zeros((B,), jnp.int32),  # drops
        jnp.zeros((B,), jnp.int32),  # admitted
        jnp.zeros((B,), jnp.float32),  # bh_sum
    )


@solver_jit(spec="_ir_cases_sim_scan")
@functools.partial(
    jax.jit,
    static_argnames=("policy", "wf_iters", "wf_rule", "n_arrivals", "backend"),
)
def _sim_scan(
    carry0,  # scan carry (see _init_carry; may be a migrated mid-run carry)
    ts,  # (T,) int32 ABSOLUTE step indices (the per-step RNG fold source)
    pe,  # (B, P, L) int32
    owner_pad,  # (B, P+1) int32, commodity of each row (K = dummy)
    cap,  # (B, S) f32, +inf on padded slots
    inv,  # (B, S) f32
    sval,  # (B, S) bool
    logits_epochs,  # (E, B, K) f32 commodity log-weights (-inf = never)
    rows_tab,  # (B, K, D) int32 candidate rows, padded with P
    rows_cnt,  # (B, K) int32
    comm_src,  # (B, K) int32
    comm_dst,  # (B, K) int32
    rate_sched,  # (T,) f32 Poisson mean arrivals per step
    epoch_sched,  # (T,) int32 index into logits_epochs
    size_params,  # (3,) f32: (p_elephant, size_mice, size_elephant)
    dt,  # f32 scalar
    bh_rate,  # f32 scalar: blackhole drain rate of held flows
    salt,  # uint32 scalar
    key,  # PRNG key
    slot_gather,  # gather-backend fan-in tables or None
    *,
    policy: str,
    wf_iters: int,
    wf_rule: str,
    n_arrivals: int,
    backend: str,
):
    B, K = rows_cnt.shape
    _, P, L = pe.shape
    S = inv.shape[1]
    D = rows_tab.shape[-1]
    A = n_arrivals
    F = carry0[0].shape[-1]
    nbins = carry0[7].shape[-1] - 1
    W_new = A * D if policy == "mptcp" else A
    loads_of = make_loads_fn_batch(pe, S, backend, slot_gather)
    bidx = jnp.arange(B)[:, None]
    if policy == "ksp_lc":
        pe_pad = jnp.concatenate(
            [pe, jnp.full((B, 1, L), S, jnp.int32)], axis=1
        )

    def step(carry, inp):
        (row, rem, age, fid_c, hold, next_id, rel_prev, fct_hist, fct_sum,
         fct_cnt, comm_del, comm_off, util_sum, drops, admitted,
         bh_sum) = carry
        t, rate_t, ep = inp
        k_n, k_c, k_sz = jax.random.split(jax.random.fold_in(key, t), 3)

        # ---- arrivals: Poisson count, commodity draw, size draw ---------- #
        logits = logits_epochs[ep]  # (B, K)
        has_comm = jnp.any(jnp.isfinite(logits), axis=1)
        n_poisson = jax.random.poisson(k_n, rate_t, (B,)).astype(jnp.int32)
        n_new = jnp.minimum(n_poisson, jnp.int32(A))
        n_new = jnp.where(has_comm, n_new, 0)
        # arrivals past the per-step cap never materialize — count them as
        # drops so the offered load the run reports stays honest
        drops = drops + jnp.where(has_comm, n_poisson - n_new, 0)
        cand_live = jnp.arange(A)[None, :] < n_new[:, None]  # (B, A)
        safe_logits = jnp.where(has_comm[:, None], logits, 0.0)
        comm = jax.random.categorical(
            k_c, safe_logits[:, None, :], axis=-1, shape=(B, A)
        )
        eleph = jax.random.bernoulli(k_sz, size_params[0], (B, A))
        size = jnp.where(eleph, size_params[2], size_params[1])
        fid = next_id[:, None] + jnp.arange(A, dtype=jnp.uint32)
        next_id = next_id + n_new.astype(jnp.uint32)

        crows = jnp.take_along_axis(rows_tab, comm[:, :, None], axis=1)
        ccnt = jnp.take_along_axis(rows_cnt, comm, axis=1)  # (B, A)
        cand_live &= ccnt > 0

        # ---- path selection --------------------------------------------- #
        if policy == "ecmp":
            csrc = jnp.take_along_axis(comm_src, comm, axis=1)
            cdst = jnp.take_along_axis(comm_dst, comm, axis=1)
            h = flow_hash(csrc, cdst, fid, salt)
            j = (h % jnp.maximum(ccnt, 1).astype(jnp.uint32)).astype(
                jnp.int32
            )
            prow = jnp.take_along_axis(crows, j[:, :, None], axis=2)[:, :, 0]
            new_live, new_row, new_rem, new_fid = cand_live, prow, size, fid
        elif policy == "ksp_lc":
            # least-congested: bottleneck utilization of each candidate
            # under the PREVIOUS step's loads (flow-level adaptive routing)
            relp = jnp.concatenate(
                [rel_prev, jnp.zeros((B, 1), jnp.float32)], axis=1
            )
            hops = pe_pad[jnp.arange(B)[:, None, None], crows]  # (B,A,D,L)
            util = jnp.max(
                relp[jnp.arange(B)[:, None, None, None], hops], axis=3
            )
            valid = jnp.arange(D)[None, None, :] < ccnt[:, :, None]
            util = jnp.where(valid, util, jnp.inf)
            j = jnp.argmin(util, axis=2)  # first minimum: deterministic
            prow = jnp.take_along_axis(crows, j[:, :, None], axis=2)[:, :, 0]
            new_live, new_row, new_rem, new_fid = cand_live, prow, size, fid
        else:  # mptcp: one subflow per candidate path, size split evenly
            sub = jnp.arange(D)[None, None, :] < ccnt[:, :, None]
            new_live = (cand_live[:, :, None] & sub).reshape(B, W_new)
            new_row = crows.reshape(B, W_new)
            per = size / jnp.maximum(ccnt, 1).astype(jnp.float32)
            new_rem = jnp.broadcast_to(
                per[:, :, None], (B, A, D)
            ).reshape(B, W_new)
            new_fid = jnp.broadcast_to(  # subflows share the parent's id
                fid[:, :, None], (B, A, D)
            ).reshape(B, W_new)

        # ---- place new flows into free slots (live-first packing) -------- #
        order = jnp.argsort(~new_live, axis=1)  # stable: live flows first
        new_live = jnp.take_along_axis(new_live, order, axis=1)
        new_row = jnp.take_along_axis(new_row, order, axis=1)
        new_rem = jnp.take_along_axis(new_rem, order, axis=1)
        new_fid = jnp.take_along_axis(new_fid, order, axis=1)
        free = row == P
        n_free = free.sum(axis=1)
        target = jnp.argsort(~free, axis=1)[:, :W_new]  # free slots first
        place = new_live & (jnp.arange(W_new)[None, :] < n_free[:, None])
        row = row.at[bidx, target].set(
            jnp.where(place, new_row, jnp.take_along_axis(row, target, axis=1))
        )
        rem = rem.at[bidx, target].set(
            jnp.where(place, new_rem, jnp.take_along_axis(rem, target, axis=1))
        )
        age = age.at[bidx, target].set(
            jnp.where(place, 0.0, jnp.take_along_axis(age, target, axis=1))
        )
        fid_c = fid_c.at[bidx, target].set(
            jnp.where(
                place, new_fid, jnp.take_along_axis(fid_c, target, axis=1)
            )
        )
        hold = hold.at[bidx, target].set(
            jnp.where(place, 0, jnp.take_along_axis(hold, target, axis=1))
        )
        drops = drops + (new_live & ~place).sum(axis=1)
        admitted = admitted + place.sum(axis=1)
        cnew = jnp.take_along_axis(owner_pad, new_row, axis=1)  # (B, W_new)
        comm_off = comm_off.at[bidx, cnew].add(
            jnp.where(place, new_rem, 0.0)
        )

        # ---- max-min waterfilling over path rows ------------------------- #
        # Held flows (hold > 0: their path died and detection has not
        # converged) blackhole at the first dead hop — they neither consume
        # downstream capacity nor deliver, so they are excluded from the
        # allocation entirely.  While hold == 0 everywhere (plain
        # ``simulate``) ``flowing == active`` and every op below is
        # bit-identical to the pre-event engine.
        active = row < P
        held = active & (hold > 0)
        flowing = active & ~held
        nflow = (
            jnp.zeros((B, P + 1), jnp.float32)
            .at[bidx, row]
            .add(flowing.astype(jnp.float32))[:, :P]
        )
        rate_p, loads = _waterfill_core(loads_of, pe, nflow, cap, sval,
                                        wf_iters, slot_gather, rule=wf_rule)
        rel = loads * inv  # (B, S) relative link loads

        # ---- drain flows, record completions ----------------------------- #
        rate_pad = jnp.concatenate(
            [rate_p, jnp.zeros((B, 1), jnp.float32)], axis=1
        )
        r_f = jnp.take_along_axis(rate_pad, row, axis=1)  # (B, F)
        delivered = jnp.minimum(rem, r_f * dt) * flowing
        bh = jnp.where(held, jnp.minimum(rem, bh_rate * dt), 0.0)
        rem = rem - delivered - bh
        age = jnp.where(active, age + 1.0, age)
        fin = active & (rem <= 1e-6)  # slot frees either way
        done = fin & ~held  # only flows that finished delivering record FCT
        # JF005: _fold_sum, not jnp.sum — F is a padded axis (empty slots
        # contribute exact zeros) and the FCT sum must not depend on the
        # max_flows envelope the run happened to compile with.
        fct_sum = fct_sum + _fold_sum(jnp.where(done, age * dt, 0.0))
        fct_cnt = fct_cnt + done.sum(axis=1)
        bins = jnp.clip(
            jnp.floor(jnp.log2(jnp.maximum(age, 1.0))).astype(jnp.int32),
            0,
            nbins - 1,
        )
        fct_hist = fct_hist.at[bidx, jnp.where(done, bins, nbins)].add(1.0)
        cflow = jnp.take_along_axis(owner_pad, row, axis=1)  # (B, F)
        comm_del = comm_del.at[bidx, cflow].add(delivered)
        util_sum = util_sum + rel
        # JF101 (caught by the IR audit, not the AST linter — method-call
        # sums are invisible to JF005): F is a padded axis, so per-step
        # throughput folds positionally like fct_sum above.
        thr = _fold_sum(delivered)
        bh_step = _fold_sum(bh)
        bh_sum = bh_sum + bh_step
        nact = (active & ~fin).sum(axis=1)  # in flight AFTER completions
        hold = jnp.where(fin, 0, jnp.maximum(hold - 1, 0))
        row = jnp.where(fin, P, row)
        rem = jnp.where(fin, 0.0, rem)
        age = jnp.where(fin, 0.0, age)
        carry = (row, rem, age, fid_c, hold, next_id, rel, fct_hist,
                 fct_sum, fct_cnt, comm_del, comm_off, util_sum, drops,
                 admitted, bh_sum)
        return carry, (thr, nact, bh_step)

    xs = (ts, rate_sched, epoch_sched)
    carry, (thr, nact, bh) = jax.lax.scan(step, carry0, xs)
    return carry, thr, nact, bh


def _scan_inputs(batch: PathSystemBatch, policy: str, cfg: SimConfig,
                 backend: str) -> dict:
    """Host-side per-segment setup shared by ``simulate`` and the segmented
    driver (``repro.sim.events``): commodity tables, capacity arrays,
    backend resolution, and the per-step admission-width check — everything
    ``_sim_scan`` needs that depends only on the batch (not the workload or
    the carry)."""
    B = batch.n_batch
    if B > SIM_MAX_BATCH:
        raise ValueError(
            f"batch has {B} instances > REPRO_SIM_MAX_BATCH={SIM_MAX_BATCH}; "
            "raise the env cap or split the batch"
        )
    K = batch.demands.shape[1] - 1  # the last column is the dummy's
    rows_tab, rows_cnt, comm_src, comm_dst = _commodity_tables(batch, K)
    D = rows_tab.shape[-1]
    w_new = cfg.max_arrivals * D if policy == "mptcp" else cfg.max_arrivals
    if w_new > cfg.max_flows:
        raise ValueError(
            f"policy {policy!r} can admit {w_new} flows per step but "
            f"max_flows={cfg.max_flows}; raise max_flows or lower "
            "max_arrivals"
        )
    owner_pad = _owner_padded(batch, K)
    cap, inv, sval = _cap_arrays(batch)
    backend, slot_tab = batch.congestion_backend(backend)
    return {
        "n_comm": K,
        "pe": jnp.asarray(batch.path_edges),
        "owner_pad": jnp.asarray(owner_pad),
        "cap": cap,
        "inv": inv,
        "sval": sval,
        "rows_tab": jnp.asarray(rows_tab),
        "rows_cnt": jnp.asarray(rows_cnt),
        "comm_src": jnp.asarray(comm_src),
        "comm_dst": jnp.asarray(comm_dst),
        "slot_tab": None if slot_tab is None else jnp.asarray(slot_tab),
        "backend": backend,
    }


def _epoch_logits(workload, batch: PathSystemBatch, n_comm: int, n_steps: int):
    """Demand epochs -> ((E, B, K) commodity log-weights, (T,) epoch ids).

    ``-inf`` marks commodities that must never be sampled (zero demand)."""
    B, K, T = batch.n_batch, n_comm, n_steps
    de = workload.demand_epochs
    if de is None:
        de = np.asarray(batch.demands, np.float32)[None, :, :K]
        eos = np.zeros(T, np.int32)
    else:
        de = np.asarray(de, np.float32)
        if de.ndim == 2:  # (E, K) shared across instances
            de = np.broadcast_to(de[:, None, :], (de.shape[0], B, de.shape[1]))
        if de.shape[1:] != (B, K):
            raise ValueError(
                f"demand_epochs must be (E, {B}, {K}) or (E, {K}); "
                f"got {de.shape}"
            )
        if workload.epoch_of_step is None:
            raise ValueError(
                "workload sets demand_epochs but not epoch_of_step"
            )
        eos = np.asarray(workload.epoch_of_step, np.int32)
        if len(eos) != T or (len(eos) and eos.max() >= de.shape[0]):
            raise ValueError("epoch_of_step must be (T,) with values < E")
    logits = np.where(
        de > 0, np.log(np.maximum(de, 1e-30)), -np.inf
    ).astype(np.float32)
    return logits, eos


def _run_segment(inp: dict, carry, ts, rates, eos, logits, size_params,
                 cfg: SimConfig, policy: str, key):
    """One ``_sim_scan`` invocation over the (absolute) step indices ``ts``.

    The same ``key`` must be passed for every segment of a run: the scan
    folds the ABSOLUTE step index into it, so splitting a horizon into
    segments replays the identical per-step RNG streams — the CT-segment
    parity contract (INVARIANTS.md)."""
    return _sim_scan(
        carry,
        jnp.asarray(ts, dtype=jnp.int32),
        inp["pe"],
        inp["owner_pad"],
        inp["cap"], inp["inv"], inp["sval"],
        jnp.asarray(logits),
        inp["rows_tab"],
        inp["rows_cnt"],
        inp["comm_src"],
        inp["comm_dst"],
        jnp.asarray(rates, dtype=jnp.float32),
        jnp.asarray(eos, dtype=jnp.int32),
        jnp.asarray(size_params),
        jnp.float32(cfg.dt),
        jnp.float32(cfg.bh_rate),
        jnp.uint32(cfg.salt),
        key,
        inp["slot_tab"],
        policy=policy,
        wf_iters=cfg.wf_iters,
        wf_rule=cfg.wf_rule,
        n_arrivals=cfg.max_arrivals,
        backend=inp["backend"],
    )


def _size_params(workload) -> np.ndarray:
    return np.asarray(
        [workload.p_elephant, workload.size_mice, workload.size_elephant],
        np.float32,
    )


def simulate(
    systems: "PathSystemBatch | Sequence[PathSystem]",
    workload,
    policy: str = "ecmp",
    config: SimConfig | None = None,
    seed: int = 0,
    backend: str = "auto",
) -> SimResult:
    """Run the batched flow-level simulator for one workload.

    ``systems`` is a ``PathSystemBatch`` (or a sequence of ``PathSystem``s,
    pad-and-stacked on the fly) — B independent instances advanced by ONE
    jitted scan.  ``workload`` is a ``sim.workloads.Workload``; ``policy``
    is one of ``POLICIES``.  ``backend`` selects the congestion backend for
    the waterfilling inner loop (``auto``: gather tables on CPU, the fused
    rank-3 kernel on TPU — the same dispatch as the batched MW solver).

    For a run with topology events (failures, repairs, expansions) injected
    mid-traffic, see ``repro.sim.events.simulate_events`` — with an empty
    schedule it reduces to exactly this function, bit for bit.
    """
    cfg = config or SimConfig()
    if policy not in POLICIES:
        raise ValueError(f"unknown sim policy {policy!r}: expected {POLICIES}")
    batch = _as_batch(systems)
    T = int(workload.n_steps)
    if T > SIM_MAX_STEPS:
        raise ValueError(
            f"workload has {T} steps > REPRO_SIM_MAX_STEPS={SIM_MAX_STEPS}; "
            "raise the env cap or split the horizon"
        )
    inp = _scan_inputs(batch, policy, cfg, backend)
    logits, eos = _epoch_logits(workload, batch, inp["n_comm"], T)
    carry0 = _init_carry(
        batch.n_batch, cfg.max_flows, batch.p_max, batch.s_max,
        inp["n_comm"], cfg.nbins,
    )
    carry, thr, nact, bh = _run_segment(
        inp, carry0, np.arange(T, dtype=np.int32), workload.rate, eos,
        logits, _size_params(workload), cfg, policy,
        jax.random.PRNGKey(seed),
    )
    (_, rem_f, _, _, _, _, _, fct_hist, fct_sum, fct_cnt, comm_del, comm_off,
     util_sum, drops, admitted, bh_sum) = carry
    result = SimResult(
        throughput=np.asarray(thr),
        active=np.asarray(nact),
        fct_hist=np.asarray(fct_hist)[:, : cfg.nbins],
        fct_sum=np.asarray(fct_sum),
        fct_count=np.asarray(fct_cnt),
        comm_delivered=np.asarray(comm_del),
        comm_offered=np.asarray(comm_off),
        util_sum=np.asarray(util_sum),
        drops=np.asarray(drops),
        admitted=np.asarray(admitted),
        blackholed=np.asarray(bh),
        blackholed_total=np.asarray(bh_sum),
        inflight=np.asarray(rem_f, np.float64).sum(axis=1),
        demands=np.asarray(batch.demands),
        slot_valid=np.asarray(inp["sval"]),
        n_steps=T,
        dt=cfg.dt,
        policy=policy,
        backend=inp["backend"],
    )
    if checks_enabled():
        check_sim_state(result)
    return result


# ---- IR audit cases (python -m repro.analysis ir) ------------------------- #

def _ir_cases_waterfill():
    from ..core.flow import _ir_batch_args

    def mk(backend, with_gather):
        def make():
            (pe3, _, _, inv2, sval2, slot_gather, _, _) = _ir_batch_args()
            B, P = pe3.shape[0], pe3.shape[1]
            nflow = np.ones((B, P), np.float32)
            cap = np.ones_like(inv2)
            sg = jnp.asarray(slot_gather) if with_gather else None
            return (pe3, nflow, cap, sval2, sg), {
                "wf_iters": 4, "backend": backend, "rule": "exact",
            }

        return make

    return [
        AuditCase(label="gather", make=mk("gather", True), backend="gather"),
        AuditCase(label="scatter", make=mk("scatter", False),
                  backend="scatter"),
    ]


def _ir_cases_sim_scan():
    from ..core.flow import _ir_batch_args

    def make():
        (pe3, owner2, _, inv2, sval2, slot_gather, _, _) = _ir_batch_args()
        B, P = pe3.shape[0], pe3.shape[1]
        S = inv2.shape[-1]
        K = int(owner2.max()) + 1
        D = slot_gather.shape[-1]
        T, E, F, A, nbins = 4, 2, 8, 2, 4
        owner_pad = np.concatenate(
            [owner2, np.full((B, 1), K, np.int32)], axis=1)
        args = (
            _init_carry(B, F, P, S, K, nbins),
            np.arange(T, dtype=np.int32),  # ts (absolute step indices)
            pe3, owner_pad,
            np.ones_like(inv2),  # cap (B, S)
            np.ones_like(inv2),  # inv
            sval2,
            np.zeros((E, B, K), np.float32),  # logits_epochs
            np.full((B, K, D), P, np.int32),  # rows_tab
            np.ones((B, K), np.int32),  # rows_cnt
            np.zeros((B, K), np.int32),  # comm_src
            np.ones((B, K), np.int32),  # comm_dst
            np.ones(T, np.float32),  # rate_sched
            np.zeros(T, np.int32),  # epoch_sched
            np.array([0.1, 1.0, 10.0], np.float32),  # size_params
            np.float32(0.1),  # dt
            np.float32(1.0),  # bh_rate
            np.uint32(7),  # salt
            jax.random.PRNGKey(0),
            jnp.asarray(slot_gather),
        )
        kwargs = {
            "policy": "ecmp", "wf_iters": 4, "wf_rule": "exact",
            "n_arrivals": A, "backend": "gather",
        }
        return args, kwargs

    return [
        AuditCase(
            label="ecmp-gather",
            make=make,
            backend="gather",
            exempt={
                "JF102": "histogram/commodity accumulators scatter-add into "
                "per-batch tallies by design; the gather-vs-scatter "
                "bit-exactness contract covers the CONGESTION backend "
                "(rate/load folds), which this entry routes through "
                "make_loads_fn_batch(gather) with no scatter in it",
            },
        ),
    ]
