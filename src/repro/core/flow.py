"""Maximum concurrent flow over a k-shortest-path system (paper §4).

The paper computes "optimal routing" throughput with CPLEX on the exact
multicommodity LP.  Over an explicit path system this module has the exact
LP as its oracle and one multiplicative-weights (MW) solver for scale:

* ``lp_concurrent_flow`` — exact LP (scipy/HiGHS).  Restricted to the path
  system, but with enough paths (k >= 8 and slack >= 2 on these
  low-diameter graphs) it matches the edge-formulation optimum to <2%
  (validated in tests on small instances against an edge-based LP).
* ``mw_concurrent_flow_batch`` — jitted JAX mirror-descent / MW iteration
  minimizing the smoothed max edge load, over a batch of instances in one
  window scan.  ``mw_concurrent_flow`` is its batch of one.

Maximum concurrent flow: maximize alpha s.t. each commodity i routes
``alpha * d_i`` and edge loads respect capacities.  For the capacity question
"does this topology support every server at full rate" the test is alpha >= 1.

The MW solver
-------------
Every headline sweep (the Fig 1c bisection, capacity-vs-size curves, Fig 7
failure stages) solves many independent instances, each with its own
(P, S) shapes.  ``PathSystemBatch`` pads B path systems to a common,
bucketed (P_max, L_max, S_max, K_max) envelope with per-instance validity
masks (padded slots carry infinite capacity and are masked out of the
softmax; padded path rows belong to a zero-demand dummy commodity), and
``mw_concurrent_flow_batch`` runs ONE window scan over the stack:

* per-instance adaptive state — plateau / ``target_alpha`` early-stop is
  tracked per instance on the host, and converged instances leave the
  computed batch at a window edge (their carries kept as they were) while
  stragglers run on;
* every backend normalises each commodity's split over its contiguous
  run of path rows (``_seg_norm``): at most ``seg_max`` dense shifted
  adds and selects, no scatter or gather.

An instance's result does not depend on the batch it is solved in —
B = 1, other instances, or bucket padding: padding is masked, and every
sum keeps an association the envelope cannot change (``_fold_sum``,
``_ordered_fan_in_sum``, ``_seg_norm``).  The Fig 1c capacity search
(``core.capacity``, whose ``probe_wave`` batches a speculative bisection
wave) and the benchmark sweeps (``benchmarks.common.batch_alphas``) sit
on top.

Congestion backends
-------------------
Each MW iteration needs the two incidence products ``loads = B^T r`` and
``costs = B w`` (B the {0,1} path x directed-slot incidence).
``make_loads_fn_batch`` writes each backend's loads half once;
``make_congestion_fn_batch`` adds the costs half:

* ``scatter`` — one flat segment-sum over ``Bt * (S + 1)`` slots with
  per-instance slot offsets; no materialized B.
* ``gather`` — the CPU default: transposed fan-in tables precomputed at
  batch build time (for every slot, the flat positions of the path hops
  crossing it) turn the XLA scatter-add, a serialized element loop on
  CPU, into vectorized gathers.  Each slot's fan-in is accumulated in the
  scatter-add's order, so the two backends agree bit for bit.
* ``dense`` — materializes the stacked (Bt, P, S) incidence once and
  calls ``repro.kernels.ops.congestion`` (the fused Pallas kernel on TPU,
  reading each B tile from HBM once per iteration for both products; the
  jnp reference elsewhere, whose matmuls reassociate: ~1e-4 alpha drift
  after the anneal).  ``backend="pallas"`` forces the kernel (interpret
  mode off-TPU) for validation.

``PathSystemBatch.congestion_backend`` is the one place a backend is
decided: ``auto`` takes ``ops.preferred_congestion_backend``'s batch policy
(gather on CPU; dense or scatter by size on TPU), and ``gather`` falls
back to ``scatter`` when the batch carries no fan-in tables.  To let the
fused kernel compute both products in a single pass over B, the iteration
uses softmax weights derived from the *previous* iterate's edge loads (a
one-step price lag — the standard Jacobi pipelining); every backend
implements the identical lagged recurrence, and the per-iterate alpha
bookkeeping uses exact current loads.

``REPRO_LP_PATH_LIMIT`` (validated at import) moves the ``throughput()``
LP-vs-MW cutoff from its 20000-path default.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .. import env
from .. import obs
from ..analysis.contracts import (
    check_path_system_batch,
    check_segment_layout,
    checks_enabled,
)
from ..analysis.registry import AuditCase, solver_jit
from .routing import PathSystem
from ..kernels import ops

__all__ = [
    "FlowResult",
    "PathSystemBatch",
    "mw_concurrent_flow",
    "mw_concurrent_flow_batch",
    "make_loads_fn_batch",
    "lp_concurrent_flow",
    "lp_edge_concurrent_flow",
    "throughput",
    "LP_PATH_LIMIT",
]


#: throughput()'s auto dispatch solves instances with at most this many path
#: variables exactly (single-core HiGHS needs minutes much beyond ~10k).
#: Validated ONCE at import through the repro.env registry so a typo fails
#: loudly at startup rather than silently running every sweep through the
#: wrong solver.
LP_PATH_LIMIT = env.read("REPRO_LP_PATH_LIMIT")


@dataclasses.dataclass
class FlowResult:
    alpha: float  # max concurrent fraction: every commodity ships alpha * d_i
    rates: np.ndarray  # (P,) per-path rates of the feasible scaled solution
    max_load: float  # max relative edge load of the *unscaled* routing
    method: str
    iters: int = 0

    def normalized_throughput(self) -> float:
        """Per-server normalized throughput, capped at line rate (<= 1)."""
        return float(min(self.alpha, 1.0))


# --------------------------------------------------------------------------- #
# congestion-primitive backends (shared with core.mptcp)
# --------------------------------------------------------------------------- #


def _fold_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Sum over the last axis by positional halving.

    XLA's reduce chooses its association by array size, so summing a
    zero-padded axis can differ from the unpadded sum by an ulp — and the
    MW anneal amplifies single-ulp differences into visible alpha drift.
    A positional halving tree is PADDING-INVARIANT: pad to a power of two
    and fold, and any all-zero half merges as an exact identity, so the
    grouping of the real elements depends only on their positions.  This
    is what keeps an instance's result independent of the (ragged,
    bucketed) envelope of the batch it is solved in.
    """
    n = x.shape[-1]
    if n == 0:
        return jnp.zeros(x.shape[:-1], x.dtype)
    pow2 = 1 << (n - 1).bit_length() if n > 1 else 1
    if pow2 != n:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, pow2 - n)]
        x = jnp.pad(x, pad)
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    return x[..., 0]


def _path_cost_gather(pr_pad: jnp.ndarray, path_edges: jnp.ndarray) -> jnp.ndarray:
    """Per-path price sums: L narrow hop-column gathers, halved positionally.

    The obvious composite — one wide ``(Bt, P*L)`` take_along_axis
    reshaped back and reduced — materializes the (Bt, P, L) intermediate
    and pays XLA:CPU's wide-gather path; L narrow per-hop-column
    ``(Bt, P)`` gathers stay on the vectorized row-gather path (the
    ``sim.engine._path_min_gather`` gotcha; see ROADMAP).  Min accumulates
    exactly in any order, but the sum must keep ``_fold_sum``'s
    padding-invariant association — so instead of stacking the columns
    (which re-materializes the rank-3 intermediate and forfeits the win)
    the halving tree runs over the column LIST: zero-pad to a power of two
    and combine ``cols[i] + cols[i+h]``.  Per element that is the
    identical grouping ``_fold_sum`` applies along the stacked axis, so the
    restructure is bit-exact — 3-10x faster than the wide gather at solver
    shapes (``path_cost_gather`` row in kernels_bench).
    """
    Bt, P, L = path_edges.shape
    if L == 0:
        return jnp.zeros((Bt, P), pr_pad.dtype)
    cols = [
        jnp.take_along_axis(pr_pad, path_edges[:, :, j], axis=1)
        for j in range(L)
    ]
    pow2 = 1 << (L - 1).bit_length() if L > 1 else 1
    if pow2 != L:
        zero = jnp.zeros((Bt, P), pr_pad.dtype)
        cols = cols + [zero] * (pow2 - L)
    while len(cols) > 1:
        h = len(cols) // 2
        cols = [cols[i] + cols[i + h] for i in range(h)]
    return cols[0]


def _masked_softmax(logits: jnp.ndarray) -> jnp.ndarray:
    """Softmax over the last axis with ``-inf`` masking and a fold-sum
    denominator (see ``_fold_sum`` for why not ``jax.nn.softmax``)."""
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.where(jnp.isfinite(logits), jnp.exp(logits - m), 0.0)
    return e / _fold_sum(e)[..., None]


def dense_incidence(path_edges: jnp.ndarray, n_slots: int) -> jnp.ndarray:
    """(P, S) {0,1} incidence from the padded path-edge table (sentinel = S)."""
    P, L = path_edges.shape
    b = jnp.zeros((P, n_slots + 1), jnp.float32)
    b = b.at[jnp.arange(P)[:, None], path_edges].add(1.0)
    return b[:, :n_slots]


def _ordered_fan_in_sum(fr: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Sum ``fr`` entries selected by a fan-in table, LEFT-TO-RIGHT.

    ``fr`` is (Bt, N + 1) with a trailing zero pad; ``table`` is
    (Bt, S, D) of indices into N+1, each row listing one segment's members
    in ascending position order, padded with N.  The D columns are
    accumulated one by one — a trace-time unroll, D is ~tens —
    so each segment's sum associates exactly like the XLA scatter-add it
    replaces (updates applied in position order).  A tree-reduction ``sum``
    here would differ by an ulp and the MW anneal amplifies that into
    visible alpha drift over hundreds of iterations.
    """
    Bt, S, d = table.shape
    acc = jnp.zeros((Bt, S), jnp.float32)
    for j in range(d):
        acc = acc + jnp.take_along_axis(fr, table[:, :, j], axis=1)
    return acc


#: Skip the transposed gather tables when slot-fan-in skew would inflate
#: them past this multiple of the hop count (the solve falls back to the
#: scatter backend).  Random-graph path systems sit far below it: fan-in is
#: within ~4x of the mean at RRG(512..8192).
_GATHER_TABLE_GUARD = 16


def _bucket_up(n: int, step: int) -> int:
    """Round ``n`` up to a multiple of ``step`` (shape-bucketing for jit
    cache reuse across batches of nearby sizes)."""
    return max(((int(n) + step - 1) // step) * step, step)


def _bucket_up_geom(n: int) -> int:
    """Scale-proportional shape bucket: the step is ~n/8 (at least 256), so
    masked-compute waste stays bounded (~12%) while nearby sizes collapse
    onto one compiled shape at every scale."""
    n = max(int(n), 1)
    step = max(256, 1 << max(n.bit_length() - 3, 0))
    return _bucket_up(n, step)


def _seg_passes(owner: np.ndarray) -> int:
    """Static pass count of ``_seg_norm`` for one owner vector: its
    longest commodity (one contiguous run of rows in the canonical layout),
    bucketed like L so that nearby systems share one compiled scan."""
    owner = np.asarray(owner)
    return _bucket_up(int(np.bincount(owner).max()) if owner.size else 1, 4)


def _shift(a: jnp.ndarray, j: int, fill) -> jnp.ndarray:
    """``out[..., p] = a[..., p + j]`` along the last axis, ``fill`` where
    ``p + j`` falls off it (``j < 0`` moves rows toward the end)."""
    cfg = [(0, 0, 0)] * (a.ndim - 1) + [(-j, j, 0)]
    return jax.lax.pad(a, jnp.asarray(fill, a.dtype), cfg)


def _seg_layout(owner: jnp.ndarray, seg_max: int, dummy: int | None = None):
    """Each path row's place in its commodity's run, for ``_seg_norm``.

    ``owner`` is (P,) or (Bt, P) in the canonical layout (CT-ps): owners
    never decrease along a row, so each commodity is one contiguous run of
    at most ``seg_max`` rows.  Returns int32 ``(pos, run)``: ``pos`` the
    row's offset from its run's head, ``run`` the rows from it to the
    run's end (the run's length at its head).  Rows of the ``dummy``
    commodity (a stacked batch's padding tail) get ``pos = -1``: their
    divisor is pinned to 1, so its arbitrarily long run never sets the
    pass count.  Dense shifted compares; computed once per program call,
    outside the scan.
    """
    pos = jnp.zeros(owner.shape, jnp.int32)
    run = jnp.ones(owner.shape, jnp.int32)
    for j in range(1, min(seg_max, owner.shape[-1])):
        pos = pos + (_shift(owner, -j, -1) == owner).astype(jnp.int32)
        run = run + (_shift(owner, j, -1) == owner).astype(jnp.int32)
    if dummy is not None:
        pos = jnp.where(owner == dummy, -1, pos)
    return pos, run


def _seg_norm(x: jnp.ndarray, pos: jnp.ndarray, run: jnp.ndarray,
              seg_max: int) -> jnp.ndarray:
    """Divide each split weight by its commodity's sum (``_seg_layout``).

    The sum forms at each run's head LEFT-TO-RIGHT, ``((x0 + x1) + x2)
    ...``, the association of XLA:CPU's in-order scatter-add and of
    ``_ordered_fan_in_sum`` (the masked-off terms add an exact 0), and is
    then broadcast back over the run by ``seg_max`` selects: ``2 seg_max``
    dense passes over ``x``, no scatter or gather.
    """
    n = min(seg_max, x.shape[-1])
    s = x
    for j in range(1, n):
        s = s + jnp.where(j < run, _shift(x, j, 0.0), 0.0)
    div = jnp.ones_like(x)
    for q in range(n):
        div = jnp.where(pos == q, _shift(s, -q, 0.0), div)
    return x / div


def make_loads_fn_batch(
    path_edges: jnp.ndarray,
    n_slots: int,
    backend: str,
    slot_gather: jnp.ndarray | None = None,
):
    """Batched loads-only closure: (Bt, P) rates -> (Bt, S) ``B^T r``.

    Each backend's load accumulation, written once.  The MW solver's fused
    closure (``make_congestion_fn_batch``) adds the costs half to it; the
    flow-level simulator's waterfilling (``repro.sim.engine``) uses it
    alone, since it needs per-slot loads and flow counts but never path
    costs.  ``path_edges`` is the (Bt, P, L) stack, padded with each
    instance's own ``n_slots`` sentinel:

    * ``scatter`` — ONE flat segment-sum over ``Bt * (S + 1)`` slots using
      per-instance slot offsets (instance b's slot e lands at
      ``b*(S+1)+e``, its padding sentinel in b's private garbage slot), so
      the whole batch is a single scatter-add rather than Bt separate ones.
    * ``gather`` — per-slot transposed fan-in tables (``slot_gather``,
      precomputed by ``PathSystemBatch``) turn the accumulation into
      vectorized gathers — ~40x faster than the serialized XLA scatter-add
      on CPU at RRG(512) shapes.  Each slot's fan-in is accumulated
      left-to-right in flat-position order (``_ordered_fan_in_sum``), the
      order the scatter-add applies its updates, so the two backends agree
      BIT-EXACTLY and the MW iteration (whose annealing softmax amplifies
      even 1-ulp load differences over hundreds of steps) follows the
      identical trajectory.
    * ``dense``/``pallas`` — materializes the stacked (Bt, P, S) incidence
      once (hoisted out of any scan by jit) and calls
      ``ops.congestion_loads`` on it.
    """
    Bt, P, L = path_edges.shape
    if backend == "gather":
        if slot_gather is None:
            raise ValueError(
                "gather backend needs the PathSystemBatch fan-in tables"
            )

        def loads_fn(rates):
            fr = jnp.concatenate(
                [
                    jnp.repeat(rates, L, axis=1),
                    jnp.zeros((Bt, 1), jnp.float32),
                ],
                axis=1,
            )
            return _ordered_fan_in_sum(fr, slot_gather)

        return loads_fn
    if backend == "scatter":
        s1 = n_slots + 1
        flat_idx = (
            jnp.arange(Bt, dtype=jnp.int32)[:, None, None] * s1 + path_edges
        ).reshape(-1)

        def loads_fn(rates):
            r = jnp.repeat(rates.reshape(-1), L)
            return (
                jnp.zeros((Bt * s1,), jnp.float32)
                .at[flat_idx]
                .add(r)
                .reshape(Bt, s1)[:, :n_slots]
            )

        return loads_fn
    b3, kernel_backend = _stacked_incidence(path_edges, n_slots, backend)

    def loads_fn(rates):
        return ops.congestion_loads(b3, rates, backend=kernel_backend)

    return loads_fn


def _stacked_incidence(path_edges: jnp.ndarray, n_slots: int, backend: str):
    """The (Bt, P, S) incidence and the ``ops`` kernel backend of the
    ``dense`` (kernel on TPU, reference elsewhere) and ``pallas`` (kernel
    everywhere) congestion backends."""
    if backend not in ("dense", "pallas"):
        raise ValueError(f"unknown congestion backend: {backend!r}")
    b3 = jax.vmap(lambda pe: dense_incidence(pe, n_slots))(path_edges)
    return b3, "pallas" if backend == "pallas" else "auto"


def make_congestion_fn_batch(
    path_edges: jnp.ndarray,
    n_slots: int,
    backend: str,
    slot_gather: jnp.ndarray | None = None,
):
    """Batched fused (loads, costs) = (B^T r, B w) closure: (Bt, P) rates
    and (Bt, S) prices to (Bt, S) loads and (Bt, P) costs.

    ``scatter`` and ``gather`` are ``make_loads_fn_batch``'s load half plus
    the per-path price sums of ``_path_cost_gather``.  ``dense``/``pallas``
    make both products in ONE ``ops.congestion`` pass over the stacked
    incidence: the fused kernel reads each B tile from HBM once per
    iteration.
    """
    if backend in ("dense", "pallas"):
        b3, kernel_backend = _stacked_incidence(path_edges, n_slots, backend)

        def fused(rates, prices):
            return ops.congestion(b3, rates, prices, backend=kernel_backend)

        return fused
    loads_fn = make_loads_fn_batch(path_edges, n_slots, backend, slot_gather)

    def fused(rates, prices):
        loads = loads_fn(rates)
        pr_pad = jnp.concatenate(
            [prices, jnp.zeros((prices.shape[0], 1), jnp.float32)], axis=1
        )
        return loads, _path_cost_gather(pr_pad, path_edges)

    return fused


def _warm_split(ps: PathSystem, warm: "FlowResult | np.ndarray") -> np.ndarray:
    """Initial per-path split from a predecessor flow vector via ``row_map``.

    ``update_path_system`` stamps ``ps.row_map`` with each path row's index
    into the predecessor path system; rows carried over inherit the previous
    solution's rate as their initial split weight.  Fresh rows (and carried
    rows the previous solve zeroed out) get a small floor share of their
    commodity — MW updates are multiplicative, so a hard zero could never
    recover.
    """
    rates = warm.rates if isinstance(warm, FlowResult) else np.asarray(warm)
    x0 = np.ones(ps.n_paths, dtype=np.float32)
    rm = ps.row_map
    if rm is None or len(rates) == 0:
        return x0
    ok = (rm >= 0) & (rm < len(rates))
    x0 = np.where(ok, rates[np.clip(rm, 0, len(rates) - 1)], 0.0).astype(np.float32)
    ssum = np.bincount(ps.path_owner, weights=x0, minlength=ps.n_commodities)
    cnt = np.bincount(ps.path_owner, minlength=ps.n_commodities)
    mean = (ssum / np.maximum(cnt, 1)).astype(np.float32)
    floor = np.where(mean[ps.path_owner] > 0, 0.05 * mean[ps.path_owner], 1.0)
    return np.maximum(x0, floor)


# --------------------------------------------------------------------------- #
# Batched multi-instance MW solver
# --------------------------------------------------------------------------- #


@dataclasses.dataclass
class PathSystemBatch:
    """Pad-and-stack of B independent path systems for one batched MW solve.

    Instances are padded to the common (P_max, L_max, S_max, K_max)
    envelope:

    * padded SLOTS (beyond an instance's ``n_slots``) carry infinite
      capacity (``inv_cap`` 0) and are masked out of the softmax via
      ``slot_valid`` — they contribute zero load, zero price, and zero
      softmax mass, so per-instance iterates match the unpadded solve;
    * padded PATH rows belong to a dummy commodity (index K_max) with zero
      demand: they ship zero rate and see zero gradient, and their split
      weight normalizes within the dummy commodity only;
    * an instance's own padding sentinel (its ``n_slots``) lands either on
      one of its padded slots or, for the widest instance, on the shared
      garbage slot — harmless either way.

    A sweep over traffic matrices on one routing is B copies of that path
    system, each with its own demands.

    Construction also precomputes the TRANSPOSED fan-in table that backs
    the ``gather`` congestion path (the CPU default):
    ``slot_gather[b, s, :]`` holds the flat positions (``p * L + l``) of
    every real path hop crossing slot s, padded with an out-of-range
    sentinel that gathers a zero.  Slot loads then become vectorized
    gather+sum instead of an XLA scatter-add (which executes as a
    serialized element loop on CPU and dominates the scatter backend's
    iteration).  A skew guard skips the table when one slot's fan-in would
    blow it up past ``_GATHER_TABLE_GUARD`` times the hop count — the
    solve then falls back to ``scatter`` (``congestion_backend``).  The
    per-commodity split sums need no table: each instance's rows keep the
    canonical layout, every commodity one contiguous run and the dummy's
    rows at the tail (``seg_max``, ``_seg_norm``).
    """

    path_edges: np.ndarray  # (B, P, L) int32
    path_owner: np.ndarray  # (B, P) int32
    demands: np.ndarray  # (B, K + 1 dummy) f32
    inv_cap: np.ndarray  # (B, S) f32, 0 on padded slots
    slot_valid: np.ndarray  # (B, S) bool
    n_paths: np.ndarray  # (B,) true per-instance path counts
    systems: list  # the original PathSystem objects (result slicing, warm)
    # transposed fan-in table for the gather backend (None: skew guard hit
    # or a hand-built batch; the solver then falls back to scatter)
    slot_gather: np.ndarray | None = None  # (B, S, D) int32

    @property
    def n_batch(self) -> int:
        return len(self.systems)

    @property
    def p_max(self) -> int:
        return self.path_edges.shape[1]

    @property
    def s_max(self) -> int:
        return self.inv_cap.shape[1]

    @property
    def seg_max(self) -> int:
        """Pass count of the window's split normalisation: the longest
        real commodity over the instances, bucketed (``_seg_passes``)."""
        uniq = {id(ps): ps for ps in self.systems}.values()
        return max(_seg_passes(ps.path_owner) for ps in uniq)

    def congestion_backend(
        self, requested: str
    ) -> tuple[str, np.ndarray | None]:
        """The congestion backend a solve over this batch runs, and the
        fan-in table it needs (``gather`` only; ``None`` otherwise).

        ``auto`` takes ``ops.preferred_congestion_backend``'s batch policy,
        asked with ``n_batch >= 2`` so that a batch of one gets it too
        (gather tables on CPU; dense or scatter by size on TPU).
        ``gather`` falls back to ``scatter`` when the batch has no table
        (skew guard tripped, or a hand-built batch).  Every other request
        passes through.
        """
        backend = requested
        if backend == "auto":
            backend = ops.preferred_congestion_backend(
                self.p_max, self.s_max, n_batch=max(self.n_batch, 2)
            )
        if backend == "gather" and self.slot_gather is None:
            backend = "scatter"
        return backend, self.slot_gather if backend == "gather" else None

    @staticmethod
    def _slot_table(pe2d: np.ndarray, n_slots: int) -> tuple[np.ndarray, np.ndarray]:
        """(positions-by-slot ragged table as (tab, counts)) for ONE instance.

        ``pe2d`` is that instance's (P, L) padded slot matrix; positions are
        flat ``p * L + l`` indices into the row-major hop array.  Entries at
        or beyond ``n_slots`` (padding sentinels) are excluded.
        """
        flat = pe2d.reshape(-1)
        valid = flat < n_slots
        slots = flat[valid]
        pos = np.flatnonzero(valid)
        order = np.argsort(slots, kind="stable")
        slots_s = slots[order]
        cnt = np.bincount(slots_s, minlength=n_slots)
        d = int(cnt.max()) if n_slots else 0
        if d == 0:
            return np.zeros((n_slots, 0), np.int32), cnt
        col = np.arange(len(slots_s)) - np.repeat(np.cumsum(cnt) - cnt, cnt)
        tab = np.full((n_slots, d), pe2d.size, dtype=np.int32)
        tab[slots_s, col] = pos[order]
        return tab, cnt

    @staticmethod
    def _owner_table(owner: np.ndarray, n_comm: int, n_rows: int) -> np.ndarray:
        """(K, D2) path-row table for ONE instance's real commodities (the
        simulator's per-commodity candidate rows), padded with ``n_rows``."""
        order = np.argsort(owner, kind="stable")
        cnt = np.bincount(owner, minlength=n_comm)
        d = int(cnt.max()) if n_comm else 0
        tab = np.full((n_comm, max(d, 1)), n_rows, dtype=np.int32)
        if d:
            col = np.arange(len(owner)) - np.repeat(np.cumsum(cnt) - cnt, cnt)
            tab[owner[order], col] = order
        return tab

    @classmethod
    def from_systems(
        cls, systems: "Sequence[PathSystem]", bucket: bool = True
    ) -> "PathSystemBatch":
        """Stack B (possibly ragged) path systems; empty instances allowed.

        ``bucket=True`` (default) rounds the common envelope up to coarse
        shape buckets so that successive batches with nearby sizes — the
        speculative bisection's waves, a sweep's failure stages — reuse one
        compiled window scan instead of retracing per batch.  All padding
        is masked, so bucketing never changes results (the composition
        invariance the wave driver relies on); it trades a bounded slice of
        extra masked compute for jit-cache hits that otherwise dominate
        mid-size probe wall-clock.
        """
        systems = list(systems)
        if not systems:
            raise ValueError("PathSystemBatch needs at least one path system")
        B = len(systems)
        P = max(max((ps.n_paths for ps in systems), default=0), 1)
        L = max(
            max(
                (ps.path_edges.shape[1] for ps in systems if ps.n_paths),
                default=1,
            ),
            1,
        )
        S = max(max((ps.n_slots for ps in systems), default=0), 1)
        K = max(ps.n_commodities for ps in systems)
        if bucket:
            P, L, S, K = (
                _bucket_up_geom(P),
                _bucket_up(L, 4),
                _bucket_up_geom(S),
                _bucket_up_geom(K),
            )
        pe = np.empty((B, P, L), dtype=np.int32)
        owner = np.full((B, P), K, dtype=np.int32)  # dummy commodity
        dem = np.zeros((B, K + 1), dtype=np.float32)
        inv = np.zeros((B, S), dtype=np.float32)
        sval = np.zeros((B, S), dtype=bool)
        for i, ps in enumerate(systems):
            pe[i, :, :] = ps.n_slots  # instance's own padding sentinel
            if ps.n_paths:
                pb, lb = ps.path_edges.shape
                pe[i, :pb, :lb] = ps.path_edges
                owner[i, :pb] = ps.path_owner
            dem[i, : ps.n_commodities] = ps.demands
            if ps.n_slots:
                inv[i, : ps.n_slots] = 1.0 / ps.capacities
                sval[i, : ps.n_slots] = True
        # transposed fan-in table (positions use the COMMON (P, L) layout)
        per = [cls._slot_table(pe[i], ps.n_slots) for i, ps in enumerate(systems)]
        d = max((t.shape[1] for t, _ in per), default=0)
        if bucket:
            d = _bucket_up(max(d, 1), 8)
        slot_tab: np.ndarray | None = None
        if 0 < S * max(d, 1) <= _GATHER_TABLE_GUARD * (P * L + 1):
            slot_tab = np.full((B, S, max(d, 1)), P * L, dtype=np.int32)
            for i, (t, _) in enumerate(per):
                slot_tab[i, : t.shape[0], : t.shape[1]] = t
        batch = cls(
            path_edges=pe,
            path_owner=owner,
            demands=dem,
            inv_cap=inv,
            slot_valid=sval,
            n_paths=np.array([ps.n_paths for ps in systems], dtype=np.int64),
            systems=systems,
            slot_gather=slot_tab,
        )
        if checks_enabled():
            check_path_system_batch(batch, name="from_systems")
        return batch


def _empty_path_system() -> PathSystem:
    """Zero-path filler instance for batch-size bucketing (inactive from the
    first window; its result row is dropped before returning)."""
    return PathSystem(
        n_edges=0,
        path_edges=np.zeros((0, 1), dtype=np.int32),
        path_len=np.zeros(0, dtype=np.int32),
        path_owner=np.zeros(0, dtype=np.int32),
        demands=np.zeros(0, dtype=np.float32),
        capacities=np.zeros(0, dtype=np.float32),
        n_commodities=0,
    )


@solver_jit(spec="_ir_cases_mw_carry_init_batch")
@functools.partial(jax.jit, static_argnames=("seg_max",))
def _mw_carry_init_batch(x_init, owner, inv_cap, demands, seg_max: int):
    Bt, K = demands.shape
    S = inv_cap.shape[1]
    x0 = _seg_norm(x_init, *_seg_layout(owner, seg_max, K - 1), seg_max)
    return (
        x0,
        jnp.zeros((Bt, S), jnp.float32),
        jnp.zeros((Bt,), jnp.float32),
        x0,
    )


@solver_jit(spec="_ir_cases_mw_window_batch")
@functools.partial(jax.jit, static_argnames=("iters_total", "n_steps",
                                              "seg_max", "backend"))
def _mw_window_batch(
    path_edges,  # (Bt, P, L) int32
    owner,  # (Bt, P) int32
    demands,  # (Bt, K) f32, K - 1 the zero-demand dummy
    inv_cap,  # (Bt, S) f32
    slot_valid,  # (Bt, S) bool
    carry,  # (x (Bt,P), rel_prev (Bt,S), best_alpha (Bt,), best_x (Bt,P))
    t0,  # traced scalar: first global iteration of this window
    valid_steps,  # traced scalar: live steps this window (rest are no-ops)
    active,  # (Bt,) bool: instances still iterating; an adaptive solve
    #          drops frozen ones from the batch, and its rung's filler rows
    #          pass through
    iters_total: int,
    n_steps: int,
    seg_max: int,  # _seg_norm pass count (PathSystemBatch.seg_max)
    backend: str = "scatter",
    slot_gather=None,  # fan-in table; required by the gather backend
):
    """``n_steps`` MW iterations of every instance, from global step ``t0``.

    The temperature anneal is driven by the *global* step over the full
    ``iters_total`` horizon, so chaining windows reproduces the single-scan
    trajectory exactly — which is what lets ``mw_concurrent_flow_batch``
    check each instance's best-alpha plateau between windows (adaptive
    iteration count) without perturbing the converged-run result.

    Two masks compose per step: ``t - t0 < valid_steps`` (TRACED, so an
    adaptive solve always calls with the same static ``n_steps =
    check_every`` and pads a short final window with no-ops — one
    compilation serves each batch size) and ``active`` (per-instance
    early-stop).  A masked step selects the old carry bit-exactly, so a
    row that is not active (a filler of a compacted batch) passes through
    unchanged.  Every op acts within one instance's row, so an instance's
    trajectory does not depend on which rows sit beside it, or how many.
    """
    Bt, K = demands.shape
    S = inv_cap.shape[1]
    fused = make_congestion_fn_batch(path_edges, S, backend, slot_gather)
    pos, run = _seg_layout(owner, seg_max, K - 1)
    dem = jnp.take_along_axis(demands, owner, axis=1)
    neg_inf = jnp.float32(-jnp.inf)

    def body(carry, t):
        x, rel_prev, best_alpha, best_x = carry
        # softmax weights from the PREVIOUS iterate's loads (one-step lag) so
        # the fused kernel computes this iterate's loads and the gradient's
        # path costs in a single pass over B.  rel_prev = 0 at t = 0 gives
        # uniform weights.  GEOMETRIC temperature anneal (0.2 -> 0.005 of
        # max load) + 1/sqrt(t) step decay.
        mx_prev = jnp.max(rel_prev, axis=1)
        frac = 0.2 * (0.005 / 0.2) ** (t.astype(jnp.float32) / iters_total)
        tau = jnp.maximum(mx_prev, 1e-12) * frac
        logits = jnp.where(slot_valid, rel_prev / tau[:, None], neg_inf)
        w = _masked_softmax(logits)
        rates = x * dem
        loads, costs = fused(rates, w * inv_cap)
        rel = loads * inv_cap
        mx = jnp.max(rel, axis=1)
        alpha = 1.0 / jnp.maximum(mx, 1e-12)
        live = active & (t - t0 < valid_steps)
        take = live & (alpha > best_alpha)
        best_alpha = jnp.where(take, alpha, best_alpha)
        best_x = jnp.where(take[:, None], x, best_x)
        g = costs * dem
        g = g / jnp.maximum(jnp.max(g, axis=1, keepdims=True), 1e-12)
        eta = 2.0 / jnp.sqrt(1.0 + t.astype(jnp.float32))
        x_next = _seg_norm(x * jnp.exp(-eta * g), pos, run, seg_max)
        x = jnp.where(live[:, None], x_next, x)
        rel = jnp.where(live[:, None], rel, rel_prev)
        return (x, rel, best_alpha, best_x), None

    carry, _ = jax.lax.scan(body, carry, t0 + jnp.arange(n_steps))
    return carry


@solver_jit(spec="_ir_cases_mw_final_batch")
@functools.partial(jax.jit, static_argnames=("backend",))
def _mw_final_batch(path_edges, owner, demands, inv_cap, carry,
                    backend: str = "scatter", slot_gather=None):
    """One exact evaluation of each last iterate, then the best-iterate
    result."""
    Bt, K = demands.shape
    S = inv_cap.shape[1]
    fused = make_congestion_fn_batch(path_edges, S, backend, slot_gather)
    dem = jnp.take_along_axis(demands, owner, axis=1)
    x, _, best_alpha, best_x = carry
    rates = x * dem
    loads, _ = fused(rates, jnp.zeros((Bt, S), jnp.float32))
    mx = jnp.max(loads * inv_cap, axis=1)
    alpha = 1.0 / jnp.maximum(mx, 1e-12)
    better = alpha > best_alpha
    best_alpha = jnp.where(better, alpha, best_alpha)
    best_x = jnp.where(better[:, None], x, best_x)
    best_rates = best_x * dem * jnp.minimum(best_alpha, 1.0)[:, None]
    return best_alpha, best_rates, 1.0 / best_alpha


#: The window jit itself, for ahead-of-time compiles (``_compile_rungs``):
#: a caller may wrap the module attribute ``_mw_window_batch`` to record
#: its calls, and a wrapper has no ``lower``.
_WINDOW_JIT = _mw_window_batch

#: Window programs compiled ahead of time in this process, by batch size
#: and envelope.
_RUNGS_COMPILED: set = set()


def _rung(n_live: int) -> int:
    """Batch size an adaptive window runs at with ``n_live`` live instances:
    ``n_live`` up to 4, else rounded up to a multiple of 4, so a solve of a
    bucketed batch of B instances compiles at most ``3 + B / 4`` window
    programs."""
    return n_live if n_live <= 4 else _bucket_up(n_live, 4)


def _compile_rungs(sizes, tables, carry, iters, check_every, seg_max,
                   backend):
    """Compile, without running, the adaptive window program at each batch
    size in ``sizes``, once per process.

    A compacted solve runs its later windows at smaller batches, which a
    warm-up solve whose instances all freeze at the first check never
    reaches; compiled here, no window of the solve compiles.
    ``lower(...).compile()`` fills the jit's own cache, so a later call at
    those shapes compiles nothing.  Only the shapes of ``tables`` (the six
    host tables, the fan-in table ``None`` off ``gather``) and of the full
    batch's ``carry`` are read.
    """
    def at(a, size):
        return None if a is None else jax.ShapeDtypeStruct(
            (size,) + a.shape[1:], a.dtype)

    envelope = (iters, check_every, seg_max, backend) + tuple(
        None if a is None else (a.shape[1:], str(a.dtype))
        for a in (*tables, *carry))
    for size in sizes:
        if (size, envelope) in _RUNGS_COMPILED:
            continue
        pe, owner, demands, inv_cap, slot_valid, slot_tab = (
            at(a, size) for a in tables)
        _WINDOW_JIT.lower(
            pe, owner, demands, inv_cap, slot_valid,
            tuple(at(a, size) for a in carry), 0, check_every,
            jax.ShapeDtypeStruct((size,), jnp.bool_), iters, check_every,
            seg_max, backend, slot_tab,
        ).compile()
        _RUNGS_COMPILED.add((size, envelope))


def _write_back(full, rows, carry) -> list:
    """Copy the computed batch's carry to the host and into ``full``, the
    full batch's carry, at the instances ``rows``; returns the copy."""
    got = [np.asarray(a) for a in carry]
    for f, a in zip(full, got):
        f[rows] = a
    return got


def mw_concurrent_flow_batch(
    systems: "PathSystemBatch | Sequence[PathSystem]",
    iters: int = 400,
    backend: str = "auto",
    warm: "Sequence[FlowResult | np.ndarray | None] | None" = None,
    early_stop: bool = False,
    check_every: int = 50,
    rel_tol: float = 1e-3,
    patience: int = 2,
    target_alpha: float | None = None,
) -> list[FlowResult]:
    """Solve B independent MW instances in ONE batched window scan.

    Accepts a ``PathSystemBatch`` or any sequence of ``PathSystem``s (which
    is pad-and-stacked on the fly, the batch size bucketed with empty
    fillers).  An instance's result does not depend on the rest of the
    batch: the adaptive state (plateau early-stop, ``target_alpha``
    cutoff) is tracked PER INSTANCE, and a converged instance leaves the
    computed batch with its carry as it was, while the rest run on.

    ``backend``: ``"auto"`` (gather tables on CPU, dense/scatter by size on
    TPU), ``"gather"``, ``"scatter"``, ``"dense"``, or ``"pallas"`` (force
    the fused kernel, interpret mode off-TPU); resolved by
    ``PathSystemBatch.congestion_backend``.

    ``warm``: per instance, ``None`` or a FlowResult (or raw per-path rate
    vector) from the *predecessor* path system of a delta update, applied
    through that instance's ``row_map`` (``_warm_split``).  Warm-started
    solves reach a given alpha quality in substantially fewer iterations
    on small topology deltas, which is where the expansion/failure sweeps
    spend their time.

    Adaptive iteration count: with ``early_stop=True`` the solve runs in
    ``check_every``-iteration windows and an instance stops once its best
    alpha has improved by less than ``rel_tol`` (relative) for ``patience``
    consecutive windows — the anneal schedule stays pinned to the full
    ``iters`` horizon, so a run that never plateaus is bit-identical to
    ``early_stop=False``.  ``target_alpha`` additionally stops an instance
    as soon as its best (exactly evaluated) alpha reaches it — the
    feasibility-probe mode of the ``core.capacity`` search.
    ``FlowResult.iters`` reports the iterations each instance ran.  At each
    window edge where the live count falls below the batch (empty fillers
    are never live), the next windows run on a batch of the live instances
    only, re-stacked through host copies: the live count up to 4, else
    that rounded up to a multiple of 4 (``_rung``), topped up with frozen
    rows that pass through.  A leaving instance's carry is written back to
    the full batch's, and the final evaluation runs on the full batch.
    Every window program a solve can reach is compiled at its first solve
    of an envelope (``_compile_rungs``), so no later window compiles.

    Metrics: ``mw/solves`` and ``mw/iters`` count the instances solved and
    their iterations, the ``mw/alpha`` gauge holds the last instance's
    alpha, ``mw/windows_batch`` counts adaptive windows,
    ``mw/compactions`` the re-stacks, and
    ``mw/stop/{target,plateau,budget}`` each instance's stop reason.

    Traced (``repro.obs``), the host phases are spans: ``mw/assemble``
    (stacking a sequence; ``rows``), ``mw/upload`` (the host tables sent
    and the carry's set-up; ``bytes``), ``mw/window_batch`` (one per window
    dispatched; ``active`` live instances of ``instances`` computed, the
    batch the window ran at; ``seg_max`` the split normalisation's pass
    count), ``mw/compact`` (adaptive solves only, one per re-stack: the
    batch sizes ``before`` and ``after``), ``mw/sync`` (adaptive solves
    only, one per window: the host's read of the live instances' best
    alphas and the stop decisions) and
    ``mw/readback`` (the final evaluation copied back: the host's wait for
    the device).
    """
    n_asked: int | None = None
    if isinstance(systems, PathSystemBatch):
        batch = systems
    else:
        systems = list(systems)
        n_asked = len(systems)
        # bucket the batch size too (with masked-out empty fillers), so
        # probe waves of nearby sizes land on one compiled window scan
        pad_b = _bucket_up(n_asked, 4) if n_asked > 1 else n_asked
        with obs.span("mw/assemble",
                      rows=sum(ps.n_paths for ps in systems)):
            if pad_b != n_asked:
                systems = systems + [
                    _empty_path_system() for _ in range(pad_b - n_asked)
                ]
            batch = PathSystemBatch.from_systems(systems)
    if checks_enabled():
        check_segment_layout(batch, name="mw_concurrent_flow_batch")
    B = batch.n_batch
    empty = batch.n_paths == 0
    method_tag = "mw-batch"
    if bool(empty.all()):
        out = [FlowResult(0.0, np.zeros(0), np.inf, method_tag, 0)
               for _ in range(B)]
        return out if n_asked is None else out[:n_asked]
    backend, slot_table = batch.congestion_backend(backend)
    method_tag = f"mw-batch-{backend}"
    x_init = np.ones((B, batch.p_max), dtype=np.float32)
    if warm is not None:
        for i, (ps, w) in enumerate(zip(batch.systems, warm)):
            if w is not None and ps.row_map is not None and ps.n_paths:
                x_init[i, : ps.n_paths] = _warm_split(ps, w)
    host = (batch.path_edges, batch.path_owner, batch.demands,
            batch.inv_cap, batch.slot_valid, x_init, slot_table)
    with obs.span("mw/upload",
                  bytes=sum(a.nbytes for a in host if a is not None)):
        pe, owner, demands, inv_cap, slot_valid, x_dev, slot_tab = (
            None if a is None else jnp.asarray(a) for a in host
        )
        seg_max = batch.seg_max
        carry = _mw_carry_init_batch(x_dev, owner, inv_cap, demands, seg_max)
    done = np.zeros(B, dtype=np.int64)
    active = ~empty
    adaptive = early_stop or target_alpha is not None
    if not adaptive:
        with obs.span("mw/window_batch", t0=0, step=iters,
                      active=int(active.sum()), instances=B, seg_max=seg_max):
            carry = _mw_window_batch(
                pe, owner, demands, inv_cap, slot_valid, carry, 0, iters,
                jnp.asarray(active), iters, iters, seg_max, backend, slot_tab,
            )
        done[active] = iters
    else:
        best_prev = np.zeros(B)
        stall = np.zeros(B, dtype=np.int64)
        # the computed batch: the instance each of its rows holds, and its
        # device tables; ``full`` is the full batch's carry on the host,
        # written once the batch is first compacted
        rows = np.arange(B)
        tables = (pe, owner, demands, inv_cap, slot_valid, slot_tab)
        host_tables = (batch.path_edges, batch.path_owner, batch.demands,
                       batch.inv_cap, batch.slot_valid, slot_table)
        full = [np.empty(a.shape, a.dtype) for a in carry]
        # every smaller batch the solve can reach (the live count only falls)
        reach = range(1, int(active.sum()) + 1)
        _compile_rungs(sorted({_rung(n) for n in reach if _rung(n) < B}),
                       host_tables, carry, iters, check_every, seg_max,
                       backend)
        t0 = 0
        while t0 < iters and active.any():
            live = active[rows]
            n_live = int(live.sum())
            size = _rung(n_live)
            if size < len(rows):
                # frozen instances (and the pad) leave the computed batch;
                # a rung above the live count is filled with frozen rows,
                # which pass through
                with obs.span("mw/compact", t0=t0, before=len(rows),
                              after=size):
                    got = _write_back(full, rows, carry)
                    keep = np.sort(np.concatenate([
                        np.flatnonzero(live),
                        np.flatnonzero(~live)[: size - n_live]]))
                    rows = rows[keep]
                    live = active[rows]
                    tables = tuple(None if a is None else jnp.asarray(a[rows])
                                   for a in host_tables)
                    carry = tuple(jnp.asarray(a[keep]) for a in got)
                obs.counter("mw/compactions").inc()
            step = min(check_every, iters - t0)
            with obs.span("mw/window_batch", t0=t0, step=step,
                          active=n_live, instances=len(rows),
                          seg_max=seg_max):
                carry = _mw_window_batch(
                    *tables[:5], carry, t0, step, jnp.asarray(live), iters,
                    check_every, seg_max, backend, tables[5],
                )
                t0 += step
                done[active] += step
            obs.counter("mw/windows_batch").inc()
            # the host waits here for the window, then decides who stops
            with obs.span("mw/sync", t0=t0, active=n_live):
                best = np.asarray(carry[2])
                if obs.trace_enabled():
                    obs.counter_event("mw/alpha_batch_mean",
                                      float(best[live].mean()))
                for j in np.flatnonzero(live):
                    b = rows[j]
                    if target_alpha is not None and best[j] >= target_alpha:
                        active[b] = False
                        obs.counter("mw/stop/target").inc()
                        continue
                    if early_stop:
                        if best[j] - best_prev[b] < rel_tol * max(best[j],
                                                                  1e-12):
                            stall[b] += 1
                            if stall[b] >= patience:
                                active[b] = False
                                obs.counter("mw/stop/plateau").inc()
                                continue
                        else:
                            stall[b] = 0
                        best_prev[b] = max(best[j], best_prev[b])
        if active.any():
            obs.counter("mw/stop/budget").inc(int(active.sum()))
        if len(rows) < B:
            _write_back(full, rows, carry)
            carry = tuple(jnp.asarray(a) for a in full)
    # the host waits here for the device to finish the solve
    with obs.span("mw/readback", instances=B):
        alpha, rates, max_load = _mw_final_batch(
            pe, owner, demands, inv_cap, carry, backend, slot_tab
        )
        alpha = np.asarray(alpha)
        rates = np.asarray(rates)
        max_load = np.asarray(max_load)
    out = []
    for b in range(B):
        if empty[b]:
            out.append(FlowResult(0.0, np.zeros(0), np.inf, method_tag, 0))
        else:
            nb = int(batch.n_paths[b])
            out.append(
                FlowResult(
                    float(alpha[b]), rates[b, :nb].copy(),
                    float(max_load[b]), method_tag, int(done[b]),
                )
            )
            obs.gauge("mw/alpha").set(out[-1].alpha)
    obs.counter("mw/solves").inc(int((~empty).sum()))
    obs.counter("mw/iters").inc(int(done.sum()))
    return out if n_asked is None else out[:n_asked]


def mw_concurrent_flow(
    ps: PathSystem,
    iters: int = 400,
    backend: str = "auto",
    warm: "FlowResult | np.ndarray | None" = None,
    early_stop: bool = False,
    check_every: int = 50,
    rel_tol: float = 1e-3,
    patience: int = 2,
    target_alpha: float | None = None,
) -> FlowResult:
    """MW max concurrent flow of one path system: a batch of one of
    ``mw_concurrent_flow_batch``, whose arguments it takes (``warm`` for
    this one instance)."""
    return mw_concurrent_flow_batch(
        [ps], iters=iters, backend=backend,
        warm=None if warm is None else [warm], early_stop=early_stop,
        check_every=check_every, rel_tol=rel_tol, patience=patience,
        target_alpha=target_alpha,
    )[0]


# --------------------------------------------------------------------------- #
# Exact LP solvers (scipy / HiGHS)
# --------------------------------------------------------------------------- #


def lp_concurrent_flow(ps: PathSystem, alpha_cap: float = 8.0) -> FlowResult:
    """Exact max concurrent flow restricted to the path system."""
    import scipy.sparse as sp
    from scipy.optimize import linprog

    P = ps.n_paths
    if P == 0:
        return FlowResult(0.0, np.zeros(0), np.inf, "lp")
    E, K = ps.n_slots, ps.n_commodities
    # COO assembly in three vectorized strips (the per-path Python loops
    # dominated LP setup on mid-size instances):
    #   directed-slot capacity rows — one entry per real hop,
    #   commodity rows (alpha * d_i - sum_p r_p <= 0),
    #   the alpha column.
    lens = ps.path_len.astype(np.int64)
    hop_mask = np.arange(ps.path_edges.shape[1])[None, :] < lens[:, None]
    rows = np.concatenate(
        [
            ps.path_edges[hop_mask].astype(np.int64),  # row-major: path order
            E + ps.path_owner.astype(np.int64),
            E + np.arange(K, dtype=np.int64),
        ]
    )
    cols = np.concatenate(
        [
            np.repeat(np.arange(P, dtype=np.int64), lens),
            np.arange(P, dtype=np.int64),
            np.full(K, P, dtype=np.int64),
        ]
    )
    vals = np.concatenate(
        [
            np.ones(int(lens.sum())),
            -np.ones(P),
            ps.demands.astype(np.float64),
        ]
    )
    A = sp.coo_matrix((vals, (rows, cols)), shape=(E + K, P + 1)).tocsr()
    b = np.concatenate([ps.capacities.astype(np.float64), np.zeros(K)])
    c = np.zeros(P + 1)
    c[P] = -1.0
    bounds = [(0, None)] * P + [(0, alpha_cap)]
    res = linprog(c, A_ub=A, b_ub=b, bounds=bounds, method="highs")
    if not res.success:
        raise RuntimeError(f"LP failed: {res.message}")
    alpha = float(res.x[P])
    rates = res.x[:P] * min(1.0, alpha) / max(alpha, 1e-12)
    return FlowResult(alpha, rates, 1.0 / max(alpha, 1e-12), "lp")


def lp_edge_concurrent_flow(top, comm, alpha_cap: float = 8.0) -> float:
    """Edge-formulation exact max concurrent flow (small instances only).

    Used in tests to validate that the path system (k paths, bounded slack)
    is rich enough.  Variables: per-commodity directed edge flows.
    """
    import scipy.sparse as sp
    from scipy.optimize import linprog

    N = top.n_switches
    E2 = 2 * top.n_edges  # directed copies (full-duplex: unit cap per direction)
    K = comm.k
    src = np.asarray(comm.src, dtype=np.int64)
    dst = np.asarray(comm.dst, dtype=np.int64)
    dem = np.asarray(comm.demand, dtype=np.float64)
    # directed edge list
    de = np.concatenate([top.edges, top.edges[:, ::-1]], axis=0)  # (E2, 2)
    nvar = K * E2 + 1
    # flow conservation per commodity per node: row i*N + v holds
    # sum_out - sum_in - alpha*d*(v==src_i) + alpha*d*(v==dst_i) = 0.
    # Assembled with index arithmetic over the (commodity x directed-edge)
    # grid — the per-commodity flatnonzero scans were O(K * N * E2).
    i_rep = np.repeat(np.arange(K, dtype=np.int64), E2)
    ee = np.tile(np.arange(E2, dtype=np.int64), K)
    var_cols = i_rep * E2 + ee
    out_rows = i_rep * N + np.tile(de[:, 0].astype(np.int64), K)
    in_rows = i_rep * N + np.tile(de[:, 1].astype(np.int64), K)
    # alpha-column entries: -d at the source row, +d at the destination row
    # (destination only when distinct, matching the src-first branch order)
    ndd = dst != src
    rows = np.concatenate(
        [out_rows, in_rows, np.arange(K) * N + src, np.arange(K)[ndd] * N + dst[ndd]]
    )
    cols = np.concatenate(
        [var_cols, var_cols,
         np.full(K, nvar - 1, dtype=np.int64),
         np.full(int(ndd.sum()), nvar - 1, dtype=np.int64)]
    )
    vals = np.concatenate(
        [np.ones(K * E2), -np.ones(K * E2), -dem, dem[ndd]]
    )
    Aeq = sp.coo_matrix((vals, (rows, cols)), shape=(K * N, nvar)).tocsr()
    beq = np.zeros(K * N)
    # capacity rows: each DIRECTED edge has unit capacity (full duplex)
    A_ub = sp.coo_matrix(
        (np.ones(K * E2), (ee, var_cols)), shape=(E2, nvar)
    ).tocsr()
    b_ub = np.ones(E2)
    c = np.zeros(nvar)
    c[-1] = -1.0
    bounds = [(0, None)] * (nvar - 1) + [(0, alpha_cap)]
    res = linprog(
        c, A_ub=A_ub, b_ub=b_ub, A_eq=Aeq, b_eq=np.asarray(beq), bounds=bounds,
        method="highs",
    )
    if not res.success:
        raise RuntimeError(f"edge LP failed: {res.message}")
    return float(res.x[-1])


# LP failures worth falling back from: our own "LP failed" RuntimeError,
# scipy/HiGHS input rejections (ValueError), and a missing scipy entirely.
_LP_FALLBACK_ERRORS = (RuntimeError, ValueError, ImportError)


def throughput(ps: PathSystem, method: str = "auto", iters: int = 400) -> FlowResult:
    """Concurrent-flow throughput with automatic solver selection.

    ``auto`` dispatches to the exact LP at or below ``LP_PATH_LIMIT`` path
    variables (20000 by default; override with ``REPRO_LP_PATH_LIMIT``) and
    to the MW solver beyond it.
    """
    if method == "lp" or (method == "auto" and ps.n_paths <= LP_PATH_LIMIT):
        try:
            return lp_concurrent_flow(ps)
        except _LP_FALLBACK_ERRORS as exc:
            warnings.warn(
                f"LP solver failed ({type(exc).__name__}: {exc}); "
                "falling back to the MW solver",
                RuntimeWarning,
                stacklevel=2,
            )
            return mw_concurrent_flow(ps, iters=iters)
    return mw_concurrent_flow(ps, iters=iters)


# --------------------------------------------------------------------------- #
# IR audit cases (python -m repro.analysis ir; see INVARIANTS.md JF1xx)
# --------------------------------------------------------------------------- #
# One shape bucket per entry is enough: the JF101–JF104 rules are properties
# of the traced program structure, not of the shapes, and JF105 only needs a
# stable reference point.  Contents are irrelevant — tracing never looks at
# values — so builders hand out zeros/aranges without building a topology.

_IR_P, _IR_L, _IR_S, _IR_K = 6, 3, 8, 3  # paths, max hops, slots, commodities
_IR_B, _IR_D = 2, 4  # batch, gather fan-in width
_IR_SEG = 4  # _seg_passes of the IR owner vector (runs of 2 rows)


def _ir_batch_args():
    import numpy as np

    pe = np.full((_IR_B, _IR_P, _IR_L), _IR_S, np.int32)
    pe[:, :, 0] = np.arange(_IR_P) % _IR_S
    owner = np.sort(np.arange(_IR_P) % _IR_K).astype(np.int32)
    owner2 = np.broadcast_to(owner, (_IR_B, _IR_P)).copy()
    dem2 = np.ones((_IR_B, _IR_K), np.float32)
    inv2 = np.ones((_IR_B, _IR_S), np.float32)
    sval2 = np.ones((_IR_B, _IR_S), bool)
    slot_gather = np.full((_IR_B, _IR_S, _IR_D), _IR_P * _IR_L, np.int32)
    carry_b = (
        np.ones((_IR_B, _IR_P), np.float32),
        np.zeros((_IR_B, _IR_S), np.float32),
        np.zeros(_IR_B, np.float32),
        np.ones((_IR_B, _IR_P), np.float32),
    )
    active = np.ones(_IR_B, bool)
    return pe, owner2, dem2, inv2, sval2, slot_gather, carry_b, active


_IR_DENSE_EXEMPT = {
    "JF101": "dense backend contracts via matmul by design; its reassociation "
    "drift vs scatter/gather is a documented contract (CG-3), not a bug",
}


def _ir_cases_mw_carry_init_batch():
    from ..analysis.registry import AuditCase
    import numpy as np

    def make():
        _, owner2, dem2, inv2, _, _, _, _ = _ir_batch_args()
        return ((np.ones((_IR_B, _IR_P), np.float32), owner2, inv2, dem2),
                {"seg_max": _IR_SEG})

    return [AuditCase(label="batch", make=make)]


def _ir_cases_mw_window_batch():
    from ..analysis.registry import AuditCase
    import numpy as np

    def mk(backend, with_gather):
        def make():
            (pe3, owner2, dem2, inv2, sval2, slot_gather, carry_b,
             active) = _ir_batch_args()
            kw = {"iters_total": 10, "n_steps": 4, "seg_max": _IR_SEG,
                  "backend": backend}
            if with_gather:
                kw["slot_gather"] = jnp.asarray(slot_gather)
            return (
                (pe3, owner2, dem2, inv2, sval2, carry_b, np.int32(0),
                 np.int32(4), active),
                kw,
            )

        return make

    return [
        AuditCase(label="gather", make=mk("gather", True), backend="gather"),
        AuditCase(label="scatter", make=mk("scatter", False), backend="scatter"),
        AuditCase(
            label="dense",
            make=mk("dense", False),
            backend="dense",
            exempt=_IR_DENSE_EXEMPT,
            budget=False,
        ),
    ]


def _ir_cases_mw_final_batch():
    from ..analysis.registry import AuditCase

    def make():
        (pe3, owner2, dem2, inv2, _, slot_gather, carry_b, _) = _ir_batch_args()
        return (
            (pe3, owner2, dem2, inv2, carry_b),
            {"backend": "gather", "slot_gather": jnp.asarray(slot_gather)},
        )

    return [AuditCase(label="gather", make=make, backend="gather")]
