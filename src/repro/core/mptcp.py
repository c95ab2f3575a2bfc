"""Fluid-level MPTCP model (paper §5).

The paper runs the MPTCP authors' packet simulator with 8 subflows over the
k=8 shortest paths and reports flow-level normalized throughput.  Packet
simulation is not a JAX-shaped workload; the standard fluid abstraction of
coupled multipath congestion control (Kelly/Wischik) is: at equilibrium,
coupled MPTCP allocates rates approximately at the *proportional-fairness*
optimum over the available path system, subject to link capacities and the
sender NIC cap.

We solve   max  sum_i d_i * log(x_i)
           s.t. x_i = sum_{p in paths(i)} r_p <= d_i  (NIC cap)
                sum_{p: e in p} r_p <= c_e            (link caps)
                r >= 0

by projected gradient ascent with a quadratic penalty on link overload
(jitted JAX scan), followed by a global feasibility rescale.  Tests validate
against the LP/MW solvers: PF throughput <= max-concurrent-flow alpha and
>= alpha for symmetric demands, and the paper's 86-90%-of-optimal headline is
reproduced by benchmarks/fig8_mptcp.py.

The price iteration's two incidence products per step — path prices
``q = B p`` and link loads ``ld = B^T r`` — go through the MW solver's
congestion closure (``core.flow.make_congestion_fn_batch``) as a batch of
one.  The backend is ``ops.preferred_congestion_backend``'s single-instance
choice: a dense incidence for toy instances and scatter otherwise on CPU,
the fused Pallas kernel over a materialized incidence on TPU when it fits.
To let the fused kernel compute both in one pass over B, the price update
uses the previous step's rates (one-step Jacobi lag); the equilibrium is
unchanged and the final exact feasibility rescale is lag-free.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp

from ..analysis.registry import AuditCase, solver_jit
from ..kernels import ops
from .flow import _warm_split, make_congestion_fn_batch
from .routing import PathSystem

__all__ = ["MptcpResult", "mptcp_throughput"]


@dataclasses.dataclass
class MptcpResult:
    per_flow: np.ndarray  # (K,) normalized per-commodity throughput in [0, 1]
    mean_throughput: float
    jain_index: float
    iters: int
    rates: np.ndarray | None = None  # (P,) per-path rates; feeds warm starts

    def summary(self) -> str:
        return (
            f"mean={self.mean_throughput:.4f} jain={self.jain_index:.4f} "
            f"min={self.per_flow.min():.4f} max={self.per_flow.max():.4f}"
        )


@solver_jit(spec="_ir_cases_pf_solve")
@functools.partial(jax.jit, static_argnames=("iters", "backend"))
def _pf_solve(
    path_edges, owner, demands, caps, n_comm: int, iters: int,
    backend: str = "scatter", r_init=None,
):
    """Kelly-style dual (link-price) iteration for coupled multipath PF.

    Prices ``p_e`` ascend on overload; each commodity responds with total rate
    ``min(d_i, w_i / q_i)`` where ``q_i`` is the cheapest path price (this is
    the fluid equilibrium of coupled MPTCP: all traffic gravitates to
    minimum-price paths, total rate follows 1/price).  Rates are split over
    near-minimum-price paths by a softmin.  Polyak-averaged rates over the
    tail half give the reported allocation, then an exact feasibility rescale.

    Each step makes ONE fused congestion call: (ld_prev, q) =
    (B^T r_prev, B p).  The price ascent therefore uses the previous step's
    loads (Jacobi lag) — same fixed point, one pass over B per step.
    """
    P, L = path_edges.shape
    E = caps.shape[0]
    K = demands.shape[0]
    fused_b = make_congestion_fn_batch(path_edges[None], E, backend)

    def fused(rates, prices):
        loads, costs = fused_b(rates[None], prices[None])
        return loads[0], costs[0]

    seg_min_init = jnp.full((K,), jnp.inf, jnp.float32)
    beta0 = 0.2
    temp = 0.05  # softmin temperature over path prices

    def response(q):
        """Commodity rate response to path prices q."""
        qmin = seg_min_init.at[owner].min(q)
        # commodity rate response (w_i = d_i: weighted PF, NIC-capped)
        x = jnp.minimum(demands, demands / jnp.maximum(qmin, 1e-3))
        # softmin split over that commodity's paths
        z = jnp.exp(-(q - qmin[owner]) / temp)
        zsum = jnp.zeros((K,), jnp.float32).at[owner].add(z)
        return x[owner] * z / jnp.maximum(zsum[owner], 1e-9)

    def body(carry, t):
        p, r_prev, r_avg, n_avg = carry
        ld_prev, q = fused(r_prev, p)
        r = response(q)
        beta = beta0 / jnp.sqrt(1.0 + t.astype(jnp.float32))
        p = jnp.maximum(p + beta * (ld_prev - caps) / jnp.maximum(caps, 1e-9), 0.0)
        # tail averaging
        take = t >= (iters // 2)
        r_avg = jnp.where(take, r_avg + r, r_avg)
        n_avg = jnp.where(take, n_avg + 1.0, n_avg)
        return (p, r, r_avg, n_avg), None

    p0 = jnp.full((E,), 0.1, jnp.float32)
    # seed the lagged rates with the response to the initial prices — or,
    # warm-starting from a predecessor allocation, with its mapped rates
    if r_init is None:
        _, q0 = fused(jnp.zeros((P,), jnp.float32), p0)
        r0 = response(q0)
    else:
        r0 = r_init
    (p, r_last, r_avg, n_avg), _ = jax.lax.scan(
        body, (p0, r0, jnp.zeros((P,), jnp.float32), jnp.float32(0.0)),
        jnp.arange(iters), length=iters,
    )
    r = r_avg / jnp.maximum(n_avg, 1.0)
    # exact feasibility: globally rescale by worst overload, then re-cap NICs
    ld, _ = fused(r, jnp.zeros((E,), jnp.float32))
    scale = jnp.maximum(jnp.max(ld / jnp.maximum(caps, 1e-9)), 1.0)
    r = r / scale
    x = jnp.zeros((K,), jnp.float32).at[owner].add(r)
    x = jnp.minimum(x, demands)
    return x, r


def mptcp_throughput(
    ps: PathSystem,
    iters: int = 2000,
    backend: str = "auto",
    warm: "MptcpResult | np.ndarray | None" = None,
) -> MptcpResult:
    """Fluid MPTCP throughput; ``warm`` seeds the price iteration's lagged
    rates from a predecessor allocation through ``ps.row_map`` (set by
    ``routing.update_path_system``) — the same plumbing as the MW solver's
    warm start, for expansion/failure sweeps that chain path-system deltas.
    """
    if ps.n_paths == 0:
        return MptcpResult(np.zeros(0), 0.0, 1.0, 0, np.zeros(0))
    if backend == "auto":
        backend = ops.preferred_congestion_backend(ps.n_paths, ps.n_slots)
    r_init = None
    if warm is not None and ps.row_map is not None:
        prev = warm.rates if isinstance(warm, MptcpResult) else warm
        if prev is not None and len(prev):
            r_init = jnp.asarray(_warm_split(ps, np.asarray(prev)))
    x, r = _pf_solve(
        jnp.asarray(ps.path_edges),
        jnp.asarray(ps.path_owner),
        jnp.asarray(ps.demands, dtype=jnp.float32),
        jnp.asarray(ps.capacities, dtype=jnp.float32),
        ps.n_commodities,
        iters,
        backend,
        r_init,
    )
    x = np.asarray(x)
    norm = x / np.maximum(ps.demands, 1e-9)
    # Jain's fairness index over per-commodity normalized throughput
    jain = float((norm.sum() ** 2) / (len(norm) * (norm**2).sum() + 1e-12))
    return MptcpResult(norm, float(norm.mean()), jain, iters, np.asarray(r))


# ---- IR audit cases (python -m repro.analysis ir) ------------------------- #

def _ir_cases_pf_solve():
    from .flow import _ir_batch_args

    def make():
        pe3, owner2, dem2, inv2, _, _, _, _ = _ir_batch_args()
        pe, owner, demands, caps = pe3[0], owner2[0], dem2[0], 1.0 / inv2[0]
        return (pe, owner, demands, caps, int(demands.shape[0])), {
            "iters": 8,
            "backend": "scatter",
        }

    return [AuditCase(label="scatter", make=make, backend="scatter")]
