"""Fused congestion kernel: edge loads and path prices in one pass.

The inner loop of every throughput solver (flow.py MW iteration, mptcp.py
price iteration) needs, per step, BOTH

    loads[e]  = sum_p rates[p]  * B[p, e]        (= B^T r)
    costs[p]  = sum_e prices[e] * B[p, e]        (= B  w)

where B is the {0,1} path x directed-edge incidence matrix — by far the
largest operand.  Computing the two products separately reads B from HBM
twice; this kernel FUSES them, reading each B tile once and feeding the MXU
twice per tile (once per product).  That halves HBM traffic for a
memory-bound op — the kind of TPU-native restructuring the brief asks for
(the paper's CPLEX solver has no analogue of this loop; it is our
reformulation of the multicommodity inner product).

Grid: (P/bp, E/be), E innermost.
  costs tile (bp, 1)    accumulates across the E-blocks  (init at ei == 0)
  loads block (E/be, be) stays resident for the whole grid; row ei
                        accumulates across the P-blocks  (set at pi == 0)
B tiles are (bp, be).  The loads block is resident because Pallas on TPU
keeps an output block's contents only across consecutive grid steps that
share its index: a (1, be) loads tile indexed by ei would be revisited once
per P-block, which the TPU interpreter refuses and the compiler does not
promise to read back.  The resident block costs E*4 bytes of VMEM (1.2 MB
at 2E = 295k).

Batched form: a stacked rank-3 incidence (Bt, P, E) with (Bt, P) rates and
(Bt, E) prices runs the same kernel under a (Bt, P/bp, E/be) grid — the
batch dimension is outermost, so each batch member still makes exactly one
pass over its own B tiles per call, its loads block is resident over its
whole (P/bp, E/be) sub-grid, and the accumulators reset when the grid
advances to the next member (pi == 0 / ei == 0 hold at each member's first
visit).  This is the inner loop of ``core.flow.mw_concurrent_flow_batch``
on TPU: Bt independent MW instances per iteration with one fused launch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..analysis.registry import AuditCase, solver_jit

__all__ = [
    "congestion_pallas",
    "congestion_kernel",
    "congestion_batch_kernel",
    "check_congestion_dtype",
]


def check_congestion_dtype(incidence, rates, prices) -> tuple:
    """Validate congestion operand dtypes before the zero-pad (JF004).

    The incidence matrix is {0,1} and may arrive as bool/int/float — all
    cast exactly to the kernel's float32 tiles.  Complex or non-numeric
    operands would be silently truncated by ``astype(float32)`` *after*
    padding, so they are rejected here with a clear error; float64
    rates/prices are accepted (the MXU accumulates in f32 anyway) but the
    cast is explicit and pre-pad rather than incidental.
    """
    out = []
    for label, x in (("incidence", incidence), ("rates", rates),
                     ("prices", prices)):
        x = jnp.asarray(x)
        ok = (
            jnp.issubdtype(x.dtype, jnp.floating)
            or jnp.issubdtype(x.dtype, jnp.integer)
            or jnp.issubdtype(x.dtype, jnp.bool_)
        )
        if not ok:
            raise ValueError(
                f"congestion {label} must be bool/integer/floating "
                f"(got {x.dtype}): the fused kernel computes in float32 and "
                "anything else would be silently truncated by the cast"
            )
        out.append(x.astype(jnp.float32))
    return tuple(out)


def _dot(x, y):
    """f32 MXU product at full precision: rates and prices are f32, and a
    single bf16 pass would round them to 8 mantissa bits."""
    return jnp.dot(x, y, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _accumulate_loads(loads_ref, pi, ei, contrib):
    """Add one (1, be) loads contribution into row ``ei`` of the resident
    (E/be, be) loads block; the first P-block writes instead of adding."""

    @pl.when(pi == 0)
    def _first():
        loads_ref[pl.ds(ei, 1), :] = contrib

    @pl.when(pi > 0)
    def _rest():
        loads_ref[pl.ds(ei, 1), :] += contrib


def congestion_kernel(b_ref, r_ref, w_ref, loads_ref, costs_ref):
    pi = pl.program_id(0)
    ei = pl.program_id(1)

    @pl.when(ei == 0)
    def _init_costs():
        costs_ref[...] = jnp.zeros_like(costs_ref)

    b = b_ref[...]  # (bp, be)
    r = r_ref[...]  # (1, bp)
    w = w_ref[...]  # (1, be)
    # loads block: r (1, bp) @ B (bp, be) -> (1, be)
    _accumulate_loads(loads_ref, pi, ei, _dot(r, b))
    # costs block: B (bp, be) @ w^T (be, 1) -> (bp, 1)
    costs_ref[...] += _dot(b, w.T)


def congestion_batch_kernel(b_ref, r_ref, w_ref, loads_ref, costs_ref):
    """Per-batch-member fused pass; grid (Bt, P/bp, E/be), E innermost."""
    pi = pl.program_id(1)
    ei = pl.program_id(2)

    @pl.when(ei == 0)
    def _init_costs():
        costs_ref[...] = jnp.zeros_like(costs_ref)

    b = b_ref[0]  # (bp, be)
    r = r_ref[0]  # (1, bp)
    w = w_ref[0]  # (1, be)
    _accumulate_loads(loads_ref.at[0], pi, ei, _dot(r, b))
    costs_ref[0, ...] += _dot(b, w.T)


@solver_jit(spec="_ir_cases_congestion_batch")
@functools.partial(jax.jit, static_argnames=("bp", "be", "interpret"))
def _congestion_pallas_batch(
    incidence: jax.Array,  # (Bt, P, E) {0,1}
    rates: jax.Array,  # (Bt, P)
    prices: jax.Array,  # (Bt, E)
    bp: int = 128,
    be: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    Bt, P, E = incidence.shape
    incidence, rates, prices = check_congestion_dtype(incidence, rates, prices)
    pp, ep = (-P) % bp, (-E) % be
    b_p = jnp.pad(incidence, ((0, 0), (0, pp), (0, ep)))
    r_p = jnp.pad(rates, ((0, 0), (0, pp)))[:, None, :]
    w_p = jnp.pad(prices, ((0, 0), (0, ep)))[:, None, :]
    _, Pp, Ep = b_p.shape
    ne = Ep // be
    loads, costs = pl.pallas_call(
        congestion_batch_kernel,
        grid=(Bt, Pp // bp, Ep // be),
        in_specs=[
            pl.BlockSpec((1, bp, be), lambda bi, pi, ei: (bi, pi, ei)),
            pl.BlockSpec((1, 1, bp), lambda bi, pi, ei: (bi, 0, pi)),
            pl.BlockSpec((1, 1, be), lambda bi, pi, ei: (bi, 0, ei)),
        ],
        out_specs=[
            pl.BlockSpec((1, ne, be), lambda bi, pi, ei: (bi, 0, 0)),
            pl.BlockSpec((1, bp, 1), lambda bi, pi, ei: (bi, pi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bt, ne, be), jnp.float32),
            jax.ShapeDtypeStruct((Bt, Pp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(b_p, r_p, w_p)
    return loads.reshape(Bt, Ep)[:, :E], costs[:, :P, 0]


@solver_jit(spec="_ir_cases_congestion")
@functools.partial(jax.jit, static_argnames=("bp", "be", "interpret"))
def congestion_pallas(
    incidence: jax.Array,  # (P, E) {0,1}, or stacked (Bt, P, E)
    rates: jax.Array,  # (P,), or (Bt, P)
    prices: jax.Array,  # (E,), or (Bt, E)
    bp: int = 128,
    be: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (loads (E,), costs (P,)) = (B^T r, B w), fused single pass.

    A rank-3 ``incidence`` (with matching rank-2 rates/prices) computes Bt
    independent products under a (Bt, P/bp, E/be) grid — see the module
    docstring — returning (Bt, E) loads and (Bt, P) costs.

    ``interpret=None`` (default) auto-detects: compiled on TPU, interpreter
    elsewhere.  Pass an explicit bool to override.
    """
    if incidence.ndim == 3:
        return _congestion_pallas_batch(
            incidence, rates, prices, bp=bp, be=be, interpret=interpret
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    P, E = incidence.shape
    incidence, rates, prices = check_congestion_dtype(incidence, rates, prices)
    pp, ep = (-P) % bp, (-E) % be
    b_p = jnp.pad(incidence, ((0, pp), (0, ep)))
    r_p = jnp.pad(rates, (0, pp))[None, :]  # (1, Pp)
    w_p = jnp.pad(prices, (0, ep))[None, :]  # (1, Ep)
    Pp, Ep = b_p.shape
    ne = Ep // be
    loads, costs = pl.pallas_call(
        congestion_kernel,
        grid=(Pp // bp, Ep // be),
        in_specs=[
            pl.BlockSpec((bp, be), lambda pi, ei: (pi, ei)),
            pl.BlockSpec((1, bp), lambda pi, ei: (0, pi)),
            pl.BlockSpec((1, be), lambda pi, ei: (0, ei)),
        ],
        out_specs=[
            pl.BlockSpec((ne, be), lambda pi, ei: (0, 0)),
            pl.BlockSpec((bp, 1), lambda pi, ei: (pi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((ne, be), jnp.float32),
            jax.ShapeDtypeStruct((Pp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(b_p, r_p, w_p)
    return loads.reshape(Ep)[:E], costs[:P, 0]


# ---- IR audit cases (python -m repro.analysis ir) ------------------------- #

_IR_MXU_EXEMPT = {
    "JF101": "the fused congestion kernel IS the dense-incidence matmul "
    "backend; its reassociation drift vs scatter/gather is the documented "
    "dense-backend contract (CG-3)",
}


def _ir_cases_congestion():
    import numpy as np

    def make():
        inc = np.ones((4, 6), np.float32)
        return (inc, np.ones(4, np.float32), np.ones(6, np.float32)), {
            "bp": 8, "be": 128, "interpret": True,
        }

    return [AuditCase(label="interpret", make=make, exempt=_IR_MXU_EXEMPT,
                      budget=False)]


def _ir_cases_congestion_batch():
    import numpy as np

    def make():
        inc3 = np.ones((2, 4, 6), np.float32)
        return (inc3, np.ones((2, 4), np.float32),
                np.ones((2, 6), np.float32)), {
            "bp": 8, "be": 128, "interpret": True,
        }

    return [AuditCase(label="interpret", make=make, exempt=_IR_MXU_EXEMPT,
                      budget=False)]
