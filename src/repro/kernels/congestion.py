"""Fused congestion kernel: edge loads and path prices in one pass.

The inner loop of every throughput solver (flow.py MW iteration, mptcp.py
price iteration) needs, per step, BOTH

    loads[e]  = sum_p rates[p]  * B[p, e]        (= B^T r)
    costs[p]  = sum_e prices[e] * B[p, e]        (= B  w)

where B is the {0,1} path x directed-edge incidence matrix — by far the
largest operand.  Computing the two products separately reads B from HBM
twice; this kernel FUSES them, reading each B tile once and forming both
products from it.  The call is bound by HBM bandwidth: B is read once,
everything else is a row or a column of it.

Each grid step reads one multi-MiB (bp, be) tile and forms both products on
the VPU as masked sums: B holds only 0 and 1, so every term ``b * r`` and
``b * w`` is an exact f32 value.  A loop walks the tile in slabs of 8 rows
(one f32 sublane tile), so no (bp, be) temporary is formed:

  loads  the slab's ``b * r`` adds elementwise into an (8, be) block of
         partial sums, one per sublane; the wrapper sums its 8 rows.
  costs  the slab's ``b * w`` reduces along lanes into the slab's 8 rows of
         the (bp, 1) costs block.

The products are not MXU dots because ``r @ B`` (M = 1) and ``B @ w.T``
(N = 1) fill one row or column of the systolic array, and at
``Precision.HIGHEST`` (a single bf16 pass would round rates and prices to
8 mantissa bits) each takes several passes: per tile they cost more than
the tile's DMA.  On the VPU the kernel runs at the speed of a plain XLA
read of B (TPU v5e, (4, 24576, 10240) f32: 5.44 ms a call, against 5.36 ms
for an XLA column sum of B and 4.92 ms for its bytes at 819 GB/s).

The tile comes from the operand's shape (``_congestion_tiles``), so that a
grid step's fixed cost is paid per few MiB read: be is the whole 128-aligned
S while a tile of ``_MIN_ROWS`` rows fits the step budget ``_STEP_BYTES``,
else the widest 128-multiple dividing it that does; then bp is the tallest
8-multiple dividing the 8-aligned P that fits.  Dividing the aligned dims
keeps the zero pad a no-op for aligned operands: a pad copies all of B.

Grid: (Bt, P/bp, E/be), E innermost.
  costs block (bp, 1)   accumulates across the E-blocks  (init at ei == 0)
  loads block (8 E/be, be) stays resident for the whole member sub-grid;
                        rows [8 ei, 8 ei + 8) accumulate across the P-blocks
                        (zeroed at pi == 0)
The loads block is resident because Pallas on TPU keeps an output block's
contents only across consecutive grid steps that share its index: a loads
tile indexed by ei would be revisited once per P-block, which the TPU
interpreter refuses and the compiler does not promise to read back.

The batch dimension is outermost, so each member of a stacked rank-3
incidence (Bt, P, E) makes exactly one pass over its own B tiles per call,
and the accumulators reset when the grid advances to the next member.  This
is the inner loop of ``core.flow.mw_concurrent_flow_batch`` on TPU: Bt
independent MW instances per iteration with one fused launch.  A rank-2
incidence runs as a batch of one.
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
    "check_congestion_dtype",
]

#: Rows of one VPU slab: one f32 sublane tile.
_SLAB = 8
#: VMEM bytes of one grid step's blocks; Pallas double-buffers them.  On a
#: TPU v5e, full-width tiles of 32 to 192 rows ran at one speed; 6 MiB gives
#: 128 rows at 10,240 slots and stays inside the default scoped VMEM.
_STEP_BYTES = 6 << 20
#: Fewest rows a full-width tile may have: below it, E is split.
_MIN_ROWS = 64


def check_congestion_dtype(incidence, rates, prices) -> tuple:
    """Validate congestion operand dtypes before the zero-pad (JF004).

    The incidence matrix is {0,1} and may arrive as bool/int/float — all
    cast exactly to the kernel's float32 tiles.  Complex or non-numeric
    operands would be silently truncated by ``astype(float32)`` *after*
    padding, so they are rejected here with a clear error; float64
    rates/prices are accepted (the kernel sums in f32 anyway) but the
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


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _step_bytes(bp: int, be: int) -> int:
    """VMEM bytes of one step's f32 blocks as the TPU lays them out: the
    (bp, be) tile, the rates and costs columns (bp, 1) padded to 128 lanes,
    the prices row (1, be) and the loads slab (8, be) on 8 sublanes each."""
    be = _round_up(be, 128)
    return 4 * (bp * be + 2 * bp * 128 + 2 * _SLAB * be)


def _congestion_tiles(P: int, S: int) -> tuple[int, int]:
    """(bp, be) for a (P, S) f32 incidence: be is the 128-aligned S when
    ``_MIN_ROWS`` rows of it fit ``_STEP_BYTES``, else the largest multiple
    of 128 dividing it that does; bp is the largest multiple of 8 dividing
    the 8-aligned P whose step fits (at least 8)."""
    Pp, Sp = _round_up(P, _SLAB), _round_up(S, 128)
    be = max(c for c in range(128, Sp + 1, 128)
             if Sp % c == 0 and (c == 128 or
                                 _step_bytes(_MIN_ROWS, c) <= _STEP_BYTES))
    bp = max(c for c in range(_SLAB, Pp + 1, _SLAB)
             if Pp % c == 0 and (c == _SLAB or
                                 _step_bytes(c, be) <= _STEP_BYTES))
    return bp, be


def congestion_kernel(b_ref, r_ref, w_ref, loads_ref, costs_ref):
    """Grid step (bi, pi, ei): both products of one (bp, be) tile.

    Blocks: b (1, bp, be), r (1, bp, 1), w (1, 1, be); loads (1, 8 ne, be)
    resident per member, costs (1, bp, 1)."""
    pi = pl.program_id(1)
    ei = pl.program_id(2)
    part = pl.ds(pl.multiple_of(ei * _SLAB, _SLAB), _SLAB)

    @pl.when(ei == 0)
    def _init_costs():
        costs_ref[...] = jnp.zeros_like(costs_ref)

    @pl.when(pi == 0)
    def _init_loads():
        loads_ref[0, part, :] = jnp.zeros(
            (_SLAB, loads_ref.shape[2]), jnp.float32)

    w = w_ref[0]  # (1, be)

    def slab(i, carry):
        rows = pl.ds(pl.multiple_of(i * _SLAB, _SLAB), _SLAB)
        b = b_ref[0, rows, :]  # (8, be)
        loads_ref[0, part, :] += b * r_ref[0, rows, :]
        costs_ref[0, rows, :] += jnp.sum(b * w, axis=1, keepdims=True)
        return carry

    jax.lax.fori_loop(0, b_ref.shape[1] // _SLAB, slab, 0)


@solver_jit(spec="_ir_cases_congestion_batch")
@functools.partial(jax.jit, static_argnames=("bp", "be", "interpret"))
def _congestion_pallas_batch(
    incidence: jax.Array,  # (Bt, P, E) {0,1}
    rates: jax.Array,  # (Bt, P)
    prices: jax.Array,  # (Bt, E)
    bp: int | None = None,
    be: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    Bt, P, E = incidence.shape
    tbp, tbe = _congestion_tiles(P, E)
    bp = tbp if bp is None else bp
    be = tbe if be is None else be
    if bp % _SLAB:
        raise ValueError(f"bp must be a multiple of {_SLAB} (got {bp})")
    incidence, rates, prices = check_congestion_dtype(incidence, rates, prices)
    pp, ep = (-P) % bp, (-E) % be
    b_p = jnp.pad(incidence, ((0, 0), (0, pp), (0, ep)))
    r_p = jnp.pad(rates, ((0, 0), (0, pp)))[:, :, None]
    w_p = jnp.pad(prices, ((0, 0), (0, ep)))[:, None, :]
    _, Pp, Ep = b_p.shape
    ne = Ep // be
    loads, costs = pl.pallas_call(
        congestion_kernel,
        grid=(Bt, Pp // bp, ne),
        in_specs=[
            pl.BlockSpec((1, bp, be), lambda bi, pi, ei: (bi, pi, ei)),
            pl.BlockSpec((1, bp, 1), lambda bi, pi, ei: (bi, pi, 0)),
            pl.BlockSpec((1, 1, be), lambda bi, pi, ei: (bi, 0, ei)),
        ],
        out_specs=[
            pl.BlockSpec((1, _SLAB * ne, be), lambda bi, pi, ei: (bi, 0, 0)),
            pl.BlockSpec((1, bp, 1), lambda bi, pi, ei: (bi, pi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bt, _SLAB * ne, be), jnp.float32),
            jax.ShapeDtypeStruct((Bt, Pp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(b_p, r_p, w_p)
    loads = loads.reshape(Bt, ne, _SLAB, be).sum(axis=2).reshape(Bt, Ep)
    return loads[:, :E], costs[:, :P, 0]


@solver_jit(spec="_ir_cases_congestion")
@functools.partial(jax.jit, static_argnames=("bp", "be", "interpret"))
def congestion_pallas(
    incidence: jax.Array,  # (P, E) {0,1}, or stacked (Bt, P, E)
    rates: jax.Array,  # (P,), or (Bt, P)
    prices: jax.Array,  # (E,), or (Bt, E)
    bp: int | None = None,
    be: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (loads (E,), costs (P,)) = (B^T r, B w), fused single pass.

    A rank-3 ``incidence`` (with matching rank-2 rates/prices) computes Bt
    independent products under a (Bt, P/bp, E/be) grid — see the module
    docstring — returning (Bt, E) loads and (Bt, P) costs.

    ``bp``/``be`` override the tile the operand's shape picks (``None``);
    ``bp`` must be a multiple of 8.  ``interpret=None`` (default)
    auto-detects: compiled on TPU, interpreter elsewhere.  Pass an
    explicit bool to override.
    """
    if incidence.ndim == 3:
        return _congestion_pallas_batch(
            incidence, rates, prices, bp=bp, be=be, interpret=interpret
        )
    loads, costs = _congestion_pallas_batch(
        incidence[None], jnp.asarray(rates)[None], jnp.asarray(prices)[None],
        bp=bp, be=be, interpret=interpret,
    )
    return loads[0], costs[0]


# ---- IR audit cases (python -m repro.analysis ir) ------------------------- #

_IR_DENSE_EXEMPT = {
    "JF101": "the fused congestion kernel IS the dense-incidence backend; "
    "its reassociation drift vs scatter/gather is the documented "
    "dense-backend contract (CG-3)",
}


def _ir_cases_congestion():
    import numpy as np

    def make():
        inc = np.ones((4, 6), np.float32)
        return (inc, np.ones(4, np.float32), np.ones(6, np.float32)), {
            "bp": 8, "be": 128, "interpret": True,
        }

    return [AuditCase(label="interpret", make=make, exempt=_IR_DENSE_EXEMPT,
                      budget=False)]


def _ir_cases_congestion_batch():
    import numpy as np

    def make():
        inc3 = np.ones((2, 4, 6), np.float32)
        return (inc3, np.ones((2, 4), np.float32),
                np.ones((2, 6), np.float32)), {
            "bp": 8, "be": 128, "interpret": True,
        }

    return [AuditCase(label="interpret", make=make, exempt=_IR_DENSE_EXEMPT,
                      budget=False)]
