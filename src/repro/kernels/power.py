"""Blocked MXU matmul Pallas kernel for spectral power iteration.

The bisection machinery (paper §4.1 Fig 1, §4.2 Fig 6) lower-bounds cut
widths with lambda_2 of the graph Laplacian, computed by deflated power
iteration on B = cI - L.  The hot loop is ``B @ V`` where V packs a block of
iteration vectors — a skinny dense matmul.  On TPU this is MXU work; the
kernel is a standard three-loop blocked matmul with a VMEM-resident f32
accumulator tile and 128-aligned tiles (MXU systolic shape).

Reused by the congestion kernel's dense-incidence mode; exposed generically
as ``matmul_pallas``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..analysis.registry import AuditCase, solver_jit

__all__ = ["matmul_pallas", "matmul_kernel", "check_matmul_dtype"]


def check_matmul_dtype(*arrays) -> tuple:
    """Validate/upcast matmul operand dtypes before the zero-pad (JF004).

    The MXU path accumulates in float32; integer/bool operands would hit
    the systolic array with an unsupported element type only after the
    tiles were already padded, so they are rejected at entry with a clear
    error, and half-precision floats are upcast to float32 (mirrors
    ``minplus.check_minplus_dtype``).
    """
    out = []
    for x in arrays:
        x = jnp.asarray(x)
        if not jnp.issubdtype(x.dtype, jnp.floating):
            raise ValueError(
                f"matmul operands must be floating point (got {x.dtype}): "
                "cast explicitly before calling matmul_pallas"
            )
        if x.dtype in (jnp.float16, jnp.bfloat16):
            x = x.astype(jnp.float32)
        out.append(x)
    return tuple(out)


def matmul_kernel(a_ref, b_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=o_ref.dtype,
    )


@solver_jit(spec="_ir_cases_matmul")
@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """C = A @ B with zero-padded 128-aligned VMEM tiles, f32 accumulation.

    ``interpret=None`` (default) auto-detects: compiled on TPU, interpreter
    elsewhere.  Pass an explicit bool to override.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    a, b = check_matmul_dtype(a, b)
    mp, np_, kp = (-m) % bm, (-n) % bn, (-k) % bk
    a_p = jnp.pad(a, ((0, mp), (0, kp)))
    b_p = jnp.pad(b, ((0, kp), (0, np_)))
    M, K = a_p.shape
    _, N = b_p.shape
    out_dtype = jnp.promote_types(a.dtype, jnp.float32)
    out = pl.pallas_call(
        matmul_kernel,
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
    )(a_p, b_p)
    return out[:m, :n]


# ---- IR audit cases (python -m repro.analysis ir) ------------------------- #

def _ir_cases_matmul():
    import numpy as np

    def make():
        a = np.ones((8, 8), np.float32)
        return (a, a), {"bm": 8, "bn": 128, "bk": 8, "interpret": True}

    return [AuditCase(
        label="interpret",
        make=make,
        exempt={"JF101": "a matmul kernel contracts by definition; no "
                "bit-exactness contract applies to the spectral-gap path"},
        budget=False,
    )]
