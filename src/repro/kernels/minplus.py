"""Blocked min-plus matrix product Pallas kernel (tropical semiring matmul).

APSP on the switch graph is min-plus matrix powering: with D the weighted
adjacency (0 diagonal, 1 for edges, +inf otherwise),
``D^(2t) = D^t (min,+) D^t`` converges to all-pairs distances in
ceil(log2(diameter)) squarings.  This is the TPU-native formulation of the
paper's path-length machinery (§4.1 Fig 4): dense, regular, VMEM-tileable —
in contrast to the pointer-chasing BFS a CPU implementation would use.

The MXU cannot evaluate (min,+) directly, so the kernel is a VPU reduction
over the K dimension, tiled so the working set stays in VMEM:

  grid = (M/bm, N/bn, K/bk), K innermost for sequential accumulation.
  For each (i, j, k): acc[bm, bn] = min(acc, min_over_t(a[:, t] + b[t, :])).

The K-slice loop is unrolled over the bk dimension with static slices,
keeping the (bm, bn) accumulator resident and avoiding an O(bm*bk*bn)
broadcast in VMEM.
Default tiles (128, 128, 128) hold 3 f32 buffers = 192 KiB << 16 MiB VMEM;
the lane dimension is 128-aligned as the VPU wants.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..analysis.registry import AuditCase, solver_jit

__all__ = ["minplus_pallas", "minplus_kernel", "check_minplus_dtype"]


def check_minplus_dtype(*arrays) -> tuple:
    """Validate/upcast min-plus operand dtypes; raise early on unsupported.

    The tropical product needs an additive identity (+inf) to pad partial
    tiles, so integer and boolean operands cannot flow through the kernel —
    padding them used to silently produce a cryptic downstream error (jnp.pad
    with inf on an int array).  Integer/bool dtypes now raise a clear
    ``ValueError`` at entry (convert hop counts with
    ``repro.core.metrics.hops_to_f32`` first); half-precision floats are
    upcast to float32 (the VPU reduction accumulates in f32 anyway).
    """
    out = []
    for x in arrays:
        x = jnp.asarray(x)
        if not jnp.issubdtype(x.dtype, jnp.floating):
            raise ValueError(
                f"min-plus operands must be floating point (got {x.dtype}): "
                "inf-padding an integer tile is undefined; convert int16 hop "
                "matrices with repro.core.metrics.hops_to_f32 first"
            )
        if x.dtype in (jnp.float16, jnp.bfloat16):
            x = x.astype(jnp.float32)
        out.append(x)
    return tuple(out)


def minplus_kernel(a_ref, b_ref, o_ref):
    """One (bm, bn) output tile; accumulates the min over K blocks.

    The bk rank-1 updates are unrolled at trace time: each takes a static
    column of the A tile and a static row of the B tile.  Mosaic cannot
    lower a slice of a loaded value at a loop-carried index (a
    ``dynamic_slice`` on the lane axis), which a ``fori_loop`` over t needs.
    """

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, jnp.inf)

    a = a_ref[...]  # (bm, bk)
    b = b_ref[...]  # (bk, bn)
    acc = o_ref[...]
    for t in range(a.shape[1]):
        # rank-1 tropical update: column t of a + row t of b
        acc = jnp.minimum(acc, a[:, t : t + 1] + b[t : t + 1, :])
    o_ref[...] = acc


@solver_jit(spec="_ir_cases_minplus")
@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def minplus_pallas(
    a: jax.Array,
    b: jax.Array,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """C[i, j] = min_k A[i, k] + B[k, j], with +inf-padded 128-aligned tiles.

    ``interpret=None`` (default) auto-detects: compiled on TPU, interpreter
    elsewhere.  Pass an explicit bool to override (e.g. interpret=True on TPU
    to debug the kernel body).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    a, b = check_minplus_dtype(a, b)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    mp, np_, kp = (-m) % bm, (-n) % bn, (-k) % bk
    a_p = jnp.pad(a, ((0, mp), (0, kp)), constant_values=jnp.inf)
    b_p = jnp.pad(b, ((0, kp), (0, np_)), constant_values=jnp.inf)
    M, K = a_p.shape
    _, N = b_p.shape
    out = pl.pallas_call(
        minplus_kernel,
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        interpret=interpret,
    )(a_p, b_p)
    return out[:m, :n]


# ---- IR audit cases (python -m repro.analysis ir) ------------------------- #

def _ir_cases_minplus():
    import numpy as np

    def make():
        a = np.ones((8, 8), np.float32)
        return (a, a), {"bm": 8, "bn": 128, "bk": 8, "interpret": True}

    return [AuditCase(label="interpret", make=make, budget=False)]
