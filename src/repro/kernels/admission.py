"""Fused admissibility + simplicity prune for the path enumerator.

Each expansion level of the batched k-shortest-path engine
(``repro.core.routing._batched_round``) decides, for every (frontier row,
candidate neighbor) cell, whether stepping there can still complete within
the pair's length budget AND keeps the prefix simple:

    ok[m, c] = dist(cand[m, c], dst[m]) <= rem[m]
               and cand[m, c] not in pref[m, :]

The numpy form materializes an (M, W, C) boolean broadcast for the
membership test — at 10k-switch scale that temporary is the level's peak
allocation.  The kernel here fuses the comparison with a W-step unrolled
loop over the prefix columns, keeping only the (bm, bc) block and
a same-shape accumulator resident; the ref backend is the same computation
as straight-line jnp (the oracle the kernel is validated against).

Every backend computes the identical mask — admissibility is an exact
float comparison on values the caller already gathered, and the membership
test is integer equality — so backend choice (``REPRO_ADMISSION_BACKEND``)
never changes enumerated path sets, only where the level's working set
lives.  This is what lets the enumerator keep its bit-exactness contract
(INVARIANTS.md CT-build) while the prune runs on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..analysis.registry import AuditCase, solver_jit

__all__ = [
    "admission_prune",
    "admission_ref",
    "admission_pallas",
    "check_admission_dtype",
]


def check_admission_dtype(*arrays) -> tuple:
    """Validate/upcast the float operands (distance values, remaining budget).

    The admissibility compare pads its row/column remainders with ``+inf``
    (a padded cell must prune itself), so integer/boolean operands cannot
    flow through the kernel; they raise a clear ``ValueError`` at entry
    instead of failing inside ``jnp.pad``.  Half-precision floats are
    upcast to float32 — distances are small integers stored as f32 and the
    comparison must match the numpy backend bit-for-bit.
    """
    out = []
    for x in arrays:
        x = jnp.asarray(x)
        if not jnp.issubdtype(x.dtype, jnp.floating):
            raise ValueError(
                f"admission operands must be floating point (got {x.dtype}): "
                "inf-padding an integer tile is undefined; gather distance "
                "values from the f32 tile (repro.core.metrics.hops_to_f32)"
            )
        if x.dtype in (jnp.float16, jnp.bfloat16):
            x = x.astype(jnp.float32)
        out.append(x)
    return tuple(out)


def admission_kernel(d_ref, r_ref, c_ref, p_ref, o_ref):
    """One (bm, bc) mask block: compare + prefix-membership over W columns.

    The W prefix columns are unrolled at trace time (W is a few hops, padded
    to a sublane multiple): Mosaic cannot lower a slice of a loaded value at
    a loop-carried index.
    """
    ok = d_ref[...] <= r_ref[...]  # (bm, bc) <= (bm, 1) broadcast
    cand = c_ref[...]  # (bm, bc) int32
    pref = p_ref[...]  # (bm, W) int32, -1 beyond the prefix
    seen = jnp.zeros(cand.shape, dtype=jnp.bool_)
    for t in range(pref.shape[1]):
        seen = seen | (pref[:, t : t + 1] == cand)
    o_ref[...] = (ok & ~seen).astype(jnp.int8)


@solver_jit(spec="_ir_cases_admission")
@functools.partial(jax.jit, static_argnames=("bm", "bc", "interpret"))
def admission_pallas(
    dvals: jax.Array,
    rem: jax.Array,
    cand: jax.Array,
    pref: jax.Array,
    bm: int = 128,
    bc: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """(M, C) admissibility mask with inf/sentinel-padded (bm, bc) tiles.

    ``dvals[m, c]`` is the already-gathered ``dist(cand[m, c], dst[m])``,
    ``rem[m]`` the remaining budget, ``pref`` the (M, W) node prefixes
    padded with -1.  Padded rows/columns hold ``+inf`` distances (prune
    themselves) and a -2 candidate sentinel that never matches a prefix
    entry, so the sliced-back mask equals the unpadded computation exactly.
    ``interpret=None`` auto-detects: compiled on TPU, interpreter elsewhere.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    dvals, rem = check_admission_dtype(dvals, rem)
    cand = jnp.asarray(cand, dtype=jnp.int32)
    pref = jnp.asarray(pref, dtype=jnp.int32)
    m, c = dvals.shape
    w = pref.shape[1]
    mp, cp = (-m) % bm, (-c) % bc
    wp = (-max(w, 1)) % 8  # sublane-pad the prefix block; -1 never matches
    d_p = jnp.pad(dvals, ((0, mp), (0, cp)), constant_values=jnp.inf)
    r_p = jnp.pad(rem[:, None], ((0, mp), (0, 0)))
    c_p = jnp.pad(cand, ((0, mp), (0, cp)), constant_values=-2)
    p_p = jnp.pad(pref, ((0, mp), (0, wp + (0 if w else 1))),
                  constant_values=-1)
    M, C = d_p.shape
    W = p_p.shape[1]
    out = pl.pallas_call(
        admission_kernel,
        grid=(M // bm, C // bc),
        in_specs=[
            pl.BlockSpec((bm, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bm, W), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, C), jnp.int8),
        interpret=interpret,
    )(d_p, r_p, c_p, p_p)
    return out[:m, :c] != 0


def admission_ref(dvals, rem, cand, pref) -> jax.Array:
    """Straight-line jnp oracle for the fused prune (same mask, any shape)."""
    dvals, rem = check_admission_dtype(dvals, rem)
    cand = jnp.asarray(cand)
    ok = dvals <= rem[:, None]
    if pref is not None and pref.shape[1]:
        seen = (jnp.asarray(pref)[:, :, None] == cand[:, None, :]).any(axis=1)
        ok = ok & ~seen
    return ok


def admission_prune(
    dist_rows, dst_row, cand, rem, pref=None, backend: str = "ref"
):
    """Admissibility + simplicity mask for one expansion level.

    ``dist_rows`` is the enumerator's (R, N+1) f32 distance tile (trailing
    +inf sentinel column), ``dst_row`` the (M,) tile row of each frontier
    row's destination.  The candidate-distance gather stays in jnp (XLA's
    vectorized gather); the kernel fuses the comparison with the
    prefix-membership reduction.  ``pref=None`` skips the simplicity test
    (the enumerator's exact ``check_simple=False`` fast path).
    """
    dist_rows = jnp.asarray(dist_rows)
    cand = jnp.asarray(cand, dtype=jnp.int32)
    dvals = dist_rows[jnp.asarray(dst_row)[:, None], cand]
    rem = jnp.asarray(rem)
    if backend == "ref":
        return admission_ref(dvals, rem, cand, pref)
    if backend != "pallas":
        raise ValueError(f"unknown admission backend: {backend!r}")
    if pref is None:
        pref = jnp.zeros((cand.shape[0], 0), dtype=jnp.int32)
    return admission_pallas(dvals, rem, cand, jnp.asarray(pref))


# ---- IR audit cases (python -m repro.analysis ir) ------------------------- #

def _ir_cases_admission():
    import numpy as np

    def make():
        M, C = 4, 6
        dvals = np.ones((M, C), np.float32)
        rem = np.ones(M, np.float32)
        cand = np.ones((M, C), np.int32)
        pref = np.full((M, 3), -1, np.int32)
        return (dvals, rem, cand, pref), {
            "bm": 8, "bc": 128, "interpret": True,
        }

    # interpret-mode lowering: auditable jaxpr, but its HLO is an emulation
    # artifact — excluded from the JF105 budget.
    return [AuditCase(label="interpret", make=make, budget=False)]
