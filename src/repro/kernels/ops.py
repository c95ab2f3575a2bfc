"""Jit'd public wrappers around the Pallas kernels with backend dispatch.

TPU is the *target*; this container is CPU-only.  Policy:

* ``backend="auto"`` (default): run the Pallas kernel on TPU, the pure-jnp
  reference (XLA-compiled, fast) on CPU.  Production code calls these and is
  correct everywhere.
* ``backend="pallas"``: force the kernel — on TPU compiled, elsewhere
  interpret mode — the validation path used by tests (executes the kernel
  body on CPU).
* ``backend="ref"``: force the oracle.

Flow-solver backend selection
-----------------------------
The MW / MPTCP inner loops (``core.flow``, ``core.mptcp``) and the sim's
waterfilling need the incidence products ``(B^T r, B w)`` every iteration.
Whether to materialize the dense (P, 2E) incidence B and call the fused
``congestion`` kernel, or to stay with gather/segment-sum over the padded
path table, is a platform *and* size question, answered here by
``preferred_congestion_backend``:

* On TPU the dense kernel wins whenever B fits comfortably in HBM (scatter
  adds are serialized and MXU-hostile), so: ``dense`` iff
  ``n_batch * P * 2E * 4 bytes <= dense_budget_bytes``.
* On CPU a batch (``n_batch > 1``) gets ``gather``: the transposed fan-in
  tables built with every ``core.flow.PathSystemBatch``.  The MW solver and
  the sim ask through ``PathSystemBatch.congestion_backend``, with
  ``n_batch >= 2`` even for a batch of one.
* ``n_batch == 1`` on CPU is ``core.mptcp``'s question: ``scatter`` unless
  the instance is tiny (B is ~99% zeros and XLA's scatter-add is
  cache-friendly), ``dense`` for toy instances.

``apsp_minplus`` is the TPU-shaped APSP (min-plus squaring, dense f32);
``apsp_minplus_blocked`` is its out-of-core sibling — host-resident int16
distance state, streamed f32 tiles — and the production path at 10k+
switches.  CPU production code defaults to the blocked BLAS frontier-BFS in
``core.metrics`` (same int16 contract); ``REPRO_APSP_BACKEND`` /
``routing.set_apsp_backend`` overrides the choice deterministically.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..analysis.registry import AuditCase, solver_jit
from . import ref
from .congestion import congestion_pallas
from .minplus import minplus_pallas
from .power import matmul_pallas

__all__ = [
    "minplus",
    "matmul",
    "congestion",
    "congestion_loads",
    "apsp_minplus",
    "apsp_minplus_blocked",
    "power_iteration_lambda2",
    "preferred_congestion_backend",
]

# int16 "unreachable" sentinel of the canonical hop representation.  Equal by
# construction to repro.core.metrics.INT16_INF (kernels cannot import core
# without a cycle through core.flow).
_INT16_INF = np.int16(np.iinfo(np.int16).max)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# Dense incidence budget for the fused congestion kernel on TPU: B tiles are
# streamed from HBM, so "fits" means HBM headroom, not VMEM.  4 GiB leaves
# room for the f32 B plus solver state on a 16+ GiB part.
DENSE_INCIDENCE_BUDGET_BYTES = 4 << 30
# On CPU a dense B only beats scatter for toy instances (fits hot in cache).
_CPU_DENSE_LIMIT_BYTES = 8 << 20


def preferred_congestion_backend(
    n_paths: int,
    n_slots: int,
    dense_budget_bytes: int | None = None,
    n_batch: int = 1,
) -> str:
    """Pick the flow-solver congestion backend by platform and size.

    ``n_paths`` x ``n_slots`` is the incidence shape (P, 2E); see module
    docstring for the policy.  ``n_batch`` > 1 is a ``PathSystemBatch``
    asking about a stacked (n_batch, P, 2E) incidence: on TPU the dense
    budget is shared by the whole stack (the rank-3 fused kernel needs
    ``n_batch`` times the headroom); on CPU the answer is ``gather`` — the
    batch build precomputes transposed fan-in tables that replace the
    serialized scatter-add with vectorized ordered gathers (see
    ``core.flow.PathSystemBatch``), measured ~4-6x faster end to end at
    B = 16 x RRG(512) on the 2-core CI box.
    """
    bytes_needed = 4 * int(n_paths) * int(n_slots) * max(int(n_batch), 1)
    if _on_tpu():
        budget = (
            DENSE_INCIDENCE_BUDGET_BYTES
            if dense_budget_bytes is None
            else dense_budget_bytes
        )
        return "dense" if bytes_needed <= budget else "scatter"
    if int(n_batch) > 1:
        return "gather"
    limit = (
        _CPU_DENSE_LIMIT_BYTES if dense_budget_bytes is None else dense_budget_bytes
    )
    return "dense" if bytes_needed <= limit else "scatter"


def minplus(a, b, backend: str = "auto", **blocks):
    if backend == "ref" or (backend == "auto" and not _on_tpu()):
        return ref.minplus_ref(a, b)
    return minplus_pallas(a, b, **blocks)


def matmul(a, b, backend: str = "auto", **blocks):
    if backend == "ref" or (backend == "auto" and not _on_tpu()):
        return ref.matmul_ref(a, b)
    return matmul_pallas(a, b, **blocks)


@solver_jit(spec="_ir_cases_ops_congestion", kind="wrapper")
def congestion(incidence, rates, prices, backend: str = "auto", **blocks):
    """Fused (B^T r, B w); a rank-3 ``incidence`` runs one fused pass per
    stacked batch member (both backends accept it — see congestion_pallas)."""
    if backend == "ref" or (backend == "auto" and not _on_tpu()):
        return ref.congestion_ref(incidence, rates, prices)
    return congestion_pallas(incidence, rates, prices, **blocks)


@solver_jit(spec="_ir_cases_ops_congestion_loads", kind="wrapper")
def congestion_loads(incidence, rates, backend: str = "auto", **blocks):
    """Loads-only ``B^T r`` over a dense (or stacked rank-3) incidence.

    The flow-level simulator's waterfilling (``repro.sim.engine``) runs the
    congestion primitive's *load* half twice per round but never consumes
    path costs.  On CPU the reference is a plain (batched) matmul — half
    the work of ``congestion_ref``.  On TPU the fused kernel reads each B
    tile from HBM once whether it forms one product or two, so the fused
    call costs the same HBM traffic and we simply drop the costs output.
    """
    if backend == "ref" or (backend == "auto" and not _on_tpu()):
        b = jnp.asarray(incidence, dtype=jnp.float32)
        r = jnp.asarray(rates, dtype=jnp.float32)
        if b.ndim == 3:
            return jnp.einsum("bp,bpe->be", r, b)
        return r @ b
    zeros = jnp.zeros(
        incidence.shape[:-2] + (incidence.shape[-1],), jnp.float32
    )
    return congestion_pallas(incidence, rates, zeros, **blocks)[0]


def _squarings_to_cover(cover: int) -> int:
    """Number of min-plus squarings after which ``D^(2^t)`` spans ``cover`` hops."""
    steps = 0
    m = 1
    while m < max(cover, 1):
        m *= 2
        steps += 1
    return steps


def apsp_minplus(
    adj,
    backend: str = "auto",
    diameter_hint: int | None = None,
    certify: bool = True,
) -> jax.Array:
    """All-pairs hop distances by min-plus squaring of the adjacency.

    ``D^(2t)`` converges once ``2^t >= diameter``.  Three sync regimes:

    * ``diameter_hint`` given (eager): run ``ceil(log2(hint))`` squarings
      with **no** per-squaring host sync, then — because callers plumb hints
      from probabilistic degree/size bounds (Bollobás), not certified ones —
      one final fixed-point check certifies the result; only an undershooting
      hint pays further synced squarings.  ``certify=False`` skips even that
      single sync for callers holding a certified bound.
    * traced (inside an outer jit): trust the hint (or the n-1 worst case)
      fully — no host sync is possible.
    * no hint (eager): the historical path — squaring stops at the first
      fixed point, one host sync per squaring (low-diameter random graphs
      converge in 2-3 squarings; the n-1 bound would do 9+ at N=512).
    """
    n = adj.shape[0]
    d = jnp.where(jnp.asarray(adj) > 0, 1.0, jnp.inf)
    d = jnp.where(jnp.eye(n, dtype=bool), 0.0, d)
    traced = isinstance(d, jax.core.Tracer)
    done = 0
    if diameter_hint is not None or traced:
        cover = diameter_hint if diameter_hint is not None else max(n - 1, 1)
        steps = _squarings_to_cover(cover)
        for _ in range(steps):
            d = minplus(d, d, backend=backend)
        done = steps
        if traced or not certify:
            return d
    # synced fixed-point loop: the full computation without a hint, or the
    # single certify pass (plus rare continuation) after an uncertified hint
    m = 1 << done
    while True:
        new = minplus(d, d, backend=backend)
        m *= 2
        if bool(jnp.all(new == d)):  # fixed point: all distances found
            return new
        d = new
        if m >= max(n - 1, 1):
            return d


def apsp_minplus_blocked(
    adj,
    bm: int = 512,
    bn: int = 512,
    bk: int = 512,
    diameter_hint: int | None = None,
    backend: str = "auto",
    chunk: int = 16,
) -> np.ndarray:
    """Out-of-core APSP by **tiled** min-plus powering; canonical int16 out.

    The distance matrix lives on the host in the canonical int16 hop
    representation (sentinel ``_INT16_INF``); each squaring streams
    ``(bm, bk) x (bk, bn)`` float32 tiles through the min-plus product —
    the ``minplus_pallas`` kernel on TPU (``backend="pallas"`` forces it,
    interpret mode off-TPU), a cache-blocked numpy broadcast reduction on
    CPU.  Float working set: one ``(bm, N)`` row band (converted once per
    output-row stripe) plus ``O(bk*bn + bm*bn + bm*chunk*bn)`` of tiles —
    i.e. ``4*bm*N`` bytes dominate at large N.  Resident distance state: two
    int16 matrices (current and next power), ``4 N^2`` bytes total at the
    peak of a squaring versus the ``>= 12 N^2`` of the dense f32 path.

    Because D is host-resident, the fixed-point check is a free host
    ``array_equal`` (no device sync), so the driver always runs to a
    *certified* fixed point (bounded by the ``n - 1`` worst case) — an
    undershooting ``diameter_hint`` can never produce wrong distances here,
    unlike a trusted hint would.  The hint is accepted for API symmetry with
    ``apsp_minplus`` (where it replaces per-squaring device syncs); it does
    not bound this driver.
    """
    a = np.asarray(adj)
    n = a.shape[0]
    if n >= int(_INT16_INF):
        raise ValueError(
            f"N = {n} >= int16 sentinel {int(_INT16_INF)}: distances could "
            "overflow the canonical int16 hop representation"
        )
    d = np.full((n, n), _INT16_INF, dtype=np.int16)
    d[a != 0] = 1
    np.fill_diagonal(d, 0)
    if n <= 1:
        return d
    use_kernel = backend == "pallas" or (
        backend == "auto" and jax.default_backend() == "tpu"
    )
    del diameter_hint  # see docstring: the host fixed-point check certifies
    max_sq = _squarings_to_cover(n - 1)
    inf16 = int(_INT16_INF)
    for _ in range(max(max_sq, 1)):
        nxt = np.empty_like(d)
        for i0 in range(0, n, bm):
            a_band = _tiles_f32(d[i0 : i0 + bm])  # (bm, n) row band, once
            for j0 in range(0, n, bn):
                acc = np.full(
                    (a_band.shape[0], min(bn, n - j0)), np.inf, dtype=np.float32
                )
                for k0 in range(0, n, bk):
                    at = a_band[:, k0 : k0 + bk]
                    bt = _tiles_f32(d[k0 : k0 + bk, j0 : j0 + bn])
                    if use_kernel:
                        cand = np.asarray(
                            minplus_pallas(jnp.asarray(at), jnp.asarray(bt))
                        )
                    else:
                        cand = _minplus_np_tile(at, bt, chunk=chunk)
                    np.minimum(acc, cand, out=acc)
                # finite accumulators are true hop counts (< n < sentinel)
                tile16 = np.where(np.isfinite(acc), acc, np.float32(inf16))
                nxt[i0 : i0 + bm, j0 : j0 + bn] = tile16.astype(np.int16)
        if np.array_equal(nxt, d):  # fixed point — host memcmp, no sync
            return nxt
        d = nxt
    return d


def _tiles_f32(d16: np.ndarray) -> np.ndarray:
    """float32 view of an int16 hop tile: sentinel -> +inf."""
    t = d16.astype(np.float32)
    t[d16 == _INT16_INF] = np.inf
    return t


def _minplus_np_tile(a: np.ndarray, b: np.ndarray, chunk: int = 16) -> np.ndarray:
    """Cache-blocked numpy min-plus tile product (the CPU tile backend).

    Broadcast temporaries are kept to ``(bm, chunk, bn)`` — the K dimension
    is walked in ``chunk``-wide strips so the strip stays L2-resident
    instead of materializing the O(bm*bk*bn) candidate cube.
    """
    m, k = a.shape
    n = b.shape[1]
    acc = np.full((m, n), np.inf, dtype=np.float32)
    for t0 in range(0, k, chunk):
        strip = a[:, t0 : t0 + chunk, None] + b[None, t0 : t0 + chunk, :]
        np.minimum(acc, strip.min(axis=1), out=acc)
    return acc


def power_iteration_lambda2(
    adj, iters: int = 300, block: int = 8, backend: str = "auto", seed: int = 0
):
    """lambda_2 of the Laplacian via block power iteration on B = cI - L.

    The matmul (B @ V) is the kernel; orthogonalization against the known
    top eigenvector (all-ones) and QR re-orthonormalization run in jnp.
    """
    a = jnp.asarray(adj, dtype=jnp.float32)
    n = a.shape[0]
    deg = a.sum(axis=1)
    c = 2.0 * jnp.max(deg) + 1.0
    ones = jnp.ones((n, 1), jnp.float32) / jnp.sqrt(n)
    key = jax.random.PRNGKey(seed)
    v = jax.random.normal(key, (n, block), jnp.float32)

    def step(v, _):
        v = v - ones @ (ones.T @ v)
        q, _ = jnp.linalg.qr(v)
        # B @ q = c q - D q + A q ; the A @ q matmul is the kernel call
        w = c * q - deg[:, None] * q + matmul(a, q, backend=backend)
        return w, None

    for _ in range(iters):
        v, _ = step(v, None)
    v = v - ones @ (ones.T @ v)
    q, _ = jnp.linalg.qr(v)
    w = c * q - deg[:, None] * q + matmul(a, q, backend=backend)
    lam_b = jnp.diag(q.T @ w)
    lam2 = c - jnp.max(lam_b)
    return jnp.maximum(lam2, 0.0)


# ---- IR audit cases (python -m repro.analysis ir) ------------------------- #
# Dispatch wrappers, not jits (kind="wrapper"): traced for the JF rules on
# their CPU/ref path, but never budgeted (JF105 needs a .lower()-able jit
# and the wrapped refs carry their own budgets).

_IR_WRAPPER_EXEMPT = {
    "JF101": "the ref dispatch path is the dense matmul oracle; bit-exact "
    "solver paths never route dense work through these wrappers",
}


def _ir_cases_ops_congestion():
    def make():
        inc = np.ones((4, 6), np.float32)
        return (inc, np.ones(4, np.float32), np.ones(6, np.float32)), {
            "backend": "ref",
        }

    return [AuditCase(label="ref", make=make, exempt=_IR_WRAPPER_EXEMPT,
                      budget=False)]


def _ir_cases_ops_congestion_loads():
    def make():
        inc = np.ones((4, 6), np.float32)
        return (inc, np.ones(4, np.float32)), {"backend": "ref"}

    return [AuditCase(label="ref", make=make, exempt=_IR_WRAPPER_EXEMPT,
                      budget=False)]
