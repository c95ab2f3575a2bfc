"""Per-kernel validation: shape/dtype sweeps of the Pallas kernels
(interpret mode on CPU) against the pure-jnp ref.py oracles."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.congestion import congestion_pallas
from repro.kernels.minplus import minplus_pallas
from repro.kernels.power import matmul_pallas
from repro.kernels import ops

RNG = np.random.default_rng(42)

# (m, k, n) shape sweep: unaligned, degenerate, and tile-straddling cases.
SHAPES = [
    (8, 8, 8),
    (16, 16, 16),
    (17, 5, 23),
    (1, 64, 1),
    (33, 40, 29),
    (64, 64, 64),
    (70, 1, 70),
]
BLOCKS = [8, 16, 32]


@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("block", BLOCKS)
def test_minplus_matches_ref(shape, block):
    m, k, n = shape
    a = jnp.asarray(RNG.uniform(0, 100, (m, k)).astype(np.float32))
    b = jnp.asarray(RNG.uniform(0, 100, (k, n)).astype(np.float32))
    got = minplus_pallas(a, b, bm=block, bn=block, bk=block, interpret=True)
    want = ref.minplus_ref(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=0)


def test_minplus_with_inf_entries():
    # +inf entries (unreachable) must flow through the tropical product
    a = jnp.asarray([[0.0, np.inf], [1.0, 0.0]], dtype=jnp.float32)
    got = minplus_pallas(a, a, bm=8, bn=8, bk=8, interpret=True)
    want = ref.minplus_ref(a, a)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_matmul_matches_ref(shape, dtype):
    m, k, n = shape
    a = jnp.asarray(RNG.standard_normal((m, k)).astype(dtype))
    b = jnp.asarray(RNG.standard_normal((k, n)).astype(dtype))
    got = matmul_pallas(a, b, bm=16, bn=16, bk=16, interpret=True)
    want = ref.matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bf16", [False, True])
def test_matmul_bf16_inputs(bf16):
    a = jnp.asarray(RNG.standard_normal((40, 24)), dtype=jnp.bfloat16 if bf16 else jnp.float32)
    b = jnp.asarray(RNG.standard_normal((24, 56)), dtype=jnp.bfloat16 if bf16 else jnp.float32)
    got = matmul_pallas(a, b, bm=16, bn=16, bk=16, interpret=True)
    want = ref.matmul_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=2e-2, atol=2e-2
    )


@pytest.mark.parametrize("pe", [(10, 7), (64, 64), (100, 60), (37, 129), (1, 1)], ids=str)
@pytest.mark.parametrize("block", [16, 32])
def test_congestion_matches_ref(pe, block):
    P, E = pe
    B = jnp.asarray((RNG.uniform(size=(P, E)) < 0.15).astype(np.float32))
    r = jnp.asarray(RNG.uniform(size=P).astype(np.float32))
    w = jnp.asarray(RNG.uniform(size=E).astype(np.float32))
    lg, cg = congestion_pallas(B, r, w, bp=block, be=block, interpret=True)
    lw, cw = ref.congestion_ref(B, r, w)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lw), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cg), np.asarray(cw), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "shape", [(3, 10, 7), (1, 64, 64), (4, 37, 129), (2, 1, 1)], ids=str
)
@pytest.mark.parametrize("block", [16, 32])
def test_congestion_batched_matches_ref(shape, block):
    """Stacked rank-3 incidence: one fused pass per batch member."""
    Bt, P, E = shape
    B = jnp.asarray((RNG.uniform(size=(Bt, P, E)) < 0.15).astype(np.float32))
    r = jnp.asarray(RNG.uniform(size=(Bt, P)).astype(np.float32))
    w = jnp.asarray(RNG.uniform(size=(Bt, E)).astype(np.float32))
    lg, cg = congestion_pallas(B, r, w, bp=block, be=block, interpret=True)
    lw, cw = ref.congestion_ref(B, r, w)
    assert lg.shape == (Bt, E) and cg.shape == (Bt, P)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lw), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cg), np.asarray(cw), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("batched", [False, True], ids=["rank2", "rank3"])
def test_congestion_tpu_interpret_multi_block(batched):
    """P and 2E each span several (bp, be) blocks, one edge of each
    unaligned.  The TPU interpreter refuses an output block revisited after
    the grid moved off it, which Pallas on TPU does not promise to read
    back; a loads tile that accumulated across P-blocks with E innermost
    fails here."""
    from jax.experimental.pallas import tpu as pltpu

    bp, be = 16, 128
    P, E = 5 * bp + 3, 3 * be
    shape = (2, P, E) if batched else (P, E)
    B = jnp.asarray((RNG.uniform(size=shape) < 0.15).astype(np.float32))
    r = jnp.asarray(RNG.uniform(size=shape[:-1]).astype(np.float32))
    w = jnp.asarray(RNG.uniform(size=shape[:-2] + (E,)).astype(np.float32))
    lg, cg = congestion_pallas(B, r, w, bp=bp, be=be,
                               interpret=pltpu.InterpretParams())
    lw, cw = ref.congestion_ref(B, r, w)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lw), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cg), np.asarray(cw), rtol=1e-5, atol=1e-5)


def test_congestion_batched_members_match_single():
    """Each rank-3 member equals its own rank-2 solve (both backends)."""
    Bt, P, E = 3, 23, 31
    B = (RNG.uniform(size=(Bt, P, E)) < 0.2).astype(np.float32)
    r = RNG.uniform(size=(Bt, P)).astype(np.float32)
    w = RNG.uniform(size=(Bt, E)).astype(np.float32)
    lb, cb = ref.congestion_ref(jnp.asarray(B), jnp.asarray(r), jnp.asarray(w))
    for b in range(Bt):
        l1, c1 = ref.congestion_ref(
            jnp.asarray(B[b]), jnp.asarray(r[b]), jnp.asarray(w[b])
        )
        np.testing.assert_allclose(np.asarray(lb[b]), np.asarray(l1), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(cb[b]), np.asarray(c1), rtol=1e-6)


def test_congestion_loads_matches_fused():
    """Loads-only entry point (the sim waterfilling's primitive) agrees
    with the fused reference's loads half, rank-2 and rank-3, and with the
    interpret-mode kernel path."""
    Bt, P, E = 3, 23, 31
    B3 = jnp.asarray((RNG.uniform(size=(Bt, P, E)) < 0.2).astype(np.float32))
    r3 = jnp.asarray(RNG.uniform(size=(Bt, P)).astype(np.float32))
    want3 = ref.congestion_ref(B3, r3, jnp.zeros((Bt, E)))[0]
    np.testing.assert_allclose(
        np.asarray(ops.congestion_loads(B3, r3, backend="ref")),
        np.asarray(want3), rtol=1e-5, atol=1e-6,
    )
    B2, r2 = B3[0], r3[0]
    want2 = ref.congestion_ref(B2, r2, jnp.zeros(E))[0]
    np.testing.assert_allclose(
        np.asarray(ops.congestion_loads(B2, r2, backend="ref")),
        np.asarray(want2), rtol=1e-5, atol=1e-6,
    )
    got_k = ops.congestion_loads(B2, r2, backend="pallas", bp=16, be=16,
                                 interpret=True)
    np.testing.assert_allclose(
        np.asarray(got_k), np.asarray(want2), rtol=1e-5, atol=1e-5
    )


def test_preferred_congestion_backend_batch_aware():
    # CPU: batched asks answer 'gather' (PathSystemBatch fan-in tables);
    # single-instance answers are unchanged
    single = ops.preferred_congestion_backend(1000, 1000)
    assert single in ("dense", "scatter")
    assert ops.preferred_congestion_backend(1000, 1000, n_batch=1) == single
    assert ops.preferred_congestion_backend(1000, 1000, n_batch=16) == "gather"


def test_apsp_minplus_matches_blas_bfs():
    from repro.core import apsp_hops, jellyfish

    top = jellyfish(48, 8, 5, seed=7)
    d_ref = apsp_hops(top.adjacency())
    d_mp = np.asarray(ops.apsp_minplus(top.adjacency(), backend="ref"))
    assert np.array_equal(np.isinf(d_ref), np.isinf(d_mp))
    finite = ~np.isinf(d_ref)
    np.testing.assert_array_equal(d_ref[finite], d_mp[finite])


def test_apsp_minplus_blocked_matches_apsp_ref():
    """Tiled int16 driver == dense jnp squaring oracle (kernel-level parity)."""
    from repro.core import jellyfish

    top = jellyfish(40, 8, 5, seed=11)
    d_ref = np.asarray(ref.apsp_ref(jnp.asarray(top.adjacency())))
    d_blk = ops.apsp_minplus_blocked(top.adjacency(), bm=16, bn=24, bk=16)
    assert d_blk.dtype == np.int16
    inf16 = np.iinfo(np.int16).max
    assert np.array_equal(np.isinf(d_ref), d_blk == inf16)
    finite = ~np.isinf(d_ref)
    np.testing.assert_array_equal(d_ref[finite], d_blk[finite].astype(np.float32))


def test_minplus_integer_dtype_raises():
    a = jnp.ones((8, 8), jnp.int16)
    with pytest.raises(ValueError, match="floating point"):
        minplus_pallas(a, a, bm=8, bn=8, bk=8, interpret=True)
    with pytest.raises(ValueError, match="floating point"):
        ref.minplus_ref(a, a)


def test_power_iteration_lambda2_matches_dense_eig():
    from repro.core import jellyfish

    top = jellyfish(40, 8, 5, seed=9)
    a = top.adjacency().astype(np.float64)
    lap = np.diag(a.sum(1)) - a
    lam2_exact = np.sort(np.linalg.eigvalsh(lap))[1]
    lam2_ops = float(ops.power_iteration_lambda2(top.adjacency(), iters=400, backend="ref"))
    np.testing.assert_allclose(lam2_ops, lam2_exact, rtol=1e-3)


def test_ops_auto_dispatch_runs_on_cpu():
    a = jnp.ones((4, 4))
    assert np.asarray(ops.minplus(a, a)).shape == (4, 4)
    assert np.asarray(ops.matmul(a, a)).shape == (4, 4)
    l, c = ops.congestion(a, jnp.ones(4), jnp.ones(4))
    assert l.shape == (4,) and c.shape == (4,)
