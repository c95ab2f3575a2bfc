"""PR 8: IR-level static auditor (repro.analysis.irlint, rules JF100-JF105).

Four test groups:

* rule fixtures: every JF10x rule fires on a minimal bad jaxpr/fixture and
  stays silent on the corrected twin (mirroring the AST linter's fixture
  discipline; a completeness assert pins the fixture set to IR_RULES).
* HEAD audit: the tree at HEAD audits clean INCLUDING the checked-in
  compile-footprint budget — the CI ir-audit lane in test form.
* corruption: deliberately breaking a solver invariant (swapping _fold_sum
  for a raw jnp.sum, re-introducing a scatter under the gather backend) is
  caught by tracing alone — no solver runs.
* golden censuses: the three batched congestion backends and
  _path_cost_gather keep their exact primitive censuses (any change to the
  lowering of the bit-exactness-critical closures must be a deliberate,
  reviewed snapshot update).
"""

import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis.irlint import (
    audit_case,
    audit_fold_tree,
    check_registration,
    compare_budget,
    iter_eqns,
    measure_case,
    primitive_census,
    run_audit,
    trace_case,
)
from repro.analysis.registry import (
    IR_RULES,
    SOLVER_MODULES,
    AuditCase,
    SolverEntry,
    registered_entries,
    solver_jit,
)
from repro.core import flow

ROOT = pathlib.Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")


@pytest.fixture
def fresh_traces():
    """Corruption tests monkeypatch trace-time globals: drop any cached
    jaxprs before AND after so neither direction sees a stale trace."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _audit_fn(fn, *args, backend=None, exempt=None):
    """Run the per-case rules on a bare function (toy-fixture harness)."""
    entry = SolverEntry(module="toy", attr=getattr(fn, "__name__", "fn"))
    case = AuditCase(
        label="t", make=lambda: (args, {}), backend=backend,
        exempt=exempt or {},
    )
    return audit_case(entry, case, jax.make_jaxpr(fn)(*args))


# --------------------------------------------------------------------------- #
# registry
# --------------------------------------------------------------------------- #


def test_registry_enumerates_all_solver_jits():
    entries = registered_entries()
    # the entry the old hand-maintained retrace list shipped without:
    assert "repro.kernels.admission.admission_pallas" in entries
    assert all(e.kind in ("jit", "wrapper") for e in entries.values())
    # every spec resolves to at least one concrete case
    for e in entries.values():
        assert e.spec is not None
        assert len(e.cases()) >= 1
    # wrappers participate in the audit but not the jit view
    from repro.analysis.retrace import named_solver_jits

    jits = named_solver_jits()
    assert "repro.kernels.ops.congestion" in entries
    assert "repro.kernels.ops.congestion" not in jits
    assert "repro.kernels.admission.admission_pallas" in jits
    assert all(hasattr(fn, "lower") for fn in jits.values())


def test_solver_jit_rejects_bad_kind():
    with pytest.raises(ValueError, match="kind"):
        solver_jit(kind="whatever")


# --------------------------------------------------------------------------- #
# rule fixtures: fire + silent per rule
# --------------------------------------------------------------------------- #


def test_jf101_fires_on_float_reduce_sum_and_dot():
    x = np.ones(5, np.float32)
    fired = _audit_fn(lambda v: jnp.sum(v), x)
    assert [f.rule for f in fired] == ["JF101"]
    assert "_fold_sum" in fired[0].message

    a = np.ones((4, 4), np.float32)
    fired = _audit_fn(lambda u, v: u @ v, a, a)
    assert [f.rule for f in fired] == ["JF101"]


def test_jf101_silent_on_fold_sum_and_int_sum():
    assert _audit_fn(flow._fold_sum, np.ones(5, np.float32)) == []
    # integer reductions are exactly associative — out of JF101's scope
    assert _audit_fn(lambda v: jnp.sum(v), np.ones(5, np.int32)) == []
    # a recorded exemption silences the rule (dense-backend contract)
    a = np.ones((4, 4), np.float32)
    assert _audit_fn(lambda u, v: u @ v, a, a,
                     exempt={"JF101": "dense by design"}) == []


def test_jf102_fires_on_scatter_under_gather_backend():
    def scat(x, idx):
        return jnp.zeros((8,), jnp.float32).at[idx].add(x)

    x = np.ones(4, np.float32)
    idx = np.arange(4, dtype=np.int32)
    fired = _audit_fn(scat, x, idx, backend="gather")
    assert [f.rule for f in fired] == ["JF102"]
    # same program under the scatter backend is the sanctioned path
    assert _audit_fn(scat, x, idx, backend="scatter") == []
    # and the gather backend's real accumulator is scatter-free
    fr = np.ones((2, 9), np.float32)
    table = np.full((2, 8, 4), 8, np.int32)
    assert _audit_fn(flow._ordered_fan_in_sum, fr, table,
                     backend="gather") == []


def test_jf103_fires_on_f64_and_silences_on_f32():
    with jax.enable_x64():
        closed = jax.make_jaxpr(lambda v: v * 2.0)(np.ones(3, np.float64))
    entry = SolverEntry(module="toy", attr="f64")
    case = AuditCase(label="t", make=lambda: ((), {}))
    fired = audit_case(entry, case, closed)
    assert fired and all(f.rule == "JF103" for f in fired)

    assert _audit_fn(lambda v: v * 2.0, np.ones(3, np.float32)) == []


def test_jf104_fires_on_cond_and_callback_in_scan():
    def cond_in_scan(x):
        def body(c, _):
            c = jax.lax.cond(c[0] > 0.0, lambda v: v + 1.0,
                             lambda v: v - 1.0, c)
            return c, None

        c, _ = jax.lax.scan(body, x, None, length=2)
        return c

    fired = _audit_fn(cond_in_scan, np.ones(3, np.float32))
    assert [f.rule for f in fired] == ["JF104"]

    def cb_in_scan(x):
        def body(c, _):
            jax.debug.callback(lambda v: None, c)
            return c, None

        c, _ = jax.lax.scan(body, x, None, length=2)
        return c

    fired = _audit_fn(cb_in_scan, np.ones(3, np.float32))
    assert fired and all(f.rule == "JF104" for f in fired)

    def masked(x):  # the sanctioned select-masked twin
        def body(c, _):
            return jnp.where(c > 0.0, c + 1.0, c - 1.0), None

        c, _ = jax.lax.scan(body, x, None, length=2)
        return c

    assert _audit_fn(masked, np.ones(3, np.float32)) == []


def test_jf104_skips_pallas_kernel_when():
    # pl.when lowers to a cond INSIDE the pallas body — grid-static control
    # flow, not a host sync.  Prove the skip is load-bearing: the cond is
    # really there, and the audit still passes the case.
    entry = registered_entries()["repro.kernels.minplus.minplus_pallas"]
    case = entry.cases()[0]
    closed = trace_case(entry, case)
    pallas_conds = sum(
        1 for eqn, _, in_pallas in iter_eqns(closed.jaxpr)
        if in_pallas and eqn.primitive.name == "cond"
    )
    assert pallas_conds >= 1
    assert audit_case(entry, case, closed) == []


def test_jf100_fires_on_unregistered_jit(tmp_path):
    d = tmp_path / "repro" / "core"
    d.mkdir(parents=True)
    f = d / "newsolver.py"
    f.write_text("import jax\n\n@jax.jit\ndef step(x):\n    return x\n")
    fired = check_registration([str(tmp_path)], entries=registered_entries())
    assert [x.rule for x in fired] == ["JF100"]
    assert "SOLVER_MODULES" in fired[0].message  # module itself unlisted

    # a registered-module file whose jit is missing the decorator
    d2 = tmp_path / "repro" / "kernels"
    d2.mkdir(parents=True)
    (d2 / "minplus.py").write_text(
        "import jax\n\n@jax.jit\ndef rogue(x):\n    return x\n"
    )
    fired = check_registration([str(d2)], entries=registered_entries())
    assert [x.rule for x in fired] == ["JF100"]
    assert "@solver_jit" in fired[0].message

    # pragma escape hatch on the def line
    f.write_text(
        "import jax\n\n@jax.jit\n"
        "def step(x):  # repro-lint: disable=JF100\n    return x\n"
    )
    assert check_registration([str(f)], entries=registered_entries()) == []


def test_jf105_compare_budget_semantics():
    base = {"jaxpr_eqns": 100, "hlo_ops": 200, "flops": 0.0,
            "hbm_bytes": 1000.0, "whiles": 1}
    budget = {"tolerance": {"rel": 0.25, "abs": {"hlo_ops": 24}},
              "entries": {"m.f[x]": dict(base)}}

    # within tolerance (growth under rel+abs headroom): silent
    grown_ok = dict(base, hlo_ops=int(200 * 1.25) + 24)
    findings, diff = compare_budget({"m.f[x]": grown_ok}, budget)
    assert findings == [] and diff["ok"]

    # beyond tolerance: fires with the limit in the message
    grown_bad = dict(base, hlo_ops=int(200 * 1.25) + 25)
    findings, diff = compare_budget({"m.f[x]": grown_bad}, budget)
    assert [f.rule for f in findings] == ["JF105"]
    assert not diff["entries"]["m.f[x]"]["hlo_ops"]["ok"]

    # shrinkage never fails
    findings, _ = compare_budget({"m.f[x]": dict(base, hlo_ops=10)}, budget)
    assert findings == []

    # a measured case with no recorded budget fires
    findings, _ = compare_budget(
        {"m.f[x]": base, "m.g[y]": base}, budget)
    assert [f.rule for f in findings] == ["JF105"]
    assert "no recorded" in findings[0].message

    # stale recorded cases fire only on a complete (unfiltered) audit
    findings, _ = compare_budget({}, budget, complete=True)
    assert [f.rule for f in findings] == ["JF105"]
    assert "stale" in findings[0].message
    findings, _ = compare_budget({}, budget, complete=False)
    assert findings == []


def test_jf105_measure_roundtrips_on_a_real_entry():
    entry = registered_entries()["repro.kernels.ref.matmul_ref"]
    case = entry.cases()[0]
    m = measure_case(entry, case)
    assert m["jaxpr_eqns"] >= 1 and m["hlo_ops"] >= 1 and m["flops"] > 0
    budget = {"tolerance": {"rel": 0.25, "abs": {}},
              "entries": {"k[f32]": m}}
    findings, diff = compare_budget({"k[f32]": m}, budget)
    assert findings == [] and diff["ok"]


def test_every_ir_rule_has_fixtures():
    # fixture discipline mirror of the AST linter: each IR rule is exercised
    # by a dedicated fire/silent test above (JF100 registration, JF101-104
    # jaxpr rules, JF105 budget).  Keep this list in lockstep with IR_RULES.
    covered = {"JF100", "JF101", "JF102", "JF103", "JF104", "JF105"}
    assert covered == set(IR_RULES)


# --------------------------------------------------------------------------- #
# HEAD audit (the CI ir-audit lane in test form)
# --------------------------------------------------------------------------- #


def test_head_audits_clean_against_checked_in_budget(tmp_path):
    budget_path = ROOT / "artifacts" / "ir_budget.json"
    assert budget_path.exists(), "regenerate with --write-budget"
    diff_out = tmp_path / "diff.json"
    findings, diff = run_audit(
        [SRC], budget_path=str(budget_path), diff_out=str(diff_out)
    )
    assert findings == [], "\n".join(str(f) for f in findings)
    assert diff["ok"]
    assert json.loads(diff_out.read_text())["ok"]
    # every budgeted case is present in the checked-in file (no silent gaps)
    recorded = set(json.loads(budget_path.read_text())["entries"])
    budgeted = {
        f"{n}[{c.label}]" for n, e in registered_entries().items()
        for c in e.cases() if c.budget
    }
    assert recorded == budgeted


def test_registration_audit_clean_at_head():
    assert check_registration([SRC]) == []


# --------------------------------------------------------------------------- #
# corruption: invariant breaks are caught without running a solver
# --------------------------------------------------------------------------- #


def test_fold_sum_corruption_caught_statically(monkeypatch, fresh_traces):
    monkeypatch.setattr(flow, "_fold_sum",
                        lambda x: jnp.sum(x, axis=-1))
    # the structural tree check fires...
    tree = audit_fold_tree()
    assert tree and all(f.rule == "JF101" for f in tree)
    # ...and so does tracing the MW window that routes costs through it
    entry = registered_entries()["repro.core.flow._mw_window_batch"]
    case = next(c for c in entry.cases() if c.label == "scatter")
    fired = audit_case(entry, case)
    assert any(f.rule == "JF101" for f in fired)


def test_gather_backend_scatter_regression_caught(monkeypatch, fresh_traces):
    def corrupt(fr, table):  # shape-correct stand-in that scatter-adds
        Bt, S = fr.shape[0], table.shape[-2]
        return jnp.zeros((Bt, S), jnp.float32).at[:, 0].add(fr[:, 0])

    monkeypatch.setattr(flow, "_ordered_fan_in_sum", corrupt)
    entry = registered_entries()["repro.core.flow._mw_window_batch"]
    case = next(c for c in entry.cases() if c.label == "gather")
    fired = audit_case(entry, case)
    assert any(f.rule == "JF102" for f in fired)


# --------------------------------------------------------------------------- #
# golden primitive censuses (congestion backends + _path_cost_gather)
# --------------------------------------------------------------------------- #

# Pinned on jax 0.9.0.  A census change here means the lowering of
# a bit-exactness-critical closure changed: update deliberately, with the
# same scrutiny as an artifacts/ir_budget.json refresh.
_GOLDEN = {
    "scatter": {
        "add": 8, "broadcast_in_dim": 5, "concatenate": 1, "gather": 3,
        "lt": 4, "jit": 3, "reshape": 6, "scatter-add": 1, "select_n": 4,
        "slice": 4, "squeeze": 3,
    },
    "gather": {
        "add": 14, "broadcast_in_dim": 5, "concatenate": 2, "gather": 7,
        "lt": 7, "jit": 7, "reshape": 8, "select_n": 7, "slice": 7,
        "squeeze": 7,
    },
    "dense": {"dot_general": 2, "jit": 1},
    "path_cost_gather": {
        "add": 6, "broadcast_in_dim": 1, "gather": 3, "lt": 3, "jit": 3,
        "reshape": 3, "select_n": 3, "slice": 3, "squeeze": 3,
    },
}


def _census_congestion(backend):
    pe3, _, _, inv2, _, slot_gather, _, _ = flow._ir_batch_args()
    B, P, _ = pe3.shape
    S = inv2.shape[1]
    kw = {}
    if backend == "gather":
        kw["slot_gather"] = jnp.asarray(slot_gather)
    fn = flow.make_congestion_fn_batch(jnp.asarray(pe3), S, backend, **kw)
    rates = np.ones((B, P), np.float32)
    prices = np.ones((B, S), np.float32)
    return primitive_census(jax.make_jaxpr(fn)(rates, prices))


@pytest.mark.parametrize("backend", ["scatter", "gather", "dense"])
def test_congestion_backend_census_stable(backend):
    assert _census_congestion(backend) == _GOLDEN[backend]


def test_congestion_census_invariants():
    # the properties behind the snapshots, stated directly: gather has no
    # scatter at all, scatter has exactly one (the load accumulation), and
    # neither bit-exact backend contracts through a float reduction
    scatter, gather = _census_congestion("scatter"), _census_congestion("gather")
    assert not any(k.startswith("scatter") for k in gather)
    assert scatter.get("scatter-add") == 1
    for census in (scatter, gather):
        assert "reduce_sum" not in census
        assert "dot_general" not in census


def test_path_cost_gather_census_stable():
    pe3, _, _, inv2, _, _, _, _ = flow._ir_batch_args()
    B = pe3.shape[0]
    S = inv2.shape[1]
    pr_pad = np.ones((B, S + 1), np.float32)
    census = primitive_census(jax.make_jaxpr(flow._path_cost_gather)(pr_pad, pe3))
    assert census == _GOLDEN["path_cost_gather"]
