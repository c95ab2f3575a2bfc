"""IR-level static auditor: jaxpr/HLO rules the AST linter cannot see.

The bit-exactness contracts (INVARIANTS.md) are properties of the IR that
XLA compiles, not of the Python source — an AST-clean refactor can still
emit a size-dependent ``reduce_sum`` (a ``.sum()`` method slips past
JF005's call-name match; the ``sim/engine.py`` per-step throughput sum
shipped exactly that way), a serialized scatter under the gather backend,
or a silent f64 upcast.  This pass traces every registered solver entry
point (``repro.analysis.registry``) over tiny per-bucket shapes with
``jax.make_jaxpr`` — no solver ever RUNS — and checks:

JF100  Registration audit (stdlib AST): every module-level jit in the
       solver directories is registered via ``@solver_jit``, and its
       module is listed in ``registry.SOLVER_MODULES``.  This is what
       retires retrace's hand-maintained jit list: exclusion is now a CI
       failure (``kernels/admission.py`` shipped excluded).
JF101  No float ``reduce_sum`` / ``dot_general`` contraction in a
       bit-exact entry's jaxpr: padded-axis reductions must lower to the
       ``_fold_sum`` positional halving tree (slice/slice/add chains) or
       the ordered fan-in unroll.  The tree itself is verified structurally
       (balanced, positional, association independent of padding).
       Integer/bool sums are exactly associative and pass.  Cases for the
       dense backend — whose reassociation drift is a documented contract —
       exempt themselves with the reason recorded.
JF102  No scatter primitives when a case selects the gather backend: the
       gather tables exist precisely to replace XLA:CPU's serialized
       scatter-add; one surviving scatter voids the ~40x win silently.
JF103  No f64/complex (or 64-bit integer) value anywhere in a solver
       jaxpr — the usual cause is a Python float touching a weakly-typed
       intermediate under ``jax_enable_x64``.
JF104  No host-sync-inducing ops inside ``scan``/``while`` bodies: any
       callback (``pure_callback``/``io_callback``/``debug_callback``),
       infeed/outfeed, or a traced ``lax.cond`` (data-dependent branching
       that XLA cannot vectorize; every solver loop is select-masked
       instead).  Bounded device-side ``while`` loops (rejection sampling
       inside ``jax.random``) are fine.  Pallas kernel bodies are skipped:
       ``pl.when`` is grid-position-static control flow.
JF105  Compile-footprint budgets: each budgeted case is lowered and
       compiled for CPU, op counts and FLOPs/bytes (via
       ``roofline.hlo_stats``) are compared against the checked-in
       ``artifacts/ir_budget.json``; growth beyond tolerance fails with a
       diff.  Regenerate deliberately with ``--write-budget`` (the diff is
       then reviewed like any other artifact change).

CLI: ``python -m repro.analysis ir [paths...] [--write-budget]
[--no-budget] [--budget FILE] [--diff-out FILE]``.  This module imports
jax; the plain lint CLI must not, so ``repro.analysis`` exposes it lazily.
"""

from __future__ import annotations

import ast
import dataclasses
import functools
import importlib
import importlib.util
import json
import os
import sys
from typing import Iterator

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr

from .linter import _dotted, _pragma_ids
from .registry import IR_RULES, SOLVER_MODULES, AuditCase, SolverEntry, \
    registered_entries

__all__ = [
    "IR_RULES",
    "IRFinding",
    "audit_case",
    "audit_fold_tree",
    "check_registration",
    "compare_budget",
    "main_ir",
    "measure_case",
    "primitive_census",
    "trace_case",
]


@dataclasses.dataclass(frozen=True)
class IRFinding:
    rule: str
    entry: str  # dotted entry-point name (or file path for JF100)
    case: str  # AuditCase label; "-" for non-case findings
    message: str

    def __str__(self) -> str:
        return f"{self.entry}[{self.case}]: {self.rule} {self.message}"


# --------------------------------------------------------------------------- #
# jaxpr walking
# --------------------------------------------------------------------------- #


def _subjaxprs(eqn) -> Iterator[Jaxpr]:
    for v in eqn.params.values():
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, (list, tuple)):
            for x in v:
                if isinstance(x, ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, Jaxpr):
                    yield x


def iter_eqns(jaxpr: Jaxpr, in_loop: bool = False, in_pallas: bool = False):
    """Yield ``(eqn, in_loop, in_pallas)`` over every nested equation.

    ``in_loop`` marks equations inside a ``scan``/``while`` body (at any
    nesting depth); ``in_pallas`` marks kernel-body equations, whose
    control flow is grid-static and exempt from the host-sync rule.
    """
    for eqn in jaxpr.eqns:
        yield eqn, in_loop, in_pallas
        name = eqn.primitive.name
        child_loop = in_loop or name in ("scan", "while")
        child_pallas = in_pallas or name == "pallas_call"
        for sub in _subjaxprs(eqn):
            yield from iter_eqns(sub, child_loop, child_pallas)


def primitive_census(closed: ClosedJaxpr) -> dict[str, int]:
    """``{primitive name: count}`` over the whole nested jaxpr — the golden
    snapshot the congestion-backend census tests pin down."""
    out: dict[str, int] = {}
    for eqn, _, _ in iter_eqns(closed.jaxpr):
        out[eqn.primitive.name] = out.get(eqn.primitive.name, 0) + 1
    return dict(sorted(out.items()))


def _out_dtype(eqn) -> str:
    av = getattr(eqn.outvars[0], "aval", None)
    return str(av.dtype) if av is not None and hasattr(av, "dtype") else ""


# --------------------------------------------------------------------------- #
# per-case rules: JF101-JF104
# --------------------------------------------------------------------------- #

_WIDE_DTYPES = ("float64", "complex64", "complex128", "int64", "uint64")
_CALLBACK_PRIMS = (
    "pure_callback", "io_callback", "debug_callback", "callback",
    "infeed", "outfeed",
)


def trace_case(entry: SolverEntry, case: AuditCase) -> ClosedJaxpr:
    """The case's jaxpr: statics bound by keyword, nothing executed."""
    fn = entry.resolve()
    args, kwargs = case.make()
    return jax.make_jaxpr(functools.partial(fn, **kwargs))(*args)


def audit_case(entry: SolverEntry, case: AuditCase,
               closed: ClosedJaxpr | None = None) -> list[IRFinding]:
    """Run JF101-JF104 on one entry/case jaxpr (rules the case exempts,
    with their recorded reason, are skipped)."""
    if closed is None:
        closed = trace_case(entry, case)
    out: list[IRFinding] = []

    def finding(rule: str, msg: str) -> None:
        out.append(IRFinding(rule, entry.name, case.label, msg))

    run101 = "JF101" not in case.exempt
    run102 = case.backend == "gather" and "JF102" not in case.exempt
    run103 = "JF103" not in case.exempt
    run104 = "JF104" not in case.exempt

    if run103:  # inputs/consts can smuggle f64 in without any eqn doing it
        for v in list(closed.jaxpr.invars) + list(closed.jaxpr.constvars):
            av = getattr(v, "aval", None)
            if av is not None and str(getattr(av, "dtype", "")) in _WIDE_DTYPES:
                finding("JF103", f"{av.dtype} input/constant {av.str_short()}")

    for eqn, in_loop, in_pallas in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if run101 and name == "reduce_sum":
            dt = _out_dtype(eqn)
            if dt.startswith("float") or dt.startswith("complex"):
                shape = tuple(eqn.invars[0].aval.shape)
                finding(
                    "JF101",
                    f"float reduce_sum over {shape} axes="
                    f"{eqn.params.get('axes')}: XLA picks the association "
                    "by size, so the result depends on the padding "
                    "envelope; route the reduction through _fold_sum / "
                    "_ordered_fan_in_sum",
                )
        elif run101 and name == "dot_general":
            dt = _out_dtype(eqn)
            if dt.startswith("float") or dt.startswith("complex"):
                finding(
                    "JF101",
                    "dot_general contraction in a bit-exact entry point: "
                    "a matmul reduces with size-dependent association "
                    "(only the dense backend may, and its cases record "
                    "the exemption)",
                )
        elif run102 and name.startswith("scatter"):
            finding(
                "JF102",
                f"{name} under the gather backend: the fan-in tables "
                "exist to replace XLA:CPU's serialized scatter path; "
                "accumulate through _ordered_fan_in_sum instead",
            )
        if run103:
            for v in eqn.outvars:
                av = getattr(v, "aval", None)
                if av is not None and \
                        str(getattr(av, "dtype", "")) in _WIDE_DTYPES:
                    finding(
                        "JF103",
                        f"{name} produces {av.dtype}: solver arithmetic "
                        "is f32/int32; check for a weakly-typed Python "
                        "scalar promoting under jax_enable_x64",
                    )
                    break
        if run104 and in_loop and not in_pallas:
            if name in _CALLBACK_PRIMS:
                finding(
                    "JF104",
                    f"{name} inside a solver loop body: every step "
                    "round-trips to the host, serializing the scan",
                )
            elif name == "cond":
                finding(
                    "JF104",
                    "traced lax.cond inside a solver loop body: a "
                    "data-dependent branch XLA cannot mask-vectorize; "
                    "solver loops use jnp.where select masking",
                )
    return out


# --------------------------------------------------------------------------- #
# fold-tree structure (the JF101 companion: the sanctioned reduction is
# itself verified to be a balanced positional halving)
# --------------------------------------------------------------------------- #


def audit_fold_tree(sizes: tuple[int, ...] = (5, 8, 13)) -> list[IRFinding]:
    """Verify ``core.flow._fold_sum`` lowers to a balanced halving tree.

    For input width ``n`` (padded to ``pow2``): no reduction primitive at
    all, and exactly ``log2(pow2)`` float adds whose operand widths halve
    ``pow2/2, pow2/4, ..., 1`` with equal-shape operands — the positional
    grouping that makes the sum padding-invariant.  Swapping the body for
    a raw ``jnp.sum`` (or any unbalanced chain) is caught here without
    running a solver.
    """
    import numpy as np

    from repro.core import flow

    out: list[IRFinding] = []
    name = "repro.core.flow._fold_sum"
    for n in sizes:
        closed = jax.make_jaxpr(flow._fold_sum)(np.ones(n, np.float32))
        pow2 = 1 << (n - 1).bit_length() if n > 1 else 1
        want = [pow2 >> k for k in range(1, pow2.bit_length())]
        adds = []
        for eqn, _, _ in iter_eqns(closed.jaxpr):
            pname = eqn.primitive.name
            if pname in ("reduce_sum", "dot_general"):
                out.append(IRFinding(
                    "JF101", name, f"n={n}",
                    f"{pname} inside the fold tree: the halving must be "
                    "positional slice+add, not an XLA reduction",
                ))
            elif pname == "add" and _out_dtype(eqn).startswith("float"):
                shapes = [tuple(v.aval.shape) for v in eqn.invars
                          if hasattr(getattr(v, "aval", None), "shape")]
                adds.append((tuple(eqn.outvars[0].aval.shape), shapes))
        got = [s[0][-1] if s[0] else 1 for s in adds]
        balanced = got == want and all(
            len(shapes) == 2 and shapes[0] == shapes[1]
            for _, shapes in adds
        )
        if not balanced:
            out.append(IRFinding(
                "JF101", name, f"n={n}",
                f"fold tree is not a balanced positional halving: add "
                f"widths {got} != expected {want} (padding-invariance "
                "holds only for the equal-halves grouping)",
            ))
    return out


# --------------------------------------------------------------------------- #
# JF100: registration audit (stdlib AST — no tracing)
# --------------------------------------------------------------------------- #

_SOLVER_DIR_PARTS = ("repro/core/", "repro/sim/", "repro/kernels/")


def _is_jit_expr(node: ast.AST) -> bool:
    if _dotted(node) in ("jax.jit", "jit"):
        return True
    if isinstance(node, ast.Call):
        if _dotted(node.func) in ("jax.jit", "jit"):
            return True
        if _dotted(node.func) in ("functools.partial", "partial") \
                and node.args and _dotted(node.args[0]) in ("jax.jit", "jit"):
            return True
    return False


def module_level_jits(source: str, path: str) -> list[tuple[str, int]]:
    """``(name, lineno)`` of every module-level jit definition in a file:
    a decorated ``def`` or a top-level ``name = jax.jit(...)`` binding."""
    tree = ast.parse(source, filename=path)
    out: list[tuple[str, int]] = []
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_is_jit_expr(d) for d in node.decorator_list):
                out.append((node.name, node.lineno))
        elif isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            if _is_jit_expr(node.value) or _is_jit_expr(node.value.func):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out.append((t.id, node.lineno))
    return out


def _module_name(path: str) -> str | None:
    parts = os.path.normpath(path).replace(os.sep, "/").split("/")
    if "repro" not in parts:
        return None
    rel = parts[parts.index("repro"):]
    return ".".join(rel)[: -len(".py")] if rel[-1].endswith(".py") else None


def check_registration(
    paths: list[str], entries: dict[str, SolverEntry] | None = None
) -> list[IRFinding]:
    """JF100 over every solver-directory file under ``paths``."""
    if entries is None:
        entries = registered_entries()
    out: list[IRFinding] = []
    files: list[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, names in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if d not in ("__pycache__", ".git"))
                files.extend(os.path.join(root, n) for n in sorted(names)
                             if n.endswith(".py"))
        elif p.endswith(".py"):
            files.append(p)
    for f in files:
        norm = os.path.normpath(f).replace(os.sep, "/")
        if not any(d in norm for d in _SOLVER_DIR_PARTS):
            continue
        with open(f, "r", encoding="utf-8") as fh:
            source = fh.read()
        lines = source.splitlines()
        mod = _module_name(f)
        for jit_name, lineno in module_level_jits(source, f):
            if 1 <= lineno <= len(lines) and \
                    "JF100" in _pragma_ids(lines[lineno - 1]):
                continue
            if mod is None or mod not in SOLVER_MODULES:
                out.append(IRFinding(
                    "JF100", f, jit_name,
                    f"module-level jit {jit_name!r} in a module missing "
                    "from registry.SOLVER_MODULES: it is invisible to the "
                    "RT-1 cache-size snapshot and the IR audit; add the "
                    "module to the list and register the jit with "
                    "@solver_jit",
                ))
            elif f"{mod}.{jit_name}" not in entries:
                out.append(IRFinding(
                    "JF100", f, jit_name,
                    f"module-level jit {jit_name!r} is not registered: "
                    "decorate it with @solver_jit(spec=...) so retrace "
                    "and the IR audit enumerate it (line "
                    f"{lineno})",
                ))
    return out


# --------------------------------------------------------------------------- #
# JF105: compile-footprint budgets
# --------------------------------------------------------------------------- #

DEFAULT_BUDGET_PATH = os.path.join("artifacts", "ir_budget.json")
#: Growth tolerance: relative headroom plus a per-metric absolute slack so
#: tiny entries aren't pinned to the op.  Shrinkage never fails (it shows
#: in the diff; refresh with --write-budget when intentional).
DEFAULT_TOLERANCE = {
    "rel": 0.25,
    "abs": {"jaxpr_eqns": 16, "hlo_ops": 24, "flops": 4096.0,
            "hbm_bytes": 8192.0, "whiles": 1},
}


def _count_eqns(jaxpr: Jaxpr) -> int:
    return sum(1 for _ in iter_eqns(jaxpr))


def measure_case(entry: SolverEntry, case: AuditCase,
                 closed: ClosedJaxpr | None = None) -> dict:
    """Compile footprint of one budgeted case (CPU-lowered optimized HLO).

    ``jaxpr_eqns`` counts trace-level equations (cheap, stable across XLA
    versions); ``hlo_ops``/``flops``/``hbm_bytes``/``whiles`` come from the
    optimized HLO text through the roofline op-census machinery.
    """
    from repro.roofline.hlo_stats import _split_computations, analyze_hlo

    fn = entry.resolve()
    args, kwargs = case.make()
    if closed is None:
        closed = jax.make_jaxpr(functools.partial(fn, **kwargs))(*args)
    text = fn.lower(*args, **kwargs).compile().as_text()
    stats = analyze_hlo(text, 1)
    seen: set[int] = set()
    hlo_ops = 0
    for comp in _split_computations(text).values():
        if id(comp) in seen:  # "__entry__" aliases its named computation
            continue
        seen.add(id(comp))
        hlo_ops += len(comp.ops)
    return {
        "jaxpr_eqns": _count_eqns(closed.jaxpr),
        "hlo_ops": hlo_ops,
        "flops": round(float(stats.flops), 1),
        "hbm_bytes": round(float(stats.hbm_bytes), 1),
        "whiles": int(stats.n_while_loops),
    }


def compare_budget(measured: dict, budget: dict,
                   complete: bool = True) -> tuple[list[IRFinding], dict]:
    """Diff measured footprints against the checked-in budget.

    Returns ``(findings, diff)``: JF105 findings for growth beyond
    tolerance, for measured cases with no recorded budget, and — when
    ``complete`` (no path filter narrowed the audit) — for stale recorded
    cases that no longer exist.  ``diff`` is the full machine-readable
    comparison (the CI artifact), including in-tolerance drift.
    """
    tol = budget.get("tolerance", DEFAULT_TOLERANCE)
    rel = float(tol.get("rel", 0.25))
    abs_ = tol.get("abs", {})
    recorded = budget.get("entries", {})
    findings: list[IRFinding] = []
    diff: dict = {"entries": {}, "ok": True}

    def split(name: str) -> tuple[str, str]:
        ent, _, lab = name.partition("[")
        return ent, lab.rstrip("]") or "-"

    for name in sorted(measured):
        m = measured[name]
        b = recorded.get(name)
        row: dict = {}
        if b is None:
            findings.append(IRFinding(
                "JF105", *split(name),
                "no recorded compile budget for this case; approve it "
                "into artifacts/ir_budget.json with "
                "`python -m repro.analysis ir --write-budget`",
            ))
            row = {k: {"measured": v, "budget": None, "ok": False}
                   for k, v in m.items()}
        else:
            for k, v in m.items():
                base = b.get(k)
                limit = None if base is None else \
                    base * (1.0 + rel) + float(abs_.get(k, 0))
                ok = limit is None or v <= limit
                row[k] = {"measured": v, "budget": base, "limit": limit,
                          "ok": ok}
                if not ok:
                    findings.append(IRFinding(
                        "JF105", *split(name),
                        f"{k} grew {base} -> {v} (limit {limit:.1f}, "
                        f"rel tol {rel:+.0%}): compile footprint regression"
                        "; if intentional, refresh the budget with "
                        "--write-budget and review the diff",
                    ))
        diff["entries"][name] = row
    if complete:
        for name in sorted(set(recorded) - set(measured)):
            findings.append(IRFinding(
                "JF105", *split(name),
                "stale budget entry: the case no longer exists; refresh "
                "artifacts/ir_budget.json with --write-budget",
            ))
            diff["entries"][name] = {"stale": True}
    diff["ok"] = not findings
    return findings, diff


# --------------------------------------------------------------------------- #
# driver / CLI
# --------------------------------------------------------------------------- #


def _entry_file(entry: SolverEntry) -> str | None:
    spec = importlib.util.find_spec(entry.module)
    return None if spec is None else spec.origin


def _under(path: str, roots: list[str]) -> bool:
    ap = os.path.abspath(path)
    for r in roots:
        ar = os.path.abspath(r)
        if ap == ar or ap.startswith(ar.rstrip(os.sep) + os.sep):
            return True
    return False


def run_audit(paths: list[str], budget_path: str | None,
              write_budget: bool = False,
              diff_out: str | None = None) -> tuple[list[IRFinding], dict]:
    """Full audit over the entries whose modules live under ``paths``."""
    entries = registered_entries()
    selected = {
        name: e for name, e in entries.items()
        if (f := _entry_file(e)) is not None and _under(f, paths)
    }
    findings = list(check_registration(paths, entries))
    measured: dict[str, dict] = {}
    for name, entry in selected.items():
        for case in entry.cases():
            closed = trace_case(entry, case)
            findings.extend(audit_case(entry, case, closed))
            if case.budget and budget_path is not None:
                measured[f"{name}[{case.label}]"] = \
                    measure_case(entry, case, closed)
    if any(e.module == "repro.core.flow" for e in selected.values()):
        findings.extend(audit_fold_tree())

    diff: dict = {}
    if budget_path is not None:
        all_budgeted = {
            f"{n}[{c.label}]" for n, e in entries.items()
            for c in e.cases() if c.budget
        }
        complete = set(measured) >= all_budgeted
        if write_budget:
            payload = {
                "comment": (
                    "JF105 compile-footprint budgets (python -m "
                    "repro.analysis ir). Regenerate deliberately with "
                    "--write-budget; the diff is reviewed like code."
                ),
                "jax": jax.__version__,
                "tolerance": DEFAULT_TOLERANCE,
                "entries": measured,
            }
            os.makedirs(os.path.dirname(budget_path) or ".", exist_ok=True)
            with open(budget_path, "w", encoding="utf-8") as fh:
                json.dump(payload, fh, indent=1, sort_keys=True)
                fh.write("\n")
        elif os.path.exists(budget_path):
            with open(budget_path, "r", encoding="utf-8") as fh:
                budget = json.load(fh)
            if budget.get("jax") != jax.__version__:
                print(
                    f"ir-audit: budget recorded on jax {budget.get('jax')}"
                    f", running {jax.__version__}: tolerance absorbs "
                    "minor drift, refresh on upgrade",
                    file=sys.stderr,
                )
            bud_findings, diff = compare_budget(
                measured, budget, complete=complete
            )
            findings.extend(bud_findings)
        elif measured:
            findings.append(IRFinding(
                "JF105", budget_path, "-",
                "budget file missing; create it with --write-budget",
            ))
    if diff_out is not None:
        os.makedirs(os.path.dirname(diff_out) or ".", exist_ok=True)
        with open(diff_out, "w", encoding="utf-8") as fh:
            json.dump(diff or {"entries": {}, "ok": not findings}, fh,
                      indent=1, sort_keys=True)
            fh.write("\n")
    return findings, diff


def main_ir(argv: list[str]) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m repro.analysis ir",
        description="jaxpr/HLO-level solver invariant audit (JF100-JF105)",
    )
    p.add_argument("paths", nargs="*", default=None,
                   help="files/dirs to audit (default: src)")
    p.add_argument("--budget", default=DEFAULT_BUDGET_PATH,
                   help="compile-footprint budget file (JF105)")
    p.add_argument("--write-budget", action="store_true",
                   help="record current footprints as the new budget")
    p.add_argument("--no-budget", action="store_true",
                   help="skip the JF105 compile/footprint pass")
    p.add_argument("--diff-out", default=None,
                   help="write the budget comparison JSON here (CI artifact)")
    ns = p.parse_args(argv)
    paths = ns.paths or ["src"]
    findings, _ = run_audit(
        paths,
        budget_path=None if ns.no_budget else ns.budget,
        write_budget=ns.write_budget,
        diff_out=ns.diff_out,
    )
    for f in findings:
        print(f)
    if findings:
        counts: dict[str, int] = {}
        for f in findings:
            counts[f.rule] = counts.get(f.rule, 0) + 1
        summary = ", ".join(
            f"{r} x{n} ({IR_RULES[r]})" for r, n in sorted(counts.items())
        )
        print(f"\nir-audit: {len(findings)} finding(s): {summary}",
              file=sys.stderr)
        return 1
    n = len(registered_entries())
    print(f"ir-audit: clean ({n} registered entries)")
    return 0
