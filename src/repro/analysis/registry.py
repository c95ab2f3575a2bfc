"""Solver entry-point registry: jits self-register for audit and retrace.

Before this module, ``retrace.py`` kept a hand-maintained 16-tuple of
``(module, attr)`` names; a newly added solver jit (the
``kernels/admission.py`` case) could ship silently excluded from the RT-1
cache-size assertions and from any IR-level audit.  Now every module-level
solver jit registers itself at definition site:

    @solver_jit(spec="_ir_cases_mw_window_batch")
    @functools.partial(jax.jit, static_argnames=(...))
    def _mw_window_batch(...): ...

and the registry is the single enumeration both consumers read:

- :mod:`repro.analysis.retrace` — ``named_solver_jits`` / RT-1 cache sizes;
- :mod:`repro.analysis.irlint` — jaxpr/HLO rule audit (JF100–JF105) over
  the shape-bucket cases each entry's ``spec`` describes.

``spec`` names a zero-argument module-level function (resolved lazily, so
spec builders can live anywhere in the module and cost nothing at import)
returning a list of :class:`AuditCase` — concrete tiny-shape arguments per
backend.  Non-jit but traceable dispatch wrappers (``kernels/ops.py``)
register with ``kind="wrapper"``: they join the IR audit but are skipped by
the retrace cache-size snapshot, which only makes sense for jits.

The "nothing is silently excluded" guarantee is mechanical: rule JF100
(:mod:`repro.analysis.irlint`) AST-scans every module under the solver
directories for module-level jits and fails the audit when one is not
registered here — including modules missing from :data:`SOLVER_MODULES`.

This module is pure stdlib (no jax import): the lint CLI and the linter's
pragma validation read :data:`IR_RULES` without warming a runtime.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Mapping

__all__ = [
    "IR_RULES",
    "SOLVER_MODULES",
    "AuditCase",
    "SolverEntry",
    "registered_entries",
    "solver_jit",
]

#: Every module that defines (or may grow) module-level solver jits.  A new
#: solver module adds itself here; rule JF100 cross-checks the list against
#: an AST scan of the solver directories, so forgetting is a CI failure,
#: not a silent exclusion.  ``core/routing.py`` holds no jits today (it is
#: host-side enumeration feeding the jitted solvers) but stays listed so
#: the first jit someone adds there must register or JF100 fires.
SOLVER_MODULES = (
    "repro.core.flow",
    "repro.core.routing",
    "repro.core.mptcp",
    "repro.sim.engine",
    "repro.sim.events",
    "repro.kernels.ops",
    "repro.kernels.admission",
    "repro.kernels.congestion",
    "repro.kernels.minplus",
    "repro.kernels.power",
    "repro.kernels.ref",
)

#: IR-level audit rules (checked by ``python -m repro.analysis ir``; see
#: INVARIANTS.md).  Kept here — stdlib-importable — so the AST linter can
#: validate repro-lint disable pragma ids against the full rule set
#: without importing jax.
IR_RULES = {
    "JF100": "every module-level solver jit is registered for audit",
    "JF101": "no raw float contraction outside the _fold_sum halving tree",
    "JF102": "no scatter-add in congestion bodies under the gather backend",
    "JF103": "no f64/complex or weak-type promotion in solver jaxprs",
    "JF104": "no host-sync ops or traced cond inside solver loop bodies",
    "JF105": "compile footprint within the checked-in ir_budget.json",
}


@dataclasses.dataclass(frozen=True)
class AuditCase:
    """One concrete tiny-shape invocation of a solver entry point.

    ``make`` returns ``(args, kwargs)`` — numpy/jax arrays for traced
    parameters, Python values for static ones (passed as keywords so the
    jit resolves them by name).  Shapes mirror one shape bucket; array
    CONTENTS are irrelevant to tracing and compiling, so builders use
    zeros/aranges and never run a topology build.

    ``backend`` scopes JF102 (it only constrains the gather backend).
    ``exempt`` maps rule ids to the documented reason a rule deliberately
    does not apply (e.g. the dense backend's reassociation drift is a
    feature contract, not a bug).  ``budget`` opts the case into the JF105
    compile-footprint snapshot — interpret-mode Pallas lowerings are left
    out: their HLO is an emulation artifact, large and version-brittle.
    """

    label: str
    make: Callable[[], tuple[tuple, dict]]
    backend: str | None = None
    exempt: Mapping[str, str] = dataclasses.field(default_factory=dict)
    budget: bool = True


@dataclasses.dataclass(frozen=True)
class SolverEntry:
    """A registered solver entry point, addressed by dotted names.

    Names (not objects) are stored so resolution happens at call time via
    ``getattr`` — a test monkeypatching the module attribute sees its
    stand-in picked up, and ``retrace.solver_cache_sizes`` keeps its
    ``-1`` non-jit fallback semantics.
    """

    module: str
    attr: str
    kind: str = "jit"  # "jit" | "wrapper" (traceable non-jit dispatcher)
    spec: str | None = None  # module-level zero-arg fn -> list[AuditCase]

    @property
    def name(self) -> str:
        return f"{self.module}.{self.attr}"

    def resolve(self) -> Any:
        return getattr(importlib.import_module(self.module), self.attr)

    def cases(self) -> list[AuditCase]:
        if self.spec is None:
            return []
        fn = getattr(importlib.import_module(self.module), self.spec)
        return list(fn())


_REGISTRY: dict[str, SolverEntry] = {}


def solver_jit(spec: str | None = None, kind: str = "jit"):
    """Decorator registering a module-level solver jit (or wrapper).

    Apply ABOVE the ``@jax.jit`` / ``functools.partial(jax.jit, ...)``
    decorator; the function object passes through untouched.  ``spec``
    names a zero-arg function in the same module returning the entry's
    :class:`AuditCase` list (resolved lazily, so it may be defined later
    in the file).
    """
    if kind not in ("jit", "wrapper"):
        raise ValueError(f"unknown solver entry kind: {kind!r}")

    def register(fn):
        module, attr = fn.__module__, fn.__name__
        if not module or not attr:
            raise ValueError(
                f"solver_jit needs __module__/__name__ on {fn!r}; decorate "
                "the jit directly (jax.jit preserves both)"
            )
        _REGISTRY[f"{module}.{attr}"] = SolverEntry(
            module=module, attr=attr, kind=kind, spec=spec
        )
        return fn

    return register


def registered_entries() -> dict[str, SolverEntry]:
    """``{dotted name: SolverEntry}`` after importing every solver module.

    Importing :data:`SOLVER_MODULES` triggers the decorators; the result is
    sorted by name so audit output and budget files are stably ordered.
    """
    for mod in SOLVER_MODULES:
        importlib.import_module(mod)
    return dict(sorted(_REGISTRY.items()))
