"""repro.analysis: the determinism toolbox — linter, contracts, tracer.

Three layers guard the invariants the solvers' bit-exactness claims rest on
(INVARIANTS.md is the catalog):

- :mod:`repro.analysis.linter` — pure-stdlib AST linter (rules JF001-JF006)
  run as ``python -m repro.analysis src benchmarks``; CI's lint lane.
- :mod:`repro.analysis.contracts` — runtime validators for PathSystem /
  PathSystemBatch / SimResult structural invariants, wired into the build
  boundaries behind ``REPRO_CHECK=1`` (tier-1 tests default it on).
- :mod:`repro.analysis.retrace` — compile-count tracer asserting
  one-compile-per-shape-bucket (exposed lazily: it imports jax, the
  lint CLI must not).
- :mod:`repro.analysis.registry` — the ``@solver_jit`` entry-point registry
  retrace and the IR auditor enumerate (pure stdlib).
- :mod:`repro.analysis.irlint` — jaxpr/HLO-level static auditor (rules
  JF100-JF105), ``python -m repro.analysis ir``; lazy like retrace.
"""

from __future__ import annotations

from .contracts import (
    ContractViolation,
    check_hop_matrix,
    check_path_system,
    check_path_system_batch,
    check_segment_layout,
    check_sim_state,
    checks_enabled,
    set_check_enabled,
)
from .linter import RULES, Violation, lint_file, lint_paths, lint_source

__all__ = [
    "ContractViolation",
    "RULES",
    "Violation",
    "check_hop_matrix",
    "check_path_system",
    "check_path_system_batch",
    "check_segment_layout",
    "check_sim_state",
    "checks_enabled",
    "irlint",
    "lint_file",
    "lint_paths",
    "lint_source",
    "registry",
    "retrace",
    "set_check_enabled",
]


def __getattr__(name: str):
    # lazy: retrace/irlint import jax; the lint CLI must not.  registry is
    # stdlib but joins them for symmetry of access.
    if name in ("retrace", "irlint", "registry"):
        import importlib

        return importlib.import_module(f"repro.analysis.{name}")
    raise AttributeError(f"module 'repro.analysis' has no attribute {name!r}")
