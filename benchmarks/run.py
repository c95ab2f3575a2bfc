# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness entry point.

    PYTHONPATH=src python -m benchmarks.run            # full suite
    PYTHONPATH=src python -m benchmarks.run fig1 fig7  # subset
    REPRO_BENCH_FULL=1 ... run                         # paper-scale sizes

Artifacts land in artifacts/bench/*.json (consumed by EXPERIMENTS.md).

Every run also appends one row to ``BENCH_OBS.json`` (repo root): per-bench
wall seconds, process peak RSS, and XLA compile counts — the persistent
perf trajectory across PRs.  With ``REPRO_TRACE=1`` the whole run is
spanned per bench and the trace exports to ``{REPRO_TRACE_OUT}/`` as both
JSONL and a Perfetto-loadable Chrome trace."""

from __future__ import annotations

import json
import sys
import time
import traceback

from repro import obs
from repro.analysis.retrace import install_compile_listener
from repro.compile_cache import REPO_ROOT, enable_compile_cache

MODULES = [
    ("fig1", "benchmarks.fig1_capacity"),
    ("fig2", "benchmarks.fig2_degree_diameter"),
    ("fig3", "benchmarks.fig3_swdc"),
    ("fig4", "benchmarks.fig4_path_length"),
    ("fig5", "benchmarks.fig5_incremental"),
    ("fig6", "benchmarks.fig6_legup"),
    ("fig7", "benchmarks.fig7_resilience"),
    ("fig7time", "benchmarks.fig7_time"),
    ("fig8", "benchmarks.fig8_mptcp"),
    ("fig9ecmp", "benchmarks.fig9_ecmp"),
    ("table1", "benchmarks.table1_diversity"),
    ("fig12", "benchmarks.fig12_locality"),
    ("cabling", "benchmarks.fig_cabling"),
    ("fabric", "benchmarks.fabric_scale"),
    ("kernels", "benchmarks.kernels_bench"),
]

#: The accreting perf-trajectory file — one JSON list, one row per run.
TRAJECTORY = REPO_ROOT / "BENCH_OBS.json"


def append_trajectory(benches: dict, failures: int) -> None:
    from benchmarks.common import FULL, SMOKE

    rows = []
    if TRAJECTORY.exists():
        try:
            rows = json.loads(TRAJECTORY.read_text())
        except json.JSONDecodeError:
            rows = []  # never let a corrupt trajectory kill a bench run
        if not isinstance(rows, list):
            rows = []
    rows.append(
        {
            "unix_time": time.time(),
            "mode": "smoke" if SMOKE else ("full" if FULL else "default"),
            "failures": failures,
            "metrics": obs.snapshot(),
            "benches": benches,
        }
    )
    TRAJECTORY.write_text(json.dumps(rows, indent=1))


def main() -> None:
    want = set(sys.argv[1:])
    enable_compile_cache()
    install_compile_listener()  # compile events -> obs bus for every bench
    print("name,us_per_call,derived")
    failures = 0
    benches: dict[str, dict] = {}
    for tag, modname in MODULES:
        if want and tag not in want:
            continue
        t0 = time.time()
        try:
            with obs.count_compiles() as cc, obs.span(f"bench/{tag}"):
                mod = __import__(modname, fromlist=["run"])
                for line in mod.run():
                    print(line, flush=True)
            dt = time.time() - t0
            benches[tag] = obs.perf_record(tag, dt, compiles=cc.count)
            print(f"# {tag} done in {dt:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {tag} FAILED:", flush=True)
            traceback.print_exc()
    append_trajectory(benches, failures)
    print(f"# trajectory row appended to {TRAJECTORY}", flush=True)
    if obs.trace_enabled():
        jsonl = obs.write_jsonl()
        chrome = obs.write_chrome_trace()
        print(f"# trace artifacts: {jsonl} {chrome}", flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
