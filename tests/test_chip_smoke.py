"""``chip_smoke.py``: refuses to run off-TPU, and its phases run end to end
at a tiny size on the CPU (the rehearsal before a chip run)."""

from __future__ import annotations

import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]

TINY = {
    "route_n": 64, "route_ports": 12, "route_r": 8,
    "dense_n": 48, "dense_ports": 10, "dense_r": 7, "mw_iters": 100,
    "ref_fattree_k": 6,
    "sim_n": 32, "sim_ports": 10, "sim_r": 7, "sim_seeds": 2,
    "sim_steps": 24, "sim_fail_step": 8, "sim_heal_step": 16,
    "sim_rate": 4.0, "sim_max_flows": 128, "sim_max_arrivals": 8,
    "sim_wf_iters": 4,
}


def test_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=120)
    assert run.returncode != 0
    assert "'cpu'" in run.stderr
    assert '"ok"' not in run.stdout


def test_phases_run_at_tiny_size(monkeypatch):
    monkeypatch.syspath_prepend(str(ROOT))
    import chip_smoke

    assert set(TINY) <= set(chip_smoke.FULL)
    sizes = dict(chip_smoke.FULL, **TINY)
    phases = chip_smoke.run_phases(sizes, lambda *a, **k: None)
    assert [p["check"] for p in phases] == ["passed"] * len(chip_smoke.PHASES)
    dense = phases[2]
    assert dense["fused_rel_err"] <= 1e-5
    assert dense["max_rel_diff"] <= 1e-3
