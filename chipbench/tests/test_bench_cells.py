"""CPU rehearsals of the benchmark's cells at tiny sizes.

Every cell of ``BENCHMARK.json`` runs through the harness
(``run.run_cell``) with its traffic's unit kind, the program's real entry
points and the plain references, with the harness's look for a chip
skipped.  A sound run is correct; the control (``Cell.control_unit``) and
each fault of the kind (``faults/<kind>.py``), planted in the timed path,
make it not correct.  The command itself refuses to run off a TPU.
"""

from __future__ import annotations

import copy
import json
import os
import pathlib
import subprocess
import sys

from chipbench import run

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
SEED = 2**33 + 12345  # past 32 bits, as benchmark seeds may be


def tiny(name: str) -> dict:
    """The cell's spec with the fabric shrunk and MW shortened."""
    spec = copy.deepcopy(run.load_spec(name))
    spec["cfg"].update(switches=40, ports=10, network_ports=6)
    tr = spec["traffic"]
    if "iters" in tr:
        tr["iters"] = 60
    if "schedule" in tr:  # 3 fabrics, 40 steps, failure at 12, repair at 28,
        # flows short enough to complete and free their slots
        tr.update(fabrics=3, steps=40, rate=6.0, size=8.0, max_flows=128,
                  check_replays=3)
        tr["schedule"] = [dict(tr["schedule"][0], step=12),
                          dict(tr["schedule"][1], step=28)]
    tr["check_pairs"] = 200  # every commodity of the tiny fabric
    return spec


def go(spec, seconds=0.0):
    return run.run_cell(spec, SEED, seconds, trace=False, require_chip=False)


def pytest_generate_tests(metafunc):
    """Every cell of ``BENCHMARK.json``, and every fault of its unit kind."""
    if "fault" in metafunc.fixturenames:
        metafunc.parametrize("name,fault", [(c, f) for c in CELLS
                                            for f in _faults(c)])
    elif "name" in metafunc.fixturenames:
        metafunc.parametrize("name", CELLS)


def test_sound_run_is_correct(name):
    res = go(tiny(name))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    measures = tiny(name)["traffic"]["measures"]
    assert set(res["metrics"]) == {measures, "setup_s"}
    assert list(res)[-2:] == ["checks", "_log"]


def _faults(name: str) -> dict:
    kind = run.load_spec(name)["traffic"]["kind"]
    return run._load(HERE / "faults" / f"{kind}.py",
                     f"chipbench_faults_{kind}").FAULTS


def test_control_is_not_correct(monkeypatch, name):
    """The control (the reference one precision step down, or the routing
    control) in the program's place."""
    spec = tiny(name)
    cls = run.kind_module(spec["traffic"]["kind"]).Cell
    monkeypatch.setattr(cls, "unit", cls.control_unit)
    res = go(spec)
    assert not res["correct"], res["checks"]


def test_fault_is_not_correct(monkeypatch, name, fault):
    """A fault planted in the timed path (``faults/<kind>.py``)."""
    spec = tiny(name)
    attr, fn = _faults(name)[fault]
    cls = run.kind_module(spec["traffic"]["kind"]).Cell
    orig = getattr(cls, attr)
    monkeypatch.setattr(cls, attr, lambda self, *a: fn(self, orig, *a))
    res = go(spec)
    assert not res["correct"], res["checks"]


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_benchmark_json_names_files_that_exist():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        spec = run.load_spec(w["name"])
        assert (run.HERE / "kinds" / f"{spec['traffic']['kind']}.py").is_file()
        assert spec["traffic"]["measures"] in {
            m["name"] for m in spec["end_to_end"]}
        assert spec["per_layer"], w["name"]
    for m in bench["per_layer"]:
        assert run.metric_reader(m["name"]).is_file(), m["name"]
    for w in bench["workloads"]:
        kind = run.load_spec(w["name"])["traffic"]["kind"]
        assert callable(getattr(run.kind_module(kind).Cell, "control_unit"))
        assert _faults(w["name"]), kind
