#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 chipbench/run.py --workload CELL --seed N --seconds S --trace 0|1

The cell is looked up by name in ``BENCHMARK.json`` at the checkout root;
its configuration, traffic and limits are data files found by name
(``configs/<config>.json``, ``traffic/<traffic>.json``,
``limits/<cell>.json``), the traffic names a unit kind
(``kinds/<kind>.py``), and each per-layer metric is read by
``metrics/<metric>.py``, or by ``metrics/<prefix>.py`` for a metric named
``<prefix>.<suffix>``.  A cell or metric is added by adding files.

A run: set-up (inputs made from the seed, the program's own set-up, one
warm-up unit so that every program the window runs is compiled and
loaded), then the window: units back to back until the first that ends at
or after ``--seconds``.  Every end-to-end metric is the window's time over
the work it finished.  With ``--trace 1`` the window runs under the JAX
profiler and the program's host spans (``REPRO_TRACE=1``), and the run
reports the per-layer metrics and a breakdown instead.  After the window
the answers are compared with the plain references (``ref.py``), each
number against its limit; ``correct`` is whether all hold.

Off a TPU, or with fewer chips than the cell asks for, the command exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: JAX's persistent compilation cache: the directory the environment names
#: in ``JAX_COMPILATION_CACHE_DIR``, else a fixed one inside the checkout.
CACHE_DIR = pathlib.Path(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                         or ROOT / ".jax_cache")


class NoChip(RuntimeError):
    """The process found no accelerator, or fewer chips than the cell needs."""


def _load(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kind_module(kind: str):
    """The unit kind ``kinds/<kind>.py``."""
    return importlib.import_module(f"chipbench.kinds.{kind}")


def metric_reader(name: str) -> pathlib.Path:
    """The reader of a per-layer metric: ``metrics/<name up to the first
    dot>.py``, so ``idle_share.mw`` and ``idle_share.sim`` share one."""
    return HERE / "metrics" / f"{name.split('.')[0]}.py"


def load_spec(workload: str) -> dict:
    """Everything the run needs to know about one cell, from the data files."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfgs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((ROOT / cfgs[cell["config"]]["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    limits = json.loads((HERE / "limits" / f"{workload}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return {"name": workload, "chips": cell["chips"], "cfg": cfg,
            "traffic": traffic, "limits": limits, "end_to_end": e2e,
            "per_layer": layer}


def prepare_env(trace: bool) -> None:
    """Process settings that must precede the first import of JAX or the
    program: the program's defaults (no contract checks), its host spans
    in a traced run, and the compilation cache inside the checkout."""
    os.environ.pop("REPRO_CHECK", None)
    if trace:
        os.environ["REPRO_TRACE"] = "1"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)


def _configure_jax():
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


class CompileCounter:
    """Backend compiles (persistent-cache misses) and their seconds."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n, self.s = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1
            self.s += float(duration)


def device_info(jax, chips: int, require_chip: bool) -> tuple[dict, list]:
    devs = jax.devices()
    d = devs[0]
    if require_chip and (d.platform != "tpu" or len(devs) < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                     f"{d.platform!r} device(s) ({d.device_kind})")
    return ({"platform": d.platform, "kind": d.device_kind,
             "count": chips}, devs[:chips])


def memory_peak(devs) -> int:
    """The runtime's peak of bytes held in arrays, on the fullest chip.  It
    leaves out a compiled program's temporaries (``program_bytes``)."""
    peaks = []
    for d in devs:
        try:
            peaks.append(int(d.memory_stats()["peak_bytes_in_use"]))
        except Exception:  # the CPU backend keeps no statistics
            peaks.append(0)
    return max(peaks)


@contextlib.contextmanager
def recording(names):
    """Record the abstract arguments of every distinct call of the jitted
    functions ``names`` (``"module.attr"``) made inside the block."""
    import jax

    calls: dict = {}
    saved = []

    def abstract(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype) if hasattr(
            x, "shape") and hasattr(x, "dtype") else x

    for name in names:
        mod_name, attr = name.rsplit(".", 1)
        mod = importlib.import_module(mod_name)
        fn = getattr(mod, attr)

        def wrapper(*a, _fn=fn, **k):
            sig = jax.tree_util.tree_map(abstract, (a, k))
            calls.setdefault(repr(sig), (_fn, sig))
            return _fn(*a, **k)

        saved.append((mod, attr, fn))
        setattr(mod, attr, wrapper)
    try:
        yield calls
    finally:
        for mod, attr, fn in saved:
            setattr(mod, attr, fn)


def program_bytes(calls: dict) -> int:
    """The largest device footprint of the recorded programs: arguments,
    outputs not aliased to them, and temporaries, as the compiler plans
    them (``memory_analysis`` of the same executable, from the cache)."""
    most = 0
    for fn, (a, k) in calls.values():
        ma = fn.lower(*a, **k).compile().memory_analysis()
        if ma is None:
            continue
        most = max(most, int(ma.argument_size_in_bytes
                             + ma.output_size_in_bytes
                             - ma.alias_size_in_bytes
                             + ma.temp_size_in_bytes))
    return most


def _annotate(trace: bool, name: str):
    if not trace:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             require_chip: bool = True) -> dict:
    """One run of one cell; returns the result object the command prints."""
    import jax

    device, devs = device_info(jax, spec["chips"], require_chip)
    compiles = CompileCounter()
    from chipbench import xtrace

    cell = kind_module(spec["traffic"]["kind"]).Cell(
        spec["cfg"], spec["traffic"], seed)
    with recording(getattr(cell, "window_programs", ())) as programs:
        cell.warm()
    n_setup_compiles, setup_compile_s = compiles.n, compiles.s

    tmp = tempfile.mkdtemp(prefix="chipbench-") if trace else None
    units, error = [], None
    prof = xtrace.profile(tmp) if trace else contextlib.nullcontext()
    with prof:
        w0 = time.perf_counter()
        with _annotate(trace, xtrace.WINDOW):
            i = 0
            while True:
                try:
                    with _annotate(trace, "chipbench/unit"):
                        units.append(cell.unit(i))
                except Exception as exc:  # a unit that raises has failed
                    traceback.print_exc()
                    error = f"unit {i}: {type(exc).__name__}: {exc}"
                    break
                i += 1
                if time.perf_counter() - w0 >= seconds:
                    break
        w1 = time.perf_counter()
    window_s = w1 - w0
    window_compiles = compiles.n - n_setup_compiles
    runtime_peak = memory_peak(devs)
    n_before = compiles.n
    program_peak = program_bytes(programs)
    footprint_compiles = compiles.n - n_before
    device["memory_peak_bytes"] = max(runtime_peak, program_peak)

    result = {"correct": False, "attempted": len(units) + (error is not None),
              "failed": int(error is not None), "metrics": {},
              "device": device}
    if trace:
        tr = xtrace.Trace.from_file(
            next(pathlib.Path(tmp).rglob("*.xplane.pb")).as_posix())
        shutil.rmtree(tmp, ignore_errors=True)
        spans = _window_spans(w0, w1)
        tr.add_host_spans([(s.name, s.t0, s.t0 + s.wall_s) for s in spans],
                          w0)
        ctx = {"trace": tr, "units": len(units), "host_window": (w0, w1),
               "spans": spans, "device_kind": device["kind"]}
        ctx.update(cell.context(units))
        for m in spec["per_layer"]:
            reader = _load(metric_reader(m["name"]),
                           f"chipbench_metric_{m['name']}")
            v = reader.read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
    else:
        scale = {"s": 1.0, "ms": 1e3}
        for m in spec["end_to_end"]:
            if m["name"] == "setup_s":
                v = w0 - T_START
            elif m["name"] == spec["traffic"]["measures"] and units:
                v = window_s / (len(units) * cell.work_per_unit) * scale[m["unit"]]
            else:
                continue
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}

    cell.release()
    checks = cell.check(units)
    limits = spec["limits"]
    numbers = dict(checks.get("numbers", {}))
    failed_units = 0
    for per in checks.get("per_unit", []):
        failed_units += any(not v <= limits.get(k, float("inf"))
                            for k, v in per.items())
        for k, v in per.items():
            numbers[k] = max(numbers.get(k, 0), v)
    result["failed"] += failed_units
    result["correct"] = bool(units) and error is None and all(
        numbers.get(k, float("inf")) <= lim for k, lim in limits.items())
    result["checks"] = {k: {"value": _number(numbers.get(k)), "limit": lim}
                        for k, lim in limits.items()}
    result["_log"] = {"error": error, "units": len(units),
                      "window_s": window_s, "compiles_in_window":
                      window_compiles, "setup_compiles": n_setup_compiles,
                      "setup_compile_s": setup_compile_s,
                      "runtime_peak_bytes": runtime_peak,
                      "program_peak_bytes": program_peak,
                      "footprint_compiles": footprint_compiles,
                      "info": checks.get("info", {})}
    return result


def _number(v):
    """A compared number for the result line; JSON has no inf or nan."""
    return v if v is None or math.isfinite(v) else str(v)


def _window_spans(w0: float, w1: float) -> list:
    from repro import obs

    return [s for s in obs.get_spans() if w0 <= s.t0 and s.t0 + s.wall_s <= w1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec(args.workload)
    prepare_env(bool(args.trace))
    _configure_jax()
    try:
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    except NoChip as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 3
    log = result.pop("_log")
    print(f"chipbench: {json.dumps(log)}", file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
