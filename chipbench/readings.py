#!/usr/bin/env python3
"""Readings that the comparison's limits are set from, on the chip.

    python3 chipbench/readings.py --workload CELL --seeds S1 S2 ... \
        [--control-seeds C1 C2 C3]

For each seed, in this one process: the cell's set-up, one unit through
the timed path at the cell's own size (``Cell.unit``), and the check's
numbers of that unit.  For each control seed the same with the control in
the program's place (``Cell.control_unit``).  One JSON line per reading on
standard output, with every number the check computes, compared or not.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
for _p in (str(HERE.parent / "src"), str(HERE.parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def reading(spec: dict, seed: int, control: bool) -> dict:
    from chipbench import run

    cell = run.kind_module(spec["traffic"]["kind"]).Cell(
        spec["cfg"], spec["traffic"], seed)
    unit = cell.control_unit(0) if control else cell.unit(0)
    out = cell.check([unit])
    numbers = dict(out.get("numbers", {}))
    for per in out.get("per_unit", []):
        numbers.update(per)
    return {"workload": spec["name"], "seed": seed,
            "side": "control" if control else "program", "numbers": numbers,
            "info": out.get("info", {})}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args()
    from chipbench import run

    spec = run.load_spec(args.workload)
    run.prepare_env(False)
    run._configure_jax()
    import jax

    print(f"device: {jax.devices()[0].device_kind}", file=sys.stderr)
    for s in args.seeds:
        print(json.dumps(reading(spec, s, False)), flush=True)
    for s in args.control_seeds:
        print(json.dumps(reading(spec, s, True)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
