"""Record the small device trace that ``tests/test_xtrace.py`` reduces.

    python3 chipbench/record_trace.py OUT_DIR

Runs, on the chip, a few calls of a jitted matmul and of the program's
fused congestion kernel at a small stacked shape, inside the benchmark's
own window and unit annotations, with the profiler on.  Writes the
``.xplane.pb`` to OUT_DIR/small.xplane.pb and a plain listing of its planes,
lines and first events to OUT_DIR/small.txt (for reading by hand), and
prints the reduction of the trace as JSON.
"""

from __future__ import annotations

import glob
import json
import pathlib
import shutil
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE.parent))


def main() -> int:
    out = pathlib.Path(sys.argv[1])
    out.mkdir(parents=True, exist_ok=True)
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print(f"needs a TPU; found {jax.devices()[0].platform}", file=sys.stderr)
        return 1
    from repro.kernels.congestion import congestion_pallas

    from chipbench import xtrace

    mm = jax.jit(lambda a: (a @ a).sum())
    a = jnp.ones((1024, 1024), jnp.float32)
    inc = jnp.asarray(np.random.default_rng(0).random((2, 512, 1024)) < 0.01,
                      jnp.float32)
    r = jnp.ones((2, 512), jnp.float32)
    w = jnp.ones((2, 1024), jnp.float32)
    mm(a).block_until_ready()
    jax.block_until_ready(congestion_pallas(inc, r, w))
    tmp = tempfile.mkdtemp()
    with xtrace.profile(tmp):
        with jax.profiler.TraceAnnotation(xtrace.WINDOW):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("chipbench/unit"):
                    mm(a).block_until_ready()
                    jax.block_until_ready(congestion_pallas(inc, r, w))
    path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(path, out / "small.xplane.pb")
    shutil.rmtree(tmp)
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(str(out / "small.xplane.pb")).planes:
        lines.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            lines.append(f"  LINE {line.name} ({len(evs)} events)")
            for e in evs[:12]:
                lines.append(f"    {e.name!r} start={e.start_ns} dur={e.duration_ns} "
                             f"stats={list(e.stats)[:8]}")
    (out / "small.txt").write_text("\n".join(lines) + "\n")
    tr = xtrace.Trace.from_file(str(out / "small.xplane.pb"))
    print(json.dumps({"window_s": tr.window_s, "busy_s": tr.busy_s(),
                      "ops": tr.top_ops(10), "gaps": tr.idle_gaps(10),
                      "modules": tr.top_modules(10)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
