"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

The profiler writes one plane per device (``/device:TPU:0`` ...) whose
``XLA Ops`` line holds one event per operation run on the device and whose
``XLA Modules`` line holds one event per compiled program run, and host
planes whose lines hold the ``TraceAnnotation`` spans of this benchmark
(``chipbench/window``, ``chipbench/unit``, ``chipbench/check``) and the
runtime's own host events.  All events share the profiler's clock.

The reduction, over the traced window (the ``chipbench/window`` span):

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices; ``1 - busy_s / window_s`` is the idle
  share.
* ``module_s`` / ``op_s``: summed device time of the programs / operations
  whose names contain a given string, with their event counts.
* ``top_ops`` / ``top_modules``: the device operations and programs that
  took the most time.
* ``idle_gaps``: the longest stretches with no device operation, each
  named by the innermost host event open at its middle.
"""

from __future__ import annotations

import contextlib
import re

WINDOW = "chipbench/window"

_DEVICE = re.compile(r"^/device:(?!CPU)[A-Za-z_]+:\d+$")
# first operand of an HLO instruction's text: "... op(f32[2,512,1024]{...} %x, ..."
_OPERAND = re.compile(r"\w[\w-]*\([a-z0-9]+\[([\d,]+)\]")


def short_name(name: str) -> str:
    """An HLO instruction's name without its text, a program's name without
    its fingerprint; other names as they are."""
    return re.sub(r"\(\d+\)$", "", name.split(" = ", 1)[0].lstrip("%"))


@contextlib.contextmanager
def profile(log_dir: str):
    """The JAX profiler around a block, host Python tracing off (it would
    slow the host path being measured)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _union(iv):
    out = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


class Trace:
    """Device and host events of one trace, clipped to the window."""

    def __init__(self, devices: list[dict], host: list[tuple]):
        self.host = host  # (name, start_ns, end_ns)
        wins = [(a, b) for n, a, b in host if n == WINDOW]
        if wins:
            self.t0, self.t1 = wins[0]
        else:  # no window span: the whole trace
            ends = [b for d in devices for _, _, b in d["ops"]] + [
                b for _, _, b in host]
            starts = [a for d in devices for _, a, _ in d["ops"]] + [
                a for _, a, _ in host]
            self.t0, self.t1 = (min(starts), max(ends)) if starts else (0, 0)
        self.devices = [
            {k: [(n, a, b) for n, a, b in v if b > self.t0 and a < self.t1]
             for k, v in d.items()}
            for d in devices
        ]

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        devices, host = [], []
        for plane in ProfileData.from_file(path).planes:
            name = plane.name
            if _DEVICE.match(name):
                d = {"ops": [], "modules": []}
                for line in plane.lines:
                    key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                        line.name)
                    if key is None:
                        continue
                    d[key] += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events]
                devices.append(d)
            elif name.startswith("/host:"):
                for line in plane.lines:
                    host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.duration_ns > 0]
        return cls(devices, host)

    def add_host_spans(self, spans, window_open: float) -> None:
        """Put host spans timed by ``time.perf_counter`` (the program's
        ``obs`` spans) on the trace's clock, by the window's opening on
        both clocks, so that idle gaps can be named by them."""
        for name, t0, t1 in spans:
            self.host.append((name, self.t0 + (t0 - window_open) * 1e9,
                              self.t0 + (t1 - window_open) * 1e9))

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _busy(self, dev: dict) -> list:
        return _union(_clip([(a, b) for _, a, b in dev["ops"]],
                            self.t0, self.t1))

    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over devices."""
        if not self.devices:
            return 0.0
        tot = sum(sum(b - a for a, b in self._busy(d)) for d in self.devices)
        return tot * 1e-9 / len(self.devices)

    def _sum(self, key: str, pattern: str) -> tuple[float, int]:
        tot, cnt = 0.0, 0
        for d in self.devices:
            for n, a, b in d[key]:
                if pattern in n:
                    a, b = max(a, self.t0), min(b, self.t1)
                    tot += b - a
                    cnt += 1
        k = max(len(self.devices), 1)
        return tot * 1e-9 / k, cnt // k

    def module_s(self, pattern: str) -> tuple[float, int]:
        """(device seconds, runs) of programs whose name contains pattern."""
        return self._sum("modules", pattern)

    def op_s(self, pattern: str) -> tuple[float, int]:
        """(device seconds, runs) of operations whose name contains pattern."""
        return self._sum("ops", pattern)

    def op_shapes(self, pattern: str) -> list[tuple[int, ...]]:
        """Shape of the first operand of each distinct operation whose name
        contains pattern (the event name is the HLO instruction's text)."""
        out = []
        for d in self.devices:
            for name, _, _ in d["ops"]:
                if pattern in name:
                    m = _OPERAND.search(name)
                    if m:
                        shape = tuple(int(x) for x in m.group(1).split(","))
                        if shape not in out:
                            out.append(shape)
        return out

    def _top(self, key: str, n: int) -> list:
        agg: dict[str, float] = {}
        for d in self.devices:
            for name, a, b in d[key]:
                name = short_name(name)
                agg[name] = agg.get(name, 0.0) + (
                    min(b, self.t1) - max(a, self.t0)) * 1e-9
        k = max(len(self.devices), 1)
        top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
        return [[name, s / k] for name, s in top]

    def top_ops(self, n: int = 10) -> list:
        return self._top("ops", n)

    def top_modules(self, n: int = 10) -> list:
        return self._top("modules", n)

    def idle_gaps(self, n: int = 10) -> list:
        """[[host activity, seconds], ...] of the n longest idle gaps on the
        first device, longest first."""
        if not self.devices:
            return []
        busy = self._busy(self.devices[0])
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:n]:
            mid = (a + b) / 2
            open_ = [(e - s, name) for name, s, e in self.host
                     if s <= mid <= e and name != WINDOW]
            label = min(open_)[1] if open_ else "no host span"
            out.append([label, (b - a) * 1e-9])
        return out
