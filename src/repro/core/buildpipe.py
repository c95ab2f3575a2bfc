"""Host/device double-buffered path-system build pipeline.

The sweep drivers (fig1c bisection probes, fig7 failure levels) interleave
two very different workloads per instance shard:

    host:   enumerate + assemble   (numpy frontier expansion, GIL-releasing
            BLAS/gather work in ``build_path_system_batch``)
    device: batched MW solve       (jit'd XLA executable; dispatch returns
            as soon as the computation is enqueued)

Run sequentially, the device sits idle while the host enumerates and vice
versa.  This module overlaps them with ONE stage of lookahead:

    shard:      0          1          2
    host    [build 0] [build 1] [build 2]
    device            [solve 0] [solve 1] [solve 2]
                       ^ build 1 runs while solve 0 executes

``stream_builds(thunks)`` submits build i+1 to a single background worker
*before* yielding build i, so the consumer's device solve of shard i always
executes concurrently with the host enumeration of shard i+1.

Buffering discipline — why exactly one worker and one slot of lookahead:

- ``max_workers=1`` serializes all builds on one thread, so the routing
  module's process-global ``_topo_cache`` (and the jit caches the builders
  touch) only ever see one mutating thread during a stream.  Builds never
  run concurrently with each other — only with the *consumer's* device
  work — which is what makes the pipeline a pure scheduling change.
- One slot of lookahead bounds peak memory at two in-flight builds
  (the one being consumed + the one being built), keeping the envelope of
  a pipelined sweep within 2x of the sequential driver's.

Bit-exactness: the pipeline reorders nothing — thunk i's result is yielded
at position i, and each thunk runs exactly once on the single worker in
submission order.  Combined with ``build_path_system_batch``'s own
contract (batch == B sequential builds, INVARIANTS.md CT-build), a
pipelined sweep produces byte-identical path systems, alphas, and verdicts
to the sequential driver; the only observable difference is wall-clock.
``REPRO_BUILD_PIPELINE=0`` (or ``enabled=False``) degrades to strict
sequential execution on the caller's thread — same results, no worker —
which is both the fallback flag the benchmarks expose and the reference
the parity tests compare against.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterable, Iterator, TypeVar

from .. import env
from .. import obs

__all__ = ["pipeline_enabled", "set_build_pipeline", "stream_builds"]

T = TypeVar("T")

_pipeline_default = bool(env.read("REPRO_BUILD_PIPELINE"))


def pipeline_enabled(enabled: bool | None = None) -> bool:
    """Resolve a driver's ``enabled`` argument against the process default.

    ``None`` means "whatever ``REPRO_BUILD_PIPELINE`` said at import" (on
    unless the env set 0, possibly overridden by ``set_build_pipeline``);
    an explicit bool always wins, so callers can force either mode
    per call site.
    """
    return _pipeline_default if enabled is None else bool(enabled)


def set_build_pipeline(flag: bool) -> bool:
    """Flip the process-wide pipeline default; returns the previous value.

    The env var only seeds the initial state (read once at import, the
    ``repro.env`` discipline); the parity benches and tests flip this to
    time/compare both drivers in one process without re-importing.
    """
    global _pipeline_default
    prev, _pipeline_default = _pipeline_default, bool(flag)
    return prev


def stream_builds(
    thunks: Iterable[Callable[[], T]],
    enabled: bool | None = None,
) -> Iterator[T]:
    """Yield ``thunk()`` results in order, prefetching one build ahead.

    Each element of ``thunks`` is a zero-argument build closure (typically
    wrapping ``build_path_system_batch`` over one instance shard).  With
    the pipeline enabled, build i+1 is submitted to the single background
    worker before build i is yielded, overlapping the consumer's device
    solve with the next host enumeration.  Results arrive in submission
    order regardless of timing; a thunk that raises propagates at its own
    yield position and cancels nothing already submitted (the single
    worker drains it, matching sequential semantics).
    """
    if not pipeline_enabled(enabled):
        for i, thunk in enumerate(thunks):
            with obs.span("build/serial", idx=i):
                result = thunk()
            yield result
        return

    def run(thunk: Callable[[], T], idx: int) -> tuple[T, float]:
        # executes on the single worker thread — the span carries that
        # thread's id, so Perfetto shows builds as their own lane
        with obs.span("build/prefetch", idx=idx):
            t0 = time.perf_counter()
            out = thunk()
            return out, time.perf_counter() - t0

    def drain(fut) -> T:
        t0 = time.perf_counter()
        out, build_s = fut.result()
        stall_s = time.perf_counter() - t0
        # stall: consumer time blocked waiting on the worker; overlap:
        # build time hidden behind the consumer's own (device) work
        obs.counter("pipeline/builds").inc()
        obs.counter("pipeline/stall_s").inc(stall_s)
        obs.counter("pipeline/overlap_s").inc(max(build_s - stall_s, 0.0))
        return out

    it = iter(thunks)
    with ThreadPoolExecutor(max_workers=1) as pool:
        pending = None
        for i, thunk in enumerate(it):
            fut = pool.submit(run, thunk, i)
            if pending is not None:
                yield drain(pending)
            pending = fut
        if pending is not None:
            yield drain(pending)
