"""Fig 7: failure resilience sweep.

(a) normalized per-server throughput vs link-failure rate for a fat-tree and
a same-equipment Jellyfish carrying MORE servers (the paper's framing: the
capacity/path/resilience advantages hold simultaneously);
(b) claim check: 15% failures cost Jellyfish < 16% raw capacity.

Failure sweeps run *incrementally*: links fail cumulatively (each level's
failed set extends the previous level's — still a uniform sample at every
level), and the path system is repaired per increment through
``routing.update_path_system`` instead of rebuilt from scratch.  A full
rebuild at every level cross-checks alpha parity; the JSON payload records
the delta-vs-rebuild routing speedup alongside the throughput rows.

The per-seed sweeps advance in LOCKSTEP so every failure level's alpha
evaluations — all seeds' delta systems plus their rebuild cross-checks —
go through ``benchmarks.common.batch_alphas`` (LP below the path cutoff,
one ``mw_concurrent_flow_batch`` call above it), the batched-solver rung.
"""

from __future__ import annotations

import numpy as np

from repro.core import (
    build_path_system,
    build_path_system_batch,
    fail_links,
    fattree,
    fattree_equipment,
    jellyfish,
    pipeline_enabled,
    random_permutation_traffic,
    same_equipment_jellyfish,
    stream_builds,
    update_path_system,
)

from .common import (
    FULL,
    Timer,
    batch_alphas,
    csv_row,
    save,
)


def _build_many(tops, comms, k: int, slack: int, cache: bool = True) -> list:
    """B path systems — one batched build when the pipeline is enabled
    (``REPRO_BUILD_PIPELINE``, default on), else the sequential loop.  The
    batch builder's CT-build contract makes both byte-identical."""
    if pipeline_enabled():
        return list(build_path_system_batch(
            tops, comms, k=k, max_slack=slack, cache=cache
        ).systems)
    return [build_path_system(t, c, k=k, max_slack=slack, cache=cache)
            for t, c in zip(tops, comms)]


def _incremental_fail_sweeps(top, fractions, seeds, k: int, slack: int) -> list[dict]:
    """Cumulatively fail links for several sweep seeds in lockstep,
    delta-updating each seed's path system per level and evaluating every
    level's (delta + rebuild) systems in one batched alpha call.  All of a
    level's rebuild cross-checks (distinct failed topologies) go through
    one ``build_path_system_batch`` call; the first level also bit-checks
    the batched rebuild against a sequential build in-bench."""
    comms = [random_permutation_traffic(top, seed=seed) for seed in seeds]
    with Timer() as t_b:
        systems = _build_many([top] * len(comms), comms, k, slack)
    per_build = t_b.dt / max(len(comms), 1)
    states = []
    for seed, comm, ps in zip(seeds, comms, systems):
        states.append({
            "rng": np.random.default_rng(seed), "comm": comm, "ps": ps,
            "cur": top, "removed": 0, "t_delta": per_build,
            "t_full": per_build, "alphas": {}, "parity": 0.0,
        })
    e0 = top.n_edges
    cur_alpha = batch_alphas([st["ps"] for st in states])
    build_parity_pending = pipeline_enabled()
    for f in fractions:
        changed, nxts = [], []
        for si, st in enumerate(states):
            need = int(round(f * e0)) - st["removed"]
            if need > 0:
                nxt = fail_links(st["cur"], seed=st["rng"], n_links=need)
                with Timer() as t_u:
                    st["ps"] = update_path_system(st["ps"], st["cur"], nxt,
                                                  st["comm"])
                st["t_delta"] += t_u.dt
                st["cur"] = nxt
                st["removed"] += need
                changed.append(si)
                nxts.append(nxt)
        if changed:
            with Timer() as t_f:
                rebuilds = _build_many(
                    nxts, [states[si]["comm"] for si in changed], k, slack,
                    cache=False,
                )
            per_full = t_f.dt / len(changed)
            for si, ps_full in zip(changed, rebuilds):
                states[si]["ps_full"] = ps_full
                states[si]["t_full"] += per_full
            if build_parity_pending:
                # batched rebuild vs legacy sequential build: byte parity
                build_parity_pending = False
                si = changed[0]
                ps_seq = build_path_system(
                    states[si]["cur"], states[si]["comm"], k=k,
                    max_slack=slack, cache=False,
                )
                assert (
                    np.array_equal(np.asarray(ps_seq.path_edges),
                                   np.asarray(rebuilds[0].path_edges))
                    and np.array_equal(np.asarray(ps_seq.path_len),
                                       np.asarray(rebuilds[0].path_len))
                    and np.array_equal(np.asarray(ps_seq.path_owner),
                                       np.asarray(rebuilds[0].path_owner))
                ), "pipelined batch build diverged from sequential build"
            # one batched evaluation per level: each changed seed's delta
            # system and its from-scratch rebuild (the parity cross-check)
            a = batch_alphas(
                [states[si]["ps"] for si in changed]
                + [states[si]["ps_full"] for si in changed]
            )
            for j, si in enumerate(changed):
                cur_alpha[si] = a[j]
                states[si]["parity"] = max(
                    states[si]["parity"], abs(a[j] - a[len(changed) + j])
                )
        for si, st in enumerate(states):
            st["alphas"][f] = min(cur_alpha[si], 1.0)
    return [
        {
            "alphas": st["alphas"], "delta_s": st["t_delta"],
            "rebuild_s": st["t_full"],
            "speedup": st["t_full"] / max(st["t_delta"], 1e-12),
            "max_alpha_diff": st["parity"],
        }
        for st in states
    ]


def run() -> list[str]:
    k = 8
    eq = fattree_equipment(k)
    ft = fattree(k)
    jf = same_equipment_jellyfish(
        eq["switches"], eq["ports_per_switch"], int(eq["servers"] * 1.15), seed=0
    )
    fractions = (0.0, 0.03, 0.06, 0.09, 0.12, 0.15)
    rows, out = [], []
    with Timer() as t:
        ft_sweeps = _incremental_fail_sweeps(ft, fractions, seeds=range(3),
                                             k=16, slack=4)
        jf_sweeps = _incremental_fail_sweeps(jf, fractions, seeds=range(3),
                                             k=16, slack=4)
        for f in fractions:
            a_ft = float(np.mean([sw["alphas"][f] for sw in ft_sweeps]))
            a_jf = float(np.mean([sw["alphas"][f] for sw in jf_sweeps]))
            rows.append({"fail": f, "fattree": a_ft, "jellyfish": a_jf})
            out.append(
                csv_row(f"fig7_fail{int(f*100):02d}", 0.0,
                        f"ft={a_ft:.3f};jf={a_jf:.3f}")
            )
    # 15%-failure claim at a full-capacity topology (paper: <16% loss).
    # Two views over 3 topology seeds at 120 switches:
    #   raw capacity (uncapped alpha) and the paper's plotted metric,
    #   normalized per-server throughput (capped at line rate).
    raw_drops, norm_after = [], []
    tseeds = (1, 2, 3)

    def claim15_build(tseed):
        def thunk():
            top = jellyfish(120, 13, 10, seed=tseed)
            failed = fail_links(top, 0.15, seed=90 + tseed)
            comms = [random_permutation_traffic(top, seed=s) for s in range(2)]
            return top, failed, comms, _build_many([top] * 2, comms, 8, 4)
        return thunk

    # stream_builds prefetches tseed t+1's intact builds on the worker
    # while this thread repairs + solves tseed t; the consumer-side repairs
    # run cache=False so the routing cache stays single-writer (the worker)
    # for the duration of the stream
    for top, failed, comms, intact in stream_builds(
        claim15_build(t) for t in tseeds
    ):
        systems = []
        for comm, ps in zip(comms, intact):
            # the failed fabric reuses the intact fabric's routing state
            ps_f = update_path_system(ps, top, failed, comm, cache=False)
            systems.extend([ps, ps_f])
        # the tseed's four (intact, failed) x matrix solves in one batch
        a = batch_alphas(systems)
        base, aft = float(np.mean(a[0::2])), float(np.mean(a[1::2]))
        raw_drops.append(1 - aft / base)
        norm_after.append(min(aft, 1.0) / min(base, 1.0))
    drop = float(np.mean(raw_drops))
    norm = float(np.mean(norm_after))
    rows.append({"raw_capacity_drop_at_15pct": drop,
                 "normalized_throughput_at_15pct": norm})
    out.append(csv_row("fig7_drop15", t.dt * 1e6,
                       f"raw_drop={drop:.3f}(~0.16);normalized={norm:.3f}(>=0.84)"))
    delta = {
        "speedup_vs_rebuild": float(np.mean(
            [sw["speedup"] for sw in ft_sweeps + jf_sweeps])),
        "max_alpha_diff": float(np.max(
            [sw["max_alpha_diff"] for sw in ft_sweeps + jf_sweeps])),
    }
    out.append(csv_row("fig7_delta_routing", 0.0,
                       f"speedup={delta['speedup_vs_rebuild']:.1f}x;"
                       f"alpha_diff={delta['max_alpha_diff']:.2e}"))
    save("fig7_resilience",
         {"rows": rows, "delta_routing": delta, "seconds": round(t.dt, 2)})
    return out


def run_time_domain() -> list[str]:
    """Fig 7 time-domain companion: throughput retention under LIVE traffic.

    The steady-state sweep above measures what a failed fabric *can* carry;
    this run measures what in-flight traffic *keeps* while failures land —
    ``sim.events.simulate_events`` injects an MTBF-driven failure process
    (paired MTTR repairs) into a running scan, migrating live flows across
    each delta and blackholing disrupted ones for the detection lag.  Per
    MTBF level: mean throughput retention across failure events, blackholed
    volume, disrupted-flow counts, and an IN-BENCH volume-conservation
    assertion (offered == delivered + blackholed + in-flight) — the
    segmented driver's acceptance criterion, checked on every row.
    """
    from repro.sim import (
        SimConfig,
        event_summary,
        poisson_failure_schedule,
        simulate,
        simulate_events,
        steady_poisson,
    )
    from repro.core.flow import PathSystemBatch
    from repro.core.traffic import (
        permutation_commodities,
        random_server_permutation,
    )

    def _jsonable_summary(summ):
        # event_summary rows carry per-instance numpy arrays (with NaN for
        # undefined retention/FCT); JSON has no NaN, so those become null
        def clean(v):
            if isinstance(v, np.ndarray):
                return [
                    None if (isinstance(x, float) and np.isnan(x)) else x
                    for x in v.astype(np.float64).tolist()
                ]
            return v

        return [{k: clean(v) for k, v in s.items()} for s in summ]

    n_sw, steps, n_inst = (40, 240, 3) if FULL else (22, 120, 2)
    mtbfs = (60.0, 30.0, 15.0) if FULL else (40.0, 15.0)
    k = 4
    tops = [jellyfish(n_sw, 8, 5, seed=s + 1) for s in range(n_inst)]
    comms = [
        permutation_commodities(
            t, random_server_permutation(t.n_servers, np.random.default_rng(s))
        )
        for s, t in enumerate(tops)
    ]
    systems = [build_path_system(t, c, k=k) for t, c in zip(tops, comms)]
    wl = steady_poisson(steps, 3.0)
    cfg = SimConfig(max_flows=512, max_arrivals=8, wf_iters=6)
    base = simulate(
        PathSystemBatch.from_systems(list(systems)), wl, policy="ecmp",
        config=cfg, seed=11,
    )
    base_thr = float(base.throughput[steps // 2:].mean())
    out, rows = [], []
    event_rows: dict[str, list] = {}
    lag_used = None
    with Timer() as t_all:
        for mtbf in mtbfs:
            sched = poisson_failure_schedule(
                steps, mtbf_steps=mtbf, mttr_steps=mtbf / 2.0,
                start_step=steps // 6, seed=17,
            )
            ev = simulate_events(
                tops, comms, sched, wl, systems=list(systems),
                policy="ecmp", config=cfg, seed=11,
            )
            res = ev.result
            lag_used = ev.lag
            # the acceptance criterion: volume conservation under live events
            off = res.comm_offered.sum(axis=1, dtype=np.float64)
            dele = res.comm_delivered.sum(axis=1, dtype=np.float64)
            err = np.abs(off - (dele + res.blackholed_total + res.inflight))
            assert np.all(err <= 1e-3 * np.maximum(off, 1.0)), (
                f"conservation violated at mtbf={mtbf}: {err}"
            )
            summ = event_summary(ev)
            event_rows[f"mtbf{int(mtbf):03d}"] = _jsonable_summary(summ)
            rets = np.concatenate(
                [s["throughput_retention"] for s in summ]
            ) if summ else np.array([1.0])
            retention = float(np.nanmean(rets))
            ev_thr = float(res.throughput[steps // 2:].mean())
            vs_base = ev_thr / max(base_thr, 1e-12)
            bh = float(res.blackholed_total.sum())
            disrupted = int(sum(int(s["disrupted"].sum()) for s in summ))
            killed = int(sum(int(s["killed"].sum()) for s in summ))
            rows.append({
                "mtbf_steps": mtbf,
                "n_events": len(sched),
                "retention_mean": retention,
                "steady_vs_nofail": vs_base,
                "blackholed": bh,
                "disrupted_flows": disrupted,
                "killed_flows": killed,
                "conservation_err_max": float(err.max()),
            })
            out.append(csv_row(
                f"fig7_time_mtbf{int(mtbf):03d}", 0.0,
                f"retention={retention:.3f};vs_nofail={vs_base:.3f};"
                f"blackholed={bh:.1f};disrupted={disrupted}",
            ))
    save("fig7_time_domain", {
        "rows": rows,
        # per-boundary telemetry, persisted — not just asserted in-bench:
        # one serialized event_summary row per failure/repair boundary
        # (throughput retention, blackholed bytes, migration counts, FCT
        # before/after), keyed by MTBF level
        "telemetry": {"event_summary": event_rows},
        "baseline_steady_throughput": base_thr,
        "policy": "ecmp",
        "lag_steps": lag_used,
        "seconds": round(t_all.dt, 2),
    })
    return out


if __name__ == "__main__":
    print("\n".join(run()))
