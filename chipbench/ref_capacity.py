"""Plain references of the capacity cells: the search's probe wave.

Nothing here imports the program.  ``mw_probe`` is the multiplicative-
weights recurrence of ``ref.mw_solve`` (one-step price lag, geometric
temperature anneal 0.2 -> 0.005 of the maximum load over the whole budget,
step 2/sqrt(1+t), best exactly-evaluated iterate), run for one instance at
a time in windows of ``CHECK_EVERY`` iterations: after each window, an
instance whose best alpha has reached 1.0 stops, and its last
iterate is evaluated once more, exactly.  That is the probe of the Fig 1c
capacity search (Singla et al., NSDI 2012, §4.1).  ``wave`` folds the
probes into per-candidate verdicts: a candidate is accepted when each of
its instances reaches alpha >= 1 - tol.

In float64 (``precision="f64"``) it is the check's reference; with
``precision="bf16"`` every stored array is rounded to bfloat16 and the
segment sums accumulate in bfloat16, in index order (the control).
"""

from __future__ import annotations

import numpy as np

from chipbench import ref

#: Iterations between the program's stop checks (``mw_concurrent_flow_batch``'s
#: ``check_every``, which the capacity search leaves at its default).
CHECK_EVERY = 50


def mw_probe(pe, plen, owner, demand, n_slots: int, iters: int,
             precision: str = "f64") -> tuple[float, np.ndarray, int]:
    """(alpha, rates, iterations run) of one windowed MW probe, unit capacity.

    ``rates`` are the best iterate's per-path rates scaled by min(alpha, 1).
    """
    if precision == "f64":
        dt, q = np.float64, (lambda x: x)
    elif precision == "bf16":
        dt, q = np.float32, ref._bf16
    else:
        raise ValueError(f"unknown precision {precision!r}")
    pe = np.asarray(pe, np.int64)
    plen = np.asarray(plen, np.int64)
    owner = np.asarray(owner, np.int64)
    K = len(demand)
    real = np.arange(pe.shape[1])[None, :] < plen[:, None]
    flat_slot = pe[real]
    flat_row = np.repeat(np.arange(len(pe)), plen)
    dem = q(np.asarray(demand, dt)[owner])

    def segment_sum(idx, vals, n):
        if precision == "f64":
            return np.bincount(idx, weights=vals, minlength=n)[:n]
        import ml_dtypes

        acc = np.zeros(n, ml_dtypes.bfloat16)
        np.add.at(acc, idx, vals.astype(ml_dtypes.bfloat16))
        return acc.astype(np.float32)

    def loads_of(rates):
        return segment_sum(flat_slot, rates[flat_row], n_slots)

    def seg_norm(x):
        return q(x / segment_sum(owner, x, K)[owner])

    x = seg_norm(np.ones(len(pe), dt))
    rel_prev = np.zeros(n_slots, dt)
    best_alpha, best_x = 0.0, x
    done = 0
    while done < iters and best_alpha < 1.0:
        for t in range(done, min(done + CHECK_EVERY, iters)):
            frac = 0.2 * (0.005 / 0.2) ** (t / iters)
            tau = max(float(rel_prev.max()), 1e-12) * frac
            z = q(rel_prev / dt(tau))
            e = q(np.exp(z - z.max()))
            w = q(e / q(np.asarray(e.sum(), dt)))
            loads = loads_of(q(x * dem))
            costs = segment_sum(flat_row, w[flat_slot], len(pe))
            alpha = 1.0 / max(float(loads.max()), 1e-12)
            if alpha > best_alpha:
                best_alpha, best_x = alpha, x
            g = q(costs * dem)
            g = q(g / max(float(g.max()), 1e-12))
            eta = 2.0 / np.sqrt(1.0 + t)
            x = seg_norm(q(x * q(np.exp(-eta * g))))
            rel_prev = loads
        done = min(done + CHECK_EVERY, iters)
    alpha = 1.0 / max(float(loads_of(q(x * dem)).max()), 1e-12)
    if alpha > best_alpha:
        best_alpha, best_x = alpha, x
    rates = q(best_x * dem * dt(min(best_alpha, 1.0)))
    return float(best_alpha), np.asarray(rates, np.float64), done


def wave(candidates, iters: int, tol: float, precision: str = "f64"):
    """(verdicts, answers) of a probe wave; ``candidates[c]`` lists
    candidate c's instances as (pe, plen, owner, demand, n_slots), and
    ``answers[c]`` their (alpha, rates, iterations)."""
    verdicts, answers = [], []
    for group in candidates:
        mine = [mw_probe(*inst, iters, precision) for inst in group]
        answers.append(mine)
        verdicts.append(all(a >= 1.0 - tol for a, _, _ in mine))
    return verdicts, answers
