"""Plain references of the benchmark: routing and the MW throughput solve.

Nothing here imports the program.  The references work from the fabric and
the traffic that ``gen`` made, and from the answers the program returned:

* ``bfs_hops`` — all-pairs hop counts by breadth-first search (scipy).
* ``k_shortest`` — the k shortest simple paths of one switch pair, in the
  order the configuration states: by length, ties by lexicographic node
  sequence from the lower to the higher switch id (reversed for a pair
  whose source is the higher id), at most ``max_slack`` hops past the
  shortest.  A depth-first walk over sorted neighbour lists, pruned by the
  hop distance to the target.
* ``path_table_faults`` — every path row of a routed path system checked
  against the fabric: directed-slot ids decode to links of the fabric
  (slot ``e`` is edge ``e`` of the sorted edge list low->high, slot
  ``e + E`` high->low), hops chain from the commodity's source to its
  destination, no switch repeats, lengths lie within the slack, and each
  commodity's rows are distinct, start at the shortest length and are in
  (length, lexicographic) order, at most k of them.
* ``mw_solve`` — the multiplicative-weights maximum-concurrent-flow
  recurrence the configuration names (one-step price lag, geometric
  temperature anneal 0.2 -> 0.005 of the maximum load, step 2/sqrt(1+t),
  best exactly-evaluated iterate), in float64 with numpy; with
  ``precision="bf16"`` every stored array is rounded to bfloat16 and the
  segment sums accumulate in bfloat16 (the control).
* ``certify`` — what an MW answer says, in float64: its rates load no
  slot past capacity (``overload``), and they ship every commodity the
  fraction ``alpha`` claims (``cert_gap``).
"""

from __future__ import annotations

import numpy as np


# --------------------------------------------------------------------------- #
# fabric
# --------------------------------------------------------------------------- #


def bfs_hops(n: int, edges: np.ndarray) -> np.ndarray:
    """(n, n) float64 hop counts (inf where unreachable)."""
    from scipy.sparse import csr_array
    from scipy.sparse.csgraph import shortest_path

    e = np.asarray(edges)
    a = csr_array((np.ones(len(e)), (e[:, 0], e[:, 1])), shape=(n, n))
    return shortest_path(a, directed=False, unweighted=True)


def neighbour_lists(n: int, edges: np.ndarray) -> list[np.ndarray]:
    e = np.asarray(edges)
    both = np.concatenate([e, e[:, ::-1]])
    order = np.lexsort((both[:, 1], both[:, 0]))
    both = both[order]
    cuts = np.searchsorted(both[:, 0], np.arange(n + 1))
    return [both[cuts[i]:cuts[i + 1], 1] for i in range(n)]


def slot_ends(n_edges: int, edges: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(head, tail) switch of every directed slot, plus a -1 sentinel slot."""
    e = np.asarray(edges)
    head = np.concatenate([e[:, 0], e[:, 1], [-1]])
    tail = np.concatenate([e[:, 1], e[:, 0], [-1]])
    return head, tail


# --------------------------------------------------------------------------- #
# routing
# --------------------------------------------------------------------------- #


def k_shortest(nbrs, dist, s: int, t: int, k: int, max_slack: int,
               reverse_ties: bool = False) -> list[list[int]]:
    """The configuration's k shortest simple s->t paths (see module doc).

    ``reverse_ties=True`` breaks ties by reverse lexicographic order: the
    broken guarantee of the routing control.
    """
    lo, hi = (s, t) if s <= t else (t, s)
    base = dist[lo, hi]
    if not np.isfinite(base):
        return []
    base = int(base)
    if base == 0:
        return [[s]]
    out: list[list[int]] = []
    drow = dist[hi]
    for length in range(base, base + max_slack + 1):
        found: list[list[int]] = []
        path = [lo]
        on = {lo}

        def walk(u: int, left: int) -> None:
            cand = nbrs[u]
            cand = cand[drow[cand] <= left - 1]
            if reverse_ties:
                cand = cand[::-1]
            for v in cand.tolist():
                if v == hi:
                    if left == 1:
                        found.append(path + [hi])
                    continue
                if v in on or left == 1:
                    continue
                path.append(v)
                on.add(v)
                walk(v, left - 1)
                path.pop()
                on.discard(v)
                if len(out) + len(found) >= k:
                    return

        walk(lo, length)
        out.extend(found[: k - len(out)])
        if len(out) >= k:
            break
    if s > t:
        out = [p[::-1] for p in out]
    return out


def decode_rows(pe: np.ndarray, plen: np.ndarray, rows: np.ndarray,
                head: np.ndarray, tail: np.ndarray) -> list[list[int]]:
    """Node sequences of the given path rows, through the slot table."""
    out = []
    for p in rows.tolist():
        h = int(plen[p])
        sl = pe[p, :h]
        out.append([int(head[sl[0]])] + tail[sl].tolist() if h else [])
    return out


def path_table_faults(pe, plen, owner, src, dst, dist, n_edges, edges, k,
                      max_slack) -> int:
    """Number of path rows and commodities that break the routing contract."""
    pe = np.asarray(pe, np.int64)
    plen = np.asarray(plen, np.int64)
    owner = np.asarray(owner, np.int64)
    P, L = pe.shape
    S = 2 * n_edges
    head, tail = slot_ends(n_edges, edges)
    col = np.arange(L)[None, :]
    real = col < plen[:, None]
    bad = np.zeros(P, dtype=bool)
    bad |= (plen < 1) | (plen > L)
    bad |= np.any(real & ((pe < 0) | (pe >= S)), axis=1)
    bad |= np.any(~real & (pe != S), axis=1)
    slot = np.where(real & (pe >= 0) & (pe < S), pe, S)
    h, t = head[slot], tail[slot]
    bad |= np.any(real[:, 1:] & (h[:, 1:] != t[:, :-1]), axis=1)
    K = len(src)
    bad |= (owner < 0) | (owner >= K)
    ow = np.clip(owner, 0, K - 1)
    bad |= h[:, 0] != src[ow]
    last = t[np.arange(P), np.clip(plen - 1, 0, L - 1)]
    bad |= last != dst[ow]
    nodes = np.concatenate([h[:, :1], np.where(real, t, -2 - col)], axis=1)
    srt = np.sort(nodes, axis=1)
    bad |= np.any((srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] >= 0), axis=1)
    base = dist[src[ow], dst[ow]]
    bad |= (plen < base) | (plen > base + max_slack)
    n_bad = int(bad.sum())
    # per commodity: grouped rows, count <= k, first is a shortest path,
    # rows strictly increasing in (length, lexicographic node sequence)
    if np.any(np.diff(owner) < 0):
        return n_bad + 1
    counts = np.bincount(ow, minlength=K)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    bad_c = (counts < 1) | (counts > k)
    has = counts > 0
    bad_c[has] |= plen[starts[has]] != base[starts[has]]
    same = owner[1:] == owner[:-1]
    # ties order the node sequences from the lower switch id to the higher
    j = np.arange(L + 1)[None, :]
    rev = (src[ow] > dst[ow])[:, None] & (j <= plen[:, None])
    canon = nodes[np.arange(P)[:, None], np.where(rev, plen[:, None] - j, j)]
    prev, nxt = canon[:-1], canon[1:]
    longer = plen[1:] > plen[:-1]
    eq_len = plen[1:] == plen[:-1]
    diff = prev != nxt
    first = np.argmax(diff, axis=1)
    lex_up = diff.any(axis=1) & (
        nxt[np.arange(len(first)), first] > prev[np.arange(len(first)), first])
    ordered = longer | (eq_len & lex_up)
    bad_c[owner[1:][same & ~ordered]] = True
    return n_bad + int(bad_c.sum())


# --------------------------------------------------------------------------- #
# MW maximum concurrent flow
# --------------------------------------------------------------------------- #


def _bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def mw_solve(pe, plen, owner, demand, n_slots: int, iters: int,
             precision: str = "f64") -> tuple[float, np.ndarray]:
    """(alpha, rates) of the MW recurrence on one path table (unit capacity).

    ``rates`` are the best iterate's per-path rates scaled by min(alpha, 1),
    which is what the program returns.
    """
    if precision == "f64":
        dt, q = np.float64, (lambda x: x)
    elif precision == "bf16":
        dt, q = np.float32, _bf16
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
        # bfloat16 scatter-add: every partial sum rounded, in index order
        import ml_dtypes

        acc = np.zeros(n, ml_dtypes.bfloat16)
        np.add.at(acc, idx, vals.astype(ml_dtypes.bfloat16))
        return acc.astype(np.float32)

    def loads_of(rates):
        return segment_sum(flat_slot, rates[flat_row], n_slots)

    def costs_of(prices):
        return segment_sum(flat_row, prices[flat_slot], len(pe))

    def seg_norm(x):
        return q(x / segment_sum(owner, x, K)[owner])

    x = seg_norm(np.ones(len(pe), dt))
    rel_prev = np.zeros(n_slots, dt)
    best_alpha, best_x = 0.0, x
    for t in range(iters):
        frac = 0.2 * (0.005 / 0.2) ** (t / iters)
        tau = max(float(rel_prev.max()), 1e-12) * frac
        z = q(rel_prev / dt(tau))
        e = q(np.exp(z - z.max()))
        w = q(e / q(np.asarray(e.sum(), dt)))
        rates = q(x * dem)
        loads = loads_of(rates)
        costs = costs_of(w)
        rel = loads
        alpha = 1.0 / max(float(rel.max()), 1e-12)
        if alpha > best_alpha:
            best_alpha, best_x = alpha, x
        g = q(costs * dem)
        g = q(g / max(float(g.max()), 1e-12))
        eta = 2.0 / np.sqrt(1.0 + t)
        x = seg_norm(q(x * q(np.exp(-eta * g))))
        rel_prev = rel
    alpha = 1.0 / max(float(loads_of(q(x * dem)).max()), 1e-12)
    if alpha > best_alpha:
        best_alpha, best_x = alpha, x
    rates = q(best_x * dem * dt(min(best_alpha, 1.0)))
    return float(best_alpha), np.asarray(rates, np.float64)


def certify(pe, plen, owner, demand, n_slots: int, rates,
            alpha: float) -> tuple[float, float]:
    """(cert_gap, overload) of one MW answer, in float64, unit capacity.

    ``overload`` is how far the most loaded slot sits past its capacity
    (0 when none does).  The answer claims every commodity ships
    min(alpha, 1) of its demand with the busiest slot at min(alpha, 1) /
    alpha; ``cert_gap`` is the relative distance between the claimed
    alpha and the concurrent-flow value its rates certify: the least
    shipped fraction over the busiest slot's load.
    """
    pe = np.asarray(pe, np.int64)
    plen = np.asarray(plen, np.int64)
    owner = np.asarray(owner, np.int64)
    rates = np.asarray(rates, np.float64)
    if len(rates) != len(pe) or not np.all(np.isfinite(rates)) or not (
            np.isfinite(alpha) and alpha > 0):
        return float("inf"), float("inf")
    real = np.arange(pe.shape[1])[None, :] < plen[:, None]
    loads = np.bincount(pe[real], weights=np.repeat(rates, plen),
                        minlength=n_slots)[:n_slots]
    mx = float(loads.max())
    ship = np.bincount(owner, weights=rates, minlength=len(demand))
    frac = float(np.min(ship / np.asarray(demand, np.float64)))
    if mx <= 0:
        return float("inf"), 0.0
    alpha_cert = frac / mx
    return abs(alpha_cert / alpha - 1.0), max(mx - 1.0, 0.0)
