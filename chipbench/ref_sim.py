"""Plain reference of the live-fabric simulation (ECMP, fail/heal events).

It replays one instance of a batched fluid flow-level simulation in
float64 with numpy, from the fabric, the traffic and the event schedule
the benchmark made, and nothing the program made.  The semantics are the
ones the configuration states:

* Routing: ECMP, the up to ``n_ways`` shortest paths of each switch pair
  in (length, lexicographic) order (``ref.k_shortest`` with slack 0).
* Arrivals: per step, the Poisson count, the commodity draw (weights
  proportional to demand) and the ECMP hash are those of the program's
  documented streams: ``fold_in(PRNGKey(seed), step)`` split three ways
  (count, commodity, size), drawn over the whole batch, replayed with
  ``jax.random`` (the library, not the program).  The flow id of arrival
  ``a`` is the instance's running id plus ``a``; a flow's path is row
  ``flow_hash(src, dst, id, salt) % group size`` of its commodity's group.
  At most ``max_arrivals`` arrivals a step; arrivals past the cap or past
  a full flow table of ``max_flows`` slots are dropped.
* Rates: progressive-filling max-min fair rates by path row, the global
  minimum fair share frozen each round (``wf_iters`` rounds), flows left
  unfrozen taking their bottleneck share; unit link capacity.
* Drain: each flow delivers min(remaining, rate * dt) a step; a flow with
  at most 1e-6 left completes and frees its slot.  The first step at
  which a flow is left with a remainder in (0, 1e-4) is the run's
  ``fragile_step``: from there on, float32 and float64 may disagree on
  when that flow completes, and the two runs part.
* Events (before the step's arrivals): ``fail_links`` removes
  ``rng.choice(E, n_links)`` of the current sorted edge list with
  ``rng = default_rng([event seed, instance])``; ``heal_links`` restores
  them.  A commodity keeps its paths unless a path crossed a removed link,
  its hop distance changed, or a path through an added link is no longer
  than its shortest; those are re-routed, and their flows re-hashed onto
  the new group.  A flow whose path lost a link holds for ``lag`` steps
  (detection and reconvergence): it takes no capacity, delivers nothing,
  and drains ``bh_rate`` a step into the blackhole.

``precision="bf16"`` rounds every stored fluid quantity (rates, loads,
remaining volumes, the per-commodity delivered ledger) to bfloat16: the
control.
"""

from __future__ import annotations

import numpy as np

from chipbench import ref

_M1, _M2, _PHI = 0x85EBCA6B, 0xC2B2AE35, 0x9E3779B9


def flow_hash(src, dst, fid, salt):
    """murmur3-fmix32 chain over (id ^ salt * phi, src, dst), uint32."""
    def fmix(h):
        h = h ^ (h >> np.uint32(16))
        h = h * np.uint32(_M1)
        h = h ^ (h >> np.uint32(13))
        h = h * np.uint32(_M2)
        return h ^ (h >> np.uint32(16))

    with np.errstate(over="ignore"):
        s = np.asarray(src).astype(np.uint32)
        d = np.asarray(dst).astype(np.uint32)
        f = np.asarray(fid).astype(np.uint32)
        h = fmix(f ^ (np.uint32(salt) * np.uint32(_PHI)))
        h = fmix(h ^ (s * np.uint32(_M1)))
        return fmix(h ^ (d * np.uint32(_M2)))


def arrival_streams(seed: int, rate: np.ndarray, n_batch: int, n_arrivals: int,
                    logits: np.ndarray):
    """(T, B) Poisson counts and (T, B, A) commodity draws of a run."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)

    @jax.jit
    def draw(t, r, lg):
        k_n, k_c, _ = jax.random.split(jax.random.fold_in(key, t), 3)
        n = jax.random.poisson(k_n, r, (n_batch,)).astype(jnp.int32)
        c = jax.random.categorical(k_c, lg[:, None, :], axis=-1,
                                   shape=(n_batch, n_arrivals))
        return n, c

    lg = jnp.asarray(logits)
    out = [draw(jnp.int32(t), jnp.float32(rate[t]), lg) for t in range(len(rate))]
    return (np.stack([np.asarray(n) for n, _ in out]),
            np.stack([np.asarray(c) for _, c in out]))


class _Table:
    """ECMP path table of one fabric: rows grouped by commodity."""

    def __init__(self, n, edges, src, dst, n_ways):
        self.edges = np.asarray(edges)
        self.dist = ref.bfs_hops(n, self.edges)
        nbrs = ref.neighbour_lists(n, self.edges)
        E = len(self.edges)
        sid = {(int(u), int(v)): i for i, (u, v) in enumerate(self.edges)}
        self.paths = [ref.k_shortest(nbrs, self.dist, int(s), int(t), n_ways, 0)
                      for s, t in zip(src, dst)]
        rows = [[sid[(a, b)] if a < b else sid[(b, a)] + E
                 for a, b in zip(p[:-1], p[1:])]
                for ps in self.paths for p in ps]
        self.n_slots = 2 * E
        L = max(map(len, rows))
        self.pe = np.full((len(rows), L), self.n_slots, np.int64)
        for i, r in enumerate(rows):
            self.pe[i, : len(r)] = r
        self.cnt = np.array([len(p) for p in self.paths])
        self.first = np.concatenate([[0], np.cumsum(self.cnt)[:-1]])
        self.owner = np.repeat(np.arange(len(self.paths)), self.cnt)
        flat = self.pe.ravel()
        real = flat < self.n_slots
        self._slot = flat[real]
        self._row = np.repeat(np.arange(len(rows)), self.pe.shape[1])[real]

    def loads(self, per_row):
        return np.bincount(self._slot, weights=per_row[self._row],
                           minlength=self.n_slots)


def waterfill(tab: _Table, nflow: np.ndarray, iters: int, q=lambda x: x):
    """Per-row max-min rates (fast rule) and the slot loads they put."""
    present = nflow > 1e-6
    fixed = np.zeros_like(nflow)
    rate = np.zeros_like(nflow)

    def share_limit(fixed, rate):
        load_fixed = q(tab.loads(q(rate * nflow * fixed)))
        cnt = q(tab.loads(q(nflow * (1.0 - fixed))))
        avail = np.maximum(1.0 - load_fixed, 0.0)
        with np.errstate(divide="ignore", invalid="ignore"):
            share = q(np.where(cnt > 1e-6, avail / np.maximum(cnt, 1e-9),
                               np.inf))
        limit = np.minimum(np.append(share, np.inf)[tab.pe].min(axis=1), 1e6)
        return share, limit, cnt > 1e-6

    for _ in range(iters):
        share, limit, binding = share_limit(fixed, rate)
        unfixed = present & (fixed < 0.5)
        theta = min(float(share[binding].min()) if binding.any() else np.inf,
                    1e6)
        newly = unfixed & (limit <= theta * (1.0 + 1e-6))
        rate = np.where(newly, limit, rate)
        fixed = np.where(newly, 1.0, fixed)
    _, limit, _ = share_limit(fixed, rate)
    rate = np.where(present, np.where(fixed > 0.5, rate, limit), 0.0)
    return rate, q(tab.loads(q(rate * nflow)))


def simulate(n, edges, src, dst, demand, instance: int, n_batch: int,
             k_pad: int, schedule, steps: int, rate: float, size: float,
             cfg: dict, seed: int, precision: str = "f64") -> dict:
    """One instance's totals and per-step delivered volume (see module doc)."""
    q = (lambda x: x) if precision == "f64" else ref._bf16
    rate_t = np.full(steps, rate, np.float32)
    dem32 = np.asarray(demand, np.float32)
    logits = np.full((n_batch, k_pad), -np.inf, np.float32)
    logits[:, : len(dem32)] = np.log(np.maximum(dem32, np.float32(1e-30)))
    npois, comm = arrival_streams(seed, rate_t, n_batch, cfg["max_arrivals"],
                                  logits)
    npois, comm = npois[:, instance], comm[:, instance]
    n_ways, F, A = cfg["ecmp_ways"], cfg["max_flows"], cfg["max_arrivals"]
    salt = cfg["hash_salt"]
    edges = np.asarray(edges)
    tab = _Table(n, edges, src, dst, n_ways)
    failed: dict = {}
    row = np.zeros(0, np.int64)
    rem = np.zeros(0)
    age = np.zeros(0)
    fid = np.zeros(0, np.uint32)
    hold = np.zeros(0, np.int64)
    next_id = np.uint32(instance << 20)
    K = len(src)
    offered, delivered = np.zeros(K), np.zeros(K)
    thr = np.zeros(steps)
    admitted = drops = fct_cnt = 0
    fct_sum = blackholed = 0.0
    lag, bh_rate = cfg["lag"], cfg["bh_rate"]
    fragile = None
    for t in range(steps):
        for ev in schedule:
            if ev["step"] != t:
                continue
            old = tab
            if ev["kind"] == "fail_links":
                rng = np.random.default_rng([int(ev["seed"]), int(instance)])
                drop = rng.choice(len(edges), size=int(ev["n_links"]),
                                  replace=False)
                keep = np.ones(len(edges), bool)
                keep[drop] = False
                failed[ev["tag"]] = edges[~keep]
                removed, added = edges[~keep], np.zeros((0, 2), np.int64)
                edges = edges[keep]
            else:
                added = failed.pop(ev["heal_of"])
                removed = np.zeros((0, 2), np.int64)
                edges = np.concatenate([edges, added])
                edges = edges[np.lexsort((edges[:, 1], edges[:, 0]))]
            tab = _Table(n, edges, src, dst, n_ways)
            keep_c = _unchanged(old, tab, src, dst, removed, added)
            row, hold = _migrate(old, tab, row, fid, hold, keep_c, src, dst,
                                 salt, removed, lag)
        # arrivals
        n_new = min(int(npois[t]), A)
        drops += int(npois[t]) - n_new
        c = comm[t, :n_new].astype(np.int64)
        with np.errstate(over="ignore"):
            f_new = (next_id + np.arange(A, dtype=np.uint32))[:n_new]
            next_id = np.uint32(next_id + np.uint32(n_new))
        live = tab.cnt[c] > 0
        c, f_new = c[live], f_new[live]
        place = min(len(c), F - len(row))
        drops += len(c) - place
        c, f_new = c[:place], f_new[:place]
        j = (flow_hash(src[c], dst[c], f_new, salt).astype(np.int64)
             % tab.cnt[c])
        row = np.concatenate([row, tab.first[c] + j])
        rem = np.concatenate([rem, np.full(place, size)])
        age = np.concatenate([age, np.zeros(place)])
        fid = np.concatenate([fid, f_new])
        hold = np.concatenate([hold, np.zeros(place, np.int64)])
        admitted += place
        np.add.at(offered, c, size)
        # rates, drain, completions
        held = hold > 0
        nflow = np.bincount(row[~held], minlength=len(tab.pe)).astype(
            np.float64)
        r_row, _ = waterfill(tab, nflow, cfg["wf_iters"], q)
        got = q(np.where(held, 0.0, np.minimum(rem, r_row[row])))
        bh = q(np.where(held, np.minimum(rem, bh_rate), 0.0))
        rem = q(rem - got - bh)
        age = age + 1.0
        np.add.at(delivered, tab.owner[row], got)
        delivered = q(delivered)
        thr[t] = got.sum()
        blackholed = float(q(np.float64(blackholed + bh.sum())))
        fin = rem <= 1e-6
        if fragile is None and np.any((rem > 0) & (rem < 1e-4)):
            fragile = t
        done = fin & ~held
        fct_cnt += int(done.sum())
        fct_sum += float(age[done].sum())
        hold = np.maximum(hold - 1, 0)
        keep = ~fin
        row, rem, age, fid, hold = (row[keep], rem[keep], age[keep], fid[keep],
                                    hold[keep])
    return {"offered": float(offered.sum()), "delivered": float(delivered.sum()),
            "offered_by_commodity": offered,
            "delivered_by_commodity": delivered,
            "inflight": float(rem.sum()), "blackholed": blackholed,
            "admitted": admitted, "drops": drops,
            "fct_count": fct_cnt, "fct_sum": fct_sum, "throughput": thr,
            "fragile_step": steps if fragile is None else fragile}


def _unchanged(old: _Table, new: _Table, src, dst, removed, added) -> np.ndarray:
    """Commodities whose path group carries over a topology delta."""
    d_old = old.dist[src, dst]
    d_new = new.dist[src, dst]
    keep = d_old == d_new
    if len(removed):
        E = len(old.edges)
        rid = np.flatnonzero((old.edges[:, None, :] == removed[None]).all(-1)
                             .any(1))
        hit = np.isin(old.pe % E, rid) & (old.pe < old.n_slots)
        broken = np.bincount(old.owner, weights=hit.any(1),
                             minlength=len(src)) > 0
        keep &= ~broken
    if len(added):
        a, b = added[:, 0], added[:, 1]
        via = np.minimum(new.dist[np.ix_(src, a)] + new.dist[np.ix_(dst, b)],
                         new.dist[np.ix_(src, b)] + new.dist[np.ix_(dst, a)]
                         ).min(axis=1) + 1
        keep &= via > d_new
    return keep


def _migrate(old: _Table, new: _Table, row, fid, hold, keep_c, src, dst,
             salt, removed, lag):
    """Flows of carried-over commodities keep their path; the rest re-hash,
    and hold for ``lag`` steps where their old path lost a link."""
    c = old.owner[row]
    j = row - old.first[c]
    moved = ~keep_c[c]
    h = flow_hash(src[c[moved]], dst[c[moved]], fid[moved], salt)
    j = j.copy()
    j[moved] = h.astype(np.int64) % new.cnt[c[moved]]
    hold = hold.copy()
    if len(removed):
        E = len(old.edges)
        rid = np.flatnonzero((old.edges[:, None, :] == removed[None]).all(-1)
                             .any(1))
        pe = old.pe[row]
        dead = (np.isin(pe % E, rid) & (pe < old.n_slots)).any(axis=1)
        hold[moved & dead] = lag
    return new.first[c] + j, hold
