"""Seeded input generators of the benchmark (its own copies, not the program's).

Everything a cell feeds the program is made here from ``--seed``:

* ``rrg_edges`` — a random regular graph RRG(N, r) by the configuration
  model (random stub matching) with double-edge-swap repair of self-loops
  and parallel edges: the uniform random regular graph Jellyfish builds
  (Singla et al., NSDI 2012, §3).  Vectorised, so a fresh 2048-switch
  fabric costs well under a second of host time.
* ``permutation_pairs`` — a uniform random server permutation with fixed
  points removed, aggregated to switch-pair commodities (paper §4's random
  permutation traffic).

Seeds are tuples fed to ``numpy.random.SeedSequence``, so a seed of any
size (beyond 32 bits too) and a per-unit index give
independent, reproducible streams.
"""

from __future__ import annotations

import numpy as np


def rng_for(*key) -> np.random.Generator:
    """Generator for a seed tuple of non-negative ints and short strings."""
    words = []
    for k in key:
        if isinstance(k, str):
            words.append(int.from_bytes(k.encode()[:8], "little"))
        else:
            words.append(int(k))
    return np.random.default_rng(np.random.SeedSequence(words))


def rrg_edges(n: int, r: int, rng: np.random.Generator) -> np.ndarray:
    """(n * r / 2, 2) int64 edges (u < v, sorted) of a simple r-regular graph."""
    if (n * r) % 2 or r >= n:
        raise ValueError(f"no simple {r}-regular graph on {n} nodes")
    stubs = np.repeat(np.arange(n, dtype=np.int64), r)
    rng.shuffle(stubs)
    e = np.sort(stubs.reshape(-1, 2), axis=1)
    for _ in range(10_000):
        key = e[:, 0] * n + e[:, 1]
        _, first = np.unique(key, return_index=True)
        bad = np.ones(len(e), dtype=bool)
        bad[first] = False  # later copies of a parallel edge are bad
        bad |= e[:, 0] == e[:, 1]
        bi = np.flatnonzero(bad)
        if not len(bi):
            order = np.lexsort((e[:, 1], e[:, 0]))
            return e[order]
        good = np.flatnonzero(~bad)
        pi = rng.choice(good, size=len(bi), replace=False)
        flip = rng.random(len(bi)) < 0.5
        a, b = e[bi, 0], e[bi, 1]
        x = np.where(flip, e[pi, 1], e[pi, 0])
        y = np.where(flip, e[pi, 0], e[pi, 1])
        e[bi] = np.sort(np.stack([a, x], 1), axis=1)
        e[pi] = np.sort(np.stack([b, y], 1), axis=1)
    raise RuntimeError("RRG repair did not converge")


def permutation_pairs(
    n_switches: int, servers_per_switch: int, rng: np.random.Generator
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """(src, dst, demand, n_servers) of random permutation traffic.

    Every server sends at line rate to one other server; server pairs on
    one switch never reach the network and are dropped; the rest are summed
    per (src switch, dst switch), sorted by that pair.
    """
    n_srv = n_switches * servers_per_switch
    perm = rng.permutation(n_srv)
    fixed = np.flatnonzero(perm == np.arange(n_srv))
    if len(fixed) == 1:
        other = (fixed[0] + 1) % n_srv
        perm[fixed[0]], perm[other] = perm[other], perm[fixed[0]]
    elif len(fixed) > 1:
        perm[fixed] = perm[np.roll(fixed, 1)]
    host = np.repeat(np.arange(n_switches, dtype=np.int64), servers_per_switch)
    s, d = host, host[perm]
    cross = s != d
    uniq, counts = np.unique(s[cross] * n_switches + d[cross],
                             return_counts=True)
    return (uniq // n_switches, uniq % n_switches,
            counts.astype(np.float64), n_srv)
