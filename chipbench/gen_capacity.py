"""Seeded inputs of the capacity cells (the benchmark's own, not the program's).

A same-equipment Jellyfish (Singla et al., NSDI 2012, §4.1, Fig 1c) puts
m servers on n identical switches, so the switches' network degrees differ
by one; ``gen.rrg_edges`` makes only regular graphs.  Here:

* ``spread`` — servers per switch: ``m // n`` each, one more on the lowest
  ids (the search's own placement).
* ``degree_sequence_edges`` — a simple random graph with a given degree
  sequence by the configuration model (random stub matching) with
  double-edge-swap repair of self-loops and parallel edges; an odd stub
  total leaves one port unmatched.
* ``permutation_pairs`` — a uniform random server permutation with fixed
  points removed, aggregated to switch-pair commodities, over any number of
  servers per switch.
"""

from __future__ import annotations

import numpy as np


def spread(total: int, n_switches: int) -> np.ndarray:
    """(n,) int64 servers per switch."""
    servers = np.full(n_switches, total // n_switches, dtype=np.int64)
    servers[: total % n_switches] += 1
    return servers


def degree_sequence_edges(deg: np.ndarray,
                          rng: np.random.Generator) -> np.ndarray:
    """(E, 2) int64 edges (u < v, sorted) of a simple graph in which node i
    has degree ``deg[i]``, or one less on one node when the total is odd."""
    deg = np.asarray(deg, dtype=np.int64)
    n = len(deg)
    if deg.min() < 0 or deg.max() >= n:
        raise ValueError("no simple graph with these degrees")
    stubs = np.repeat(np.arange(n, dtype=np.int64), deg)
    rng.shuffle(stubs)
    stubs = stubs[: len(stubs) // 2 * 2]
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
    raise RuntimeError("degree-sequence repair did not converge")


def permutation_pairs(
    servers: np.ndarray, rng: np.random.Generator
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """(src, dst, demand, n_servers) of random permutation traffic.

    Switch i hosts ``servers[i]`` servers.  Every server sends at line rate
    to one other server; server pairs on one switch never reach the network
    and are dropped; the rest are summed per (src switch, dst switch),
    sorted by that pair.
    """
    n_switches = len(servers)
    host = np.repeat(np.arange(n_switches, dtype=np.int64), servers)
    n_srv = len(host)
    perm = rng.permutation(n_srv)
    fixed = np.flatnonzero(perm == np.arange(n_srv))
    if len(fixed) == 1:
        other = (fixed[0] + 1) % n_srv
        perm[fixed[0]], perm[other] = perm[other], perm[fixed[0]]
    elif len(fixed) > 1:
        perm[fixed] = perm[np.roll(fixed, 1)]
    s, d = host, host[perm]
    cross = s != d
    uniq, counts = np.unique(s[cross] * n_switches + d[cross],
                             return_counts=True)
    return (uniq // n_switches, uniq % n_switches,
            counts.astype(np.float64), n_srv)
