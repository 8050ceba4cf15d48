"""Dependency-free stats helpers shared by the example orchestrators.

Deliberately imports nothing beyond the stdlib: the sweep parents
(osdi22ae/run_all.py, osdi22ae/ranker_fidelity.py) isolate
framework/jax failures in per-model subprocesses, and a chip belongs to
one process at a time, so the parent imports neither the framework nor
JAX.
"""
from __future__ import annotations


def spearman(xs, ys):
    """Spearman rank correlation without scipy (tie-averaged ranks).
    Single shared implementation — the osdi22ae sweep, the ranker
    fidelity A/B and the on-chip fidelity script must stay comparable."""
    def ranks(v):
        order = sorted(range(len(v)), key=lambda i: v[i])
        r = [0.0] * len(v)
        k = 0
        while k < len(order):
            j = k
            while j + 1 < len(order) and v[order[j + 1]] == v[order[k]]:
                j += 1
            avg = (k + j) / 2.0          # averaged rank for ties
            for t in order[k:j + 1]:
                r[t] = avg
            k = j + 1
        return r
    rx, ry = ranks(xs), ranks(ys)
    n = len(xs)
    mx, my = sum(rx) / n, sum(ry) / n
    num = sum((a - mx) * (b - my) for a, b in zip(rx, ry))
    dx = sum((a - mx) ** 2 for a in rx) ** 0.5
    dy = sum((b - my) ** 2 for b in ry) ** 0.5
    return num / (dx * dy) if dx > 0 and dy > 0 else 0.0
