"""Whether what the timed path produced is correct.

Every simulation a request of the window completed is an answer: a
makespan, the bytes moved between workers and an ``ok`` flag, for one
(cluster, graph, grid point).  Once the window has closed, a sample of
the distinct answers, drawn from ``--seed`` and holding the one that ran
the most loop steps, is computed again by the plain reference
(``reference/``) on the unpadded cluster.  The numbers compared:

* ``gap_worst``: the widest relative gap of an answer in the sample, the
  larger of its makespan's and its transferred bytes' (those over
  ``max(reference, 1 B)``);
* ``failed``: answers of the window that are not ``ok`` (a simulation that
  overflowed its bounded state or ran out of loop steps);
* ``inconsistent``: (cluster, graph, point) triples that two requests of
  the window answered differently, since the program is deterministic.

Each limit sits in the cell's traffic file, set from the readings listed
in ``PERF.md``.
"""
from __future__ import annotations

import math
import sys

import numpy as np

from . import reference


def collect(results, points_of):
    """Distinct answers of the window: ``{(k, b, p): (makespan,
    transferred, ok, n_steps)}``, with the count of answers that differ
    from an earlier answer to the same triple and the count not ok."""
    answers, inconsistent, failed = {}, 0, 0
    for res, pts in zip(results, points_of, strict=True):
        K, B, N = res.makespan.shape
        failed += int((~res.ok).sum())
        for k in range(K):
            for b in range(B):
                for n, p in enumerate(pts):
                    a = (float(res.makespan[k, b, n]),
                         float(res.transferred[k, b, n]),
                         bool(res.ok[k, b, n]), int(res.n_steps[k, b, n]))
                    old = answers.setdefault((k, b, p), a)
                    if old[:3] != a[:3] and not (
                            math.isnan(old[0]) and math.isnan(a[0])):
                        inconsistent += 1
    return answers, inconsistent, failed


def sample(answers, size: int, seed: int):
    """``size`` distinct keys drawn from ``seed``, the one with the most
    loop steps first."""
    keys = sorted(answers)
    longest = max(keys, key=lambda key: answers[key][3])
    rest = [key for key in keys if key != longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(size - 1, len(rest)),
                      replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def gap(answer, ref) -> float:
    """Relative gap of one answer to the reference's ``(makespan,
    transferred)``; an answer that is not ok counts as a gap of 1."""
    makespan, transferred, ok = answer[:3]
    if not ok or not (math.isfinite(makespan) and math.isfinite(transferred)):
        return 1.0
    return max(abs(makespan - ref[0]) / ref[0],
               abs(transferred - ref[1]) / max(abs(ref[1]), 1.0))


def reference_answers(keys, graphs, config, traffic, points):
    """The reference's ``(makespan, transferred)`` for each key."""
    return {(k, b, p): reference.simulate(
                graphs[b], config["clusters"][k], traffic["scheduler"],
                traffic["netmodel"], points[p])
            for k, b, p in keys}


def compare(answers, refs, inconsistent: int, failed: int) -> dict:
    gaps = [gap(answers[key], refs[key]) for key in refs]
    return {"gap_worst": float(max(gaps)), "failed": failed,
            "inconsistent": inconsistent, "sampled": len(gaps)}


def limits(traffic) -> dict:
    """The compared numbers and their limits: ``gap_worst`` at the limit
    the cell's traffic file states, ``failed`` and ``inconsistent`` at
    0."""
    return {"gap_worst": traffic["check"]["gap_worst_limit"], "failed": 0,
            "inconsistent": 0}


def verdict(numbers: dict, lims: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` over the compared
    numbers."""
    compared = {name: {"value": numbers[name], "limit": lim}
                for name, lim in lims.items()}
    return all(c["value"] <= c["limit"] for c in compared.values()), compared


def print_compared(compared) -> None:
    for name, c in compared.items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
