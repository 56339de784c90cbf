#!/usr/bin/env python3
"""How often does a window's ``p50_ms`` land on the two-pass mode in
``prove-mainnet.scan``, and how often would the driver's check then
refuse the cell for noise? Arithmetic, not a measurement: the origin of
the figures PERF.md quotes (section 6, PR 27).

    python3 benchmark/tools/median_of_passes.py [--pass-s 2.85]
        [--window 40] [--level-sd 0.01] [--jitter-sd 0.03] [--midpoint]

A nonce wins when at least K2 of the store's labels fall under the
threshold: hits are Binomial(total, K1/total), Poisson(K1) to four
digits at any store of 2^20 labels or more. A pass decides 64 nonces,
so a proof needs another pass with q = P(no winner among 64), and the
number of passes is geometric. A window holds the proofs that end
inside it, back to back; a run's one-pass proof takes ``--pass-s``
times a level drawn once a run (``--level-sd``: the host's speed in
that process) times a jitter drawn per proof. The defaults are what the
chip runs of PR 27's third round showed: 2.85 s, 1%, 3%.

The driver's rule, as BENCHMARK_REFUSED.md stated it for PR 27: two sets
of six runs; a set's spread is the distance between the quartiles
(``statistics.quantiles``), of the six runs or of the five left when the
run farthest from the median is dropped, whichever is narrower; the mean
of the two spreads may be at most half the metric's bound.
"""
import argparse
import math
import random
import statistics


def q_more_passes(k1: int, k2: int, nonces: int) -> float:
    p_win = 1.0 - sum(math.exp(-k1) * k1 ** h / math.factorial(h)
                      for h in range(k2))
    return (1.0 - p_win) ** nonces


def window_median(rng, q, pass_s, window_s, level_sd, jitter_sd, midpoint):
    level = pass_s * rng.gauss(1.0, level_sd)
    t, lat = 0.0, []
    while True:
        passes = 1
        while rng.random() < q:
            passes += 1
        one = level * passes * rng.gauss(1.0, jitter_sd)
        if t + one > window_s:
            break
        t += one
        lat.append(one)
    if not lat:
        return None
    if midpoint:
        return statistics.median(lat)
    return sorted(lat)[(len(lat) - 1) // 2]     # the driver's median_request


def set_spread(six):
    med = statistics.median(six)
    far = max(range(len(six)), key=lambda i: abs(six[i] - med))
    spreads = []
    for runs in (six, six[:far] + six[far + 1:]):
        q1, _q2, q3 = statistics.quantiles(runs, n=4)
        spreads.append(q3 - q1)
    return min(spreads) / med


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pass-s", type=float, default=2.85)
    ap.add_argument("--window", type=float, default=40.0)
    ap.add_argument("--k1", type=int, default=26)
    ap.add_argument("--k2", type=int, default=37)
    ap.add_argument("--nonces", type=int, default=64)
    ap.add_argument("--bound", type=float, default=0.12)
    ap.add_argument("--level-sd", type=float, default=0.01)
    ap.add_argument("--jitter-sd", type=float, default=0.03)
    ap.add_argument("--midpoint", action="store_true",
                    help="the interpolated median, not the lower one")
    ap.add_argument("--trials", type=int, default=20000,
                    help="checks simulated (twelve windows each)")
    a = ap.parse_args()
    q = q_more_passes(a.k1, a.k2, a.nonces)
    rng = random.Random(27)
    windows = off = empty = refused = 0
    for _ in range(a.trials):
        sets = [[window_median(rng, q, a.pass_s, a.window, a.level_sd,
                               a.jitter_sd, a.midpoint) for _ in range(6)]
                for _ in range(2)]
        meds = [m for s in sets for m in s]
        windows += len(meds)
        empty += sum(m is None for m in meds)
        if None in meds:
            continue
        off += sum(m > 1.25 * a.pass_s for m in meds)
        refused += (set_spread(sets[0]) + set_spread(sets[1])) / 2 \
            > a.bound / 2
    print(f"P(a proof needs more than one pass) = {q:.4f}")
    print(f"pass {a.pass_s} s, window {a.window} s: "
          f"proofs a window about {a.window / (a.pass_s / (1 - q)):.1f}; "
          f"windows with none {empty}")
    print(f"P(window median off the one-pass mode by 25% or more) "
          f"= {off / windows:.4f}")
    print(f"P(a check of two sets of six refuses the cell for noise) = "
          f"{refused / a.trials:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
