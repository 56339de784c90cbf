#!/usr/bin/env python3
"""Measure a cell as the driver does: sets of runs, each run a new
process with another ``--seed``, and for each end-to-end metric the
spread of a set (distance between the quartiles over the median).

    python3 benchmark/tools/measure.py --workload <cell> [--sets 2]
        [--runs 6] [--seconds <run_seconds>] [--seed0 100] [--out DIR]

Never imports JAX itself (a parent that touched JAX would hold the
chip). Writes one JSON summary per cell under ``--out`` (default
``chiprun_out/``) and prints it.
"""
import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmark"))
from lib import stats  # noqa: E402


def spread(values):
    med = stats.median(values)
    return (stats.percentile(values, 75) - stats.percentile(values, 25)) \
        / med if med else None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=int, default=json.load(
        open(ROOT / "BENCHMARK.json"))["run_seconds"])
    ap.add_argument("--seed0", type=int, default=100)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out"))
    ap.add_argument("--set", action="append", default=[],
                    help="passed through to run.py (sweeps only)")
    ap.add_argument("--tag", default="", help="suffix of the summary file")
    a = ap.parse_args()
    os.makedirs(a.out, exist_ok=True)
    sets, lines, seed = [], [], a.seed0
    for s in range(a.sets):
        rows = []
        for _ in range(a.runs):
            seed += 1
            p = subprocess.run(
                [sys.executable, str(ROOT / "benchmark" / "run.py"),
                 "--workload", a.workload, "--seed", str(seed),
                 "--seconds", str(a.seconds), "--trace", "0"]
                + [x for item in a.set for x in ("--set", item)],
                capture_output=True, text=True)
            tail = p.stderr.strip().splitlines()[-2:]
            if p.returncode != 0:
                print(f"run seed {seed} exited {p.returncode}:\n"
                      + p.stderr[-2000:], file=sys.stderr)
                return 1
            line = json.loads(p.stdout.strip().splitlines()[-1])
            line["seed"], line["set"] = seed, s
            lines.append(line)
            rows.append(line)
            print(f"set {s} seed {seed}: correct={line['correct']} "
                  f"failed={line['failed']}/{line['attempted']} "
                  + " ".join(f"{k}={v['value']:.6g}"
                             for k, v in line["metrics"].items()),
                  flush=True)
            if not line["correct"]:
                print("\n".join(tail), file=sys.stderr)
        sets.append(rows)
    summary = {"workload": a.workload, "seconds": a.seconds, "metrics": {}}
    for name in lines[0]["metrics"]:
        per_set = []
        for rows in sets:
            vals = [r["metrics"][name]["value"] for r in rows
                    if name in r["metrics"]]
            per_set.append({"median": stats.median(vals),
                            "spread": spread(vals), "values": vals})
        summary["metrics"][name] = {
            "sets": per_set,
            "widest_spread": max(s["spread"] for s in per_set),
            "second_vs_first": (per_set[-1]["median"] / per_set[0]["median"]
                                - 1 if len(per_set) > 1 else None)}
    summary["all_correct"] = all(r["correct"] for r in lines)
    summary["failed"] = sum(r["failed"] for r in lines)
    summary["attempted"] = sum(r["attempted"] for r in lines)
    summary["device"] = lines[0]["device"]
    with open(os.path.join(a.out, f"measure_{a.workload}{a.tag}.json"), "w") as f:
        json.dump({"summary": summary, "runs": lines}, f, indent=1)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
