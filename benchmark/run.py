#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process from the first JAX import to exit: the chip belongs to it.
A cell is ``benchmark/workloads/<cell>.json`` and names a configuration
(``benchmark/configs/<name>.json``) and a traffic mix
(``benchmark/traffic/<name>.json``). The configuration names the driver
(``benchmark/drivers/<name>.py``) that runs the system under test; the
traffic mix names the generator (``benchmark/generators/<name>.py``)
that turns its parameters and ``--seed`` into inputs. With ``--trace
1`` every reader in ``benchmark/layer_metrics/`` is asked for its
metric; a reader with nothing to read returns nothing. All of these are
found by name, and which cell reports which metric is read from
``BENCHMARK.json``: a later PR adds files and entries and edits no file.

The last line of stdout is the contract's JSON object. Everything else
goes to stderr or under ``.cache/benchmark/`` in the checkout.

It refuses any platform but ``tpu`` (exit 3, no result line) unless
``--rehearse`` is given: a rehearsal runs the tiny copy of the
configuration in ``benchmark/configs/rehearse/`` on the CPU and its
numbers are never written down.
"""

import time

_T_START = time.perf_counter()   # set-up is counted from here

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def log(*a) -> None:
    print("benchmark:", *a, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Run:
    """What a driver, a generator and a layer-metric reader are handed."""

    def __init__(self, args) -> None:
        self.args = args
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.rehearse = bool(args.rehearse)
        self.t_start = _T_START
        self.bench = BENCH
        self.root = ROOT
        self.cell = load_json(BENCH / "workloads" / f"{args.workload}.json")
        self.cell.setdefault("name", args.workload)
        cfg_dir = BENCH / "configs" / ("rehearse" if self.rehearse else "")
        self.config = load_json(cfg_dir / f"{self.cell['config']}.json")
        self.traffic = load_json(
            BENCH / "traffic" / f"{self.cell['traffic']}.json")
        self.benchmark = load_json(ROOT / "BENCHMARK.json")
        if self.rehearse:
            self.traffic.update(self.traffic.get("rehearse", {}))
        for item in args.set:
            key, _, value = item.partition("=")
            self.traffic[key] = json.loads(value)
        self.chips = int(self.cell["chips"])
        # everything a run writes: inside the checkout, fixed path
        self.cache = ROOT / ".cache" / "benchmark"
        self.out = self.cache / "run" / self.cell["name"]
        self.devices = None
        self.device = None
        self.clock = None

    @property
    def window_s(self) -> float:
        """Seconds of measured window: ``--seconds``; in a traced run
        the traffic mix's ``trace_seconds`` if that is shorter (the
        device's trace buffer holds only so much, lib/xplane.py)."""
        if self.trace:
            return min(self.seconds, float(
                self.traffic.get("trace_seconds", self.seconds)))
        return self.seconds

    def declared(self, kind: str) -> dict:
        """The metrics of one kind (``end_to_end`` | ``per_layer``)
        that ``BENCHMARK.json`` gives this cell: those with no
        ``workloads`` list, or with this cell in it. Which cell reports
        what is data there and nowhere in the code."""
        cell = self.cell["name"]
        return {m["name"]: m for m in self.benchmark[kind]
                if cell in m.get("workloads", [cell])}

    def fresh_dir(self, name: str) -> Path:
        d = self.out / name
        shutil.rmtree(d, ignore_errors=True)
        d.mkdir(parents=True, exist_ok=True)
        return d

    def generator(self):
        return importlib.import_module(
            f"generators.{self.traffic['generator']}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny configuration on the CPU; numbers from it "
                         "are never written down")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the profiler's files in place after "
                         "they were reduced (they are large)")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=JSON",
                    help="override one traffic parameter (for the sweep "
                         "that fixes a cell's rate; the driver never "
                         "passes it)")
    args = ap.parse_args()
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT))
    run = Run(args)
    if run.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append("--xla_force_host_platform_device_count="
                     f"{run.chips}")
        os.environ["XLA_FLAGS"] = " ".join(flags)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    from lib import compileclock, device

    devices = jax.devices()
    run.devices, run.device = devices, device.describe(devices)
    if run.device["platform"] != "tpu" and not run.rehearse:
        log(f"refusing to run: JAX landed on {run.device}; a cell is "
            "measured on the chip (--rehearse for a CPU rehearsal)")
        return 3
    if len(devices) < run.chips:
        log(f"refusing to run: the cell needs {run.chips} chips, JAX "
            f"sees {len(devices)}")
        return 3
    if not run.rehearse:
        device.peaks(run.device["kind"])     # unknown device: an error
    run.clock = compileclock.CompileClock()

    from spacemesh_tpu.utils import accel   # the system under test

    log(f"cell {run.cell['name']} seed {run.seed} seconds {run.seconds} "
        f"trace {int(run.trace)} device {run.device} compile_cache "
        f"{accel.enable_persistent_cache()}")
    driver = importlib.import_module(f"drivers.{run.config['driver']}")
    res = driver.run(run)

    metrics = {}
    breakdown = None
    dev = dict(run.device)
    dev["memory_peak_bytes"] = device.memory_peak_bytes(
        devices, res.get("program_bytes", 0))
    if run.trace:
        from lib import layers

        metrics, breakdown, busy = layers.read_all(run, res)
        dev["busy_s"] = busy["busy_s"]
        dev["window_s"] = busy["window_s"]
    else:
        declared = run.declared("end_to_end")
        for name, (value, unit) in res["end_to_end"].items():
            if name in declared and value is not None:
                metrics[name] = {"value": value, "unit": unit}
        if set(declared) - set(metrics):   # None: could not be measured
            log(f"not measured: {sorted(set(declared) - set(metrics))}")
    line = {"correct": bool(res["correct"]),
            "attempted": int(res["attempted"]),
            "failed": int(res["failed"]),
            "metrics": metrics, "device": dev}
    if breakdown is not None:
        line["breakdown"] = breakdown
    log("checks: " + json.dumps(res.get("checks", {}), default=str))
    log("setup: " + json.dumps(res.get("setup_parts", {})))
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
