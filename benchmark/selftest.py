#!/usr/bin/env python3
"""The benchmark's own tests. No chip needed.

    python3 benchmark/selftest.py              # seconds
    python3 benchmark/selftest.py --rehearse   # + CPU rehearsals, minutes

Checks, in order: the trace reduction on the small recorded trace
(``testdata/small_tpu_v5e.xplane.pb``, recorded on a v5e by
``tools/record_small_trace.py`` and cut down by ``tools/strip_trace.py``)
against numbers worked out by hand from its events; the arithmetic
helpers; the plain references against the program's own kernels and
``post/verifier.verify_many`` at the rehearsal size; that
``BENCHMARK.json`` and the files under ``benchmark/`` name each other
consistently. With ``--rehearse``: every cell end to end on the CPU
(the four-chip cell on four virtual devices, the load-generator child
included), and a throw-away cell made only of NEW files (configuration,
traffic mix, generator, per-layer metric, cell) and NEW entries in
``BENCHMARK.json`` in a scratch copy, two of which name the cell under
metrics that exist and are restricted to other cells, to show that no
file that exists has to be edited.
"""

import argparse
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def near(a, b, rel=1e-6):
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-12)


def test_trace_reduction():
    from lib import xplane

    red = xplane.reduce(
        str(BENCH / "testdata" / "small_tpu_v5e.xplane.pb"),
        span_names=("init.fetch", "init.write_stall"),
        window_span="bench.window")
    # the window is the bench.window annotation: 95,748,748 ns
    assert near(red.window_s, 0.095748748), red.window_s
    assert len(red.chips) == 1 and not red.dropped
    # nine programs ran: three label programs (1,183,346 + 1,182,752 +
    # 1,183,246 ns), three converts (594 + 593 + 595) and three adds
    # (591 + 590 + 593); none overlap, so busy is their sum
    assert near(red.busy_s, 3552900e-9, 1e-4), red.busy_s
    assert near(red.worst_idle_share, 1 - 3552900 / 95748748, 1e-4)
    # the first and the last execution of each program in the trace are
    # never counted among the durations (either could be a program cut
    # by the trace's edge): the middle label program remains, 1,182,752 ns
    assert len(red.program_durations("convert_element_type")) == 1
    labels = red.program_durations(r"labels_(min_)?fused")
    assert len(labels) == 1 and near(labels[0], 1182752e-9, 1e-4)
    assert red.top_programs(1)[0][0] == "jit__labels_fused"
    # gaps by what the host was doing at the gap's midpoint: the 22.36 ms
    # hole between the second and third label program sits under the
    # 20 ms sleep in init.write_stall; the 1.78 ms after the first
    # label program sits in the first init.fetch; the rest has no span
    g = red.gaps_by_span
    assert near(g["init.write_stall"], (133653978 - 111290052) * 1e-9,
                1e-3), g
    assert near(g["init.fetch"], (109810983 - 108032800) * 1e-9, 1e-3), g
    assert near(sum(g.values()) + red.busy_s, red.window_s, 1e-6)
    assert set(g) == {"init.write_stall", "init.fetch", "no-span"}
    assert red.top_gaps(1)[0][0] == "no-span"
    assert red.top_ops(3) and all(len(n) <= 96 for n, _ in red.top_ops(10))


def test_helpers():
    from lib import compileclock, device, shapes, stats, xplane

    assert stats.median([3, 1, 2]) == 2 and stats.percentile([], 50) is None
    assert near(stats.percentile(list(range(101)), 99), 99)
    assert stats.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert xplane.union_seconds([(0, 2e9), (1e9, 3e9)], 0, 4e9) == \
        (3.0, [(3e9, 4e9)])
    assert xplane.program_name("jit__labels_min_fused(123)") == \
        "jit__labels_min_fused"
    assert xplane.short_op(
        "%fusion.191 = u32[8192,32]{0,1:T(8,128)S(1)} fusion(u32[8192]{0} "
        "%x), kind=kCustom, calls=%f") == "%fusion.191 fusion kCustom"
    # 2*128*N bytes per label: 2 MiB at N=8192 (tools/profiler.py)
    assert shapes.romix_hbm_bytes(8192, 1) == 2 * 1024 * 1024
    assert shapes.romix_v_bytes(8192, 8192) == 8 << 30
    assert shapes.romix_salsa_cores(8192, 1) == 4 * 8192
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    try:
        device.peaks("TPU v9")
    except device.UnknownDevice:
        pass
    else:
        raise AssertionError("an unknown device must be an error")
    assert compileclock.is_eager_primitive("jit(concatenate)")
    assert not compileclock.is_eager_primitive("jit(_labels_fused)")


def test_cursor_rate():
    from drivers.init_pipeline import cursor_rate

    # one chip: every save leaves the cursor on a batch boundary, so
    # the pair is the first and the last advance
    b = 8192
    adv = [(5.0 * i, 2 * b * i) for i in range(1, 8)]
    rate, how = cursor_rate(adv, b)
    assert near(rate, 2 * b / 5.0) and how == {
        "from": 0, "to": 6, "same_offset": True}
    # four chips: a save catches 3 or 4 of the newest batch's 4 stripes.
    # First to last would read 7*2*b - b/4 labels here, 1.8% short
    b, stripe = 32768, 8192
    offs = [4, 3, 3, 4, 4, 3, 4, 3]
    adv = [(5.48 * i, 2 * b * i + o * stripe) for i, o in enumerate(offs)]
    rate, how = cursor_rate(adv, b)
    assert near(rate, 2 * b / 5.48), rate
    assert how == {"from": 0, "to": 6, "same_offset": True}, how
    assert not near((adv[-1][1] - adv[0][1]) / (adv[-1][0] - adv[0][0]),
                    rate, 1e-2)
    # no two advances at one offset: first to last, and said so
    rate, how = cursor_rate([(0.0, 0), (5.0, 3 * stripe)], b)
    assert near(rate, 3 * stripe / 5.0) and not how["same_offset"]
    assert cursor_rate([(1.0, 5)], b) == (None, {})
    assert cursor_rate([], b) == (None, {})


def test_reference_against_program():
    import hashlib

    import jax.numpy as jnp
    import numpy as np

    from lib import atxpool, reference
    from spacemesh_tpu.ops import scrypt
    from spacemesh_tpu.post import verifier
    from spacemesh_tpu.post.prover import Proof, ProofParams

    rng = np.random.default_rng(1)
    blk = rng.integers(0, 2**32, size=(16, 5), dtype=np.uint32)
    assert np.array_equal(np.asarray(scrypt.salsa20_8(jnp.asarray(blk))),
                          reference.salsa20_8(blk))
    com = hashlib.sha256(b"c").digest()
    got = scrypt.scrypt_labels(com, np.array([5, 2**32 + 9]), n=32)
    assert bytes(got[0]) == reference.label(com, 5, 32)
    assert bytes(got[1]) == reference.label(com, 2**32 + 9, 32)
    cfg = json.load(open(BENCH / "configs" / "rehearse"
                         / "verifyd-atx-v5e1.json"))
    pool, _ = atxpool.load_or_build(
        cfg, ROOT / ".cache" / "benchmark", lambda *a: None)
    diff = bytes.fromhex(cfg["pow_difficulty"])
    seed = b"selftest-seed"
    for k3 in (1, 5, 37):
        params = ProofParams(k1=cfg["k1"], k2=cfg["k2"], k3=k3,
                             pow_difficulty=diff)
        items, want = [], []
        for p in pool["proofs"][:6]:
            ident = pool["identities"][p["identity"]]
            for variant in range(4):
                idx = list(p["indices"])
                pow_nonce = p["pow_nonce"]
                ch = bytes.fromhex(p["challenge"])
                if variant == 1:
                    idx[p["swap_pos"]] = p["swap_index"]
                elif variant == 2:
                    idx[0] = pool["total_labels"] + 17
                elif variant == 3:
                    ch = hashlib.sha256(ch).digest()
                kw = dict(challenge=ch,
                          node_id=bytes.fromhex(ident["node_id"]),
                          commitment=bytes.fromhex(ident["commitment"]),
                          scrypt_n=cfg["scrypt_n"],
                          total_labels=pool["total_labels"])
                items.append(verifier.VerifyItem(
                    proof=Proof(p["nonce"], idx, pow_nonce, cfg["k2"]),
                    **kw))
                want.append(reference.verify_post(
                    indices=idx, nonce=p["nonce"], pow_nonce=pow_nonce,
                    k1=cfg["k1"], k2=cfg["k2"], k3=k3,
                    pow_difficulty=diff, seed=seed, **kw))
        got = verifier.verify_many(items, params, seed=seed)
        assert got == want, (k3, got, want)
        assert any(want) and not all(want)


def test_benchmark_json():
    doc = json.load(open(ROOT / "BENCHMARK.json"))
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert doc["paths"] == ["benchmark"]
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert "setup_s" in e2e
    configs = {c["name"]: c for c in doc["configs"]}
    for c in doc["configs"]:
        body = json.load(open(ROOT / c["file"]))
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in body and NAME.match(key), key
        assert (BENCH / "drivers" / f"{body['driver']}.py").exists()
        assert (BENCH / "configs" / "rehearse"
                / f"{c['name']}.json").exists()
    four = 0
    for w in doc["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200, w["name"]
        cell = json.load(open(BENCH / "workloads" / f"{w['name']}.json"))
        for key in ("config", "traffic", "chips", "why"):
            assert cell[key] == w[key], (w["name"], key)
        assert w["config"] in configs
        tr = json.load(open(BENCH / "traffic" / f"{w['traffic']}.json"))
        assert (BENCH / "generators" / f"{tr['generator']}.py").exists()
        four += w["chips"] == 4
    assert four <= len(doc["workloads"]) // 2
    readers = {p.stem for p in (BENCH / "layer_metrics").glob("[!_]*.py")}
    listed = {m["name"] for m in doc["per_layer"]}
    assert readers == listed, readers ^ listed
    import importlib

    cells = {w["name"] for w in doc["workloads"]}
    for m in doc["per_layer"]:
        mod = importlib.import_module(f"layer_metrics.{m['name']}")
        # a reader says what it reads, never where: cells are data
        assert set(mod.META) == {"layer", "unit", "source", "moves",
                                 "better"}, m["name"]
        for key in mod.META:
            assert mod.META[key] == m[key], (m["name"], key)
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        # reported only where the metric it moves is
        assert set(m.get("workloads", cells)) <= \
            set(e2e[m["moves"]].get("workloads", cells)), m["name"]
    for cell in cells:
        mine = [m["name"] for m in doc["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert "setup_s" in mine and len(mine) >= 2, (cell, mine)
        assert any(cell in m.get("workloads", cells)
                   for m in doc["per_layer"]), cell
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"]), m
    for m in doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def _run_cell(root: Path, cell: str, trace: int, seconds: int = 12):
    out = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         cell, "--seed", "5", "--seconds", str(seconds), "--trace",
         str(trace), "--rehearse"], capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    return line, out.stderr


def test_rehearse_cells():
    doc = json.load(open(ROOT / "BENCHMARK.json"))
    for w in doc["workloads"]:
        line, err = _run_cell(ROOT, w["name"], 0)
        assert line["device"]["count"] == w["chips"], line["device"]
        assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
        assert line["failed"] == 0, err[-2000:]
        print(f"    {w['name']}: correct={line['correct']} "
              f"metrics={sorted(line['metrics'])}")
        line, err = _run_cell(ROOT, w["name"], 1)
        assert line["metrics"], err[-2000:]
        print(f"    {w['name']} traced: {sorted(line['metrics'])}")


def test_throwaway_cell_needs_no_edit():
    """A new configuration, traffic mix, generator, per-layer metric
    and cell, as new files and new entries in ``BENCHMARK.json`` only,
    in a scratch copy of the checkout. The cell also asks for two
    metrics that exist and are listed for other cells only
    (``lat_p99_ms``, ``gen_late_ms``) by being named in their lists."""
    scratch = ROOT / ".cache" / "benchmark" / "selftest"
    shutil.rmtree(scratch, ignore_errors=True)
    shutil.copytree(BENCH, scratch / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "spacemesh_tpu", scratch / "spacemesh_tpu")
    b = scratch / "benchmark"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    cfg = json.load(open(b / "configs" / "rehearse"
                         / "verifyd-atx-v5e1.json"))
    cfg["name"] = "throwaway-config"
    for d in (b / "configs", b / "configs" / "rehearse"):
        json.dump(cfg, open(d / "throwaway-config.json", "w"))
    tr = json.load(open(b / "traffic" / "gossip-full.json"))
    tr.update(tr.pop("rehearse"))
    tr.update(name="throwaway-mix", generator="throwaway_gen",
              rate_atx_per_s=10.0)
    json.dump(tr, open(b / "traffic" / "throwaway-mix.json", "w"))
    (b / "generators" / "throwaway_gen.py").write_text(
        "from generators import atx_stream\n\n\n"
        "def generate(run, pool):\n"
        "    out = atx_stream.generate(run, pool)\n"
        "    out['throwaway'] = True\n"
        "    return out\n")
    meta = {"layer": "load generator", "unit": "requests",
            "source": "host_clock", "moves": "p50_ms", "better": "higher"}
    (b / "layer_metrics" / "throwaway_metric.py").write_text(
        f"META = {meta!r}\n\n\n"
        "def read(facts):\n"
        "    return facts.generator.get('requests')\n")
    cell = {"name": "throwaway.cell", "config": "throwaway-config",
            "traffic": "throwaway-mix", "chips": 1, "why": "selftest"}
    json.dump(cell, open(b / "workloads" / "throwaway.cell.json", "w"))
    doc = json.load(open(ROOT / "BENCHMARK.json"))
    doc["configs"].append({"name": cfg["name"], "source": cfg["source"],
                           "file": "benchmark/configs/throwaway-config.json",
                           "reduced": cfg["reduced"], "why": "selftest"})
    doc["workloads"].append(cell)
    doc["per_layer"].append(dict(meta, name="throwaway_metric",
                                 workloads=[cell["name"]]))
    for m in doc["end_to_end"] + doc["per_layer"]:
        if m["name"] in ("p50_ms", "lat_p99_ms", "gen_late_ms"):
            m["workloads"].append(cell["name"])
    json.dump(doc, open(scratch / "BENCHMARK.json", "w"))
    line, err = _run_cell(scratch, "throwaway.cell", 0, seconds=5)
    assert sorted(line["metrics"]) == ["p50_ms", "setup_s"], line
    line, err = _run_cell(scratch, "throwaway.cell", 1, seconds=5)
    assert line["metrics"]["throwaway_metric"]["value"] > 0, err[-2000:]
    for name in ("lat_p99_ms", "gen_late_ms"):
        assert line["metrics"][name]["value"] > 0, (name, err[-2000:])
    assert "vd_device_idle_share" not in line["metrics"]    # not its cell
    after = {p: p.read_bytes() for p in before}
    assert after == before, "an existing file changed"
    shutil.rmtree(scratch, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    tests = [test_trace_reduction, test_helpers, test_cursor_rate,
             test_benchmark_json, test_reference_against_program]
    if args.rehearse:
        tests += [test_rehearse_cells, test_throwaway_cell_needs_no_edit]
    failed = 0
    for t in tests:
        try:
            t()
            print(f"PASS {t.__name__}")
        except Exception as e:  # noqa: BLE001 - report every test
            failed += 1
            import traceback

            traceback.print_exc()
            print(f"FAIL {t.__name__}: {type(e).__name__}: {e}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
