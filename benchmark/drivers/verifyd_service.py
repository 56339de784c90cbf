"""One VerifydServer with its defaults on the chip, clients over HTTP
on loopback.

The system under test is ``verifyd``: ``VerifydServer(post_params=...,
post_seed=...)`` and nothing else configured (tuner on, ``max_batch``
256, default rates and quotas, four scheduler workers). The server
runs on this process's event loop; the load generator is a child
process that never imports JAX (``lib/loadgen.py``).

Set-up: the pool of real proofs (built once per checkout, then loaded),
this run's requests from ``--seed``, the server's start (its tuner
races once per checkout), a warm-up of every label/proving-hash/k2pow
shape this cell's traffic can reach, called through ``post/verifier``
and ``ops/pow`` directly, then ``warm_s`` seconds of the cell's own
traffic through HTTP. The window starts after that, at a moment fixed
in advance and handed to the child.
"""

from __future__ import annotations

import asyncio
import inspect
import json
import random
import sys
import time
from concurrent.futures import ThreadPoolExecutor

from generators import atx_stream
from lib import atxpool, reference, shapes, stats, tracewin

GAP_SPANS = ("verifyd.request", "verifyd.drain", "farm.request",
             "farm.batch", "romix.dispatch", "pow_verify.dispatch",
             "pow_verify.retire")
REFERENCE_ATXS = 16
CHILD_START_S = 1.5     # the child needs ~0.5 s to import aiohttp and register


def log(*a) -> None:
    print("benchmark:", *a, file=sys.stderr, flush=True)


def _device_counts(gen: dict, concurrent: int) -> list:
    """Every number of device-checked proofs one farm batch can hold.

    The POST items of one request enter the farm in one loop turn, so a
    batch holds whole requests: up to ``concurrent`` of them. A request
    contributes its ATXs that are not rejected on the host (known by
    construction). With d_min..d_max such ATXs per request, m requests
    give every count in [m*d_min, m*d_max]."""
    per_req = [sum(1 for f in r["atx"] if f["on_device"])
               for r in gen["requests"]]
    lo, hi = min(per_req), max(per_req)
    counts = set()
    for m in range(1, concurrent + 1):
        counts.update(range(m * lo, m * hi + 1))
    counts.discard(0)
    return sorted(counts)


def _warm_shapes(cfg, pool, params, k3, gen, concurrent, setup) -> None:
    """Run once, outside the server, every device shape the cell's
    traffic can reach (same process, so the server finds them compiled):
    a label recompute + proving hash for every reachable count of
    device-checked proofs (the lane bucket's program, and the eager
    pad/trim ops that compile per count, lib/compileclock.py), and a
    k2pow batch for every power-of-two bucket of witnesses."""
    from spacemesh_tpu.ops import pow as k2pow
    from spacemesh_tpu.ops import scrypt
    from spacemesh_tpu.post import verifier
    from spacemesh_tpu.post.prover import Proof

    t0 = time.perf_counter()
    total = int(pool["total_labels"])
    counts = _device_counts(gen, concurrent)
    if max(counts) > len(pool["proofs"]):
        raise RuntimeError("the pool is smaller than one farm batch")
    items = []
    for p in pool["proofs"][:max(counts)]:
        ident = pool["identities"][p["identity"]]
        items.append(verifier.VerifyItem(
            proof=Proof(nonce=p["nonce"], indices=list(p["indices"]),
                        pow_nonce=p["pow_nonce"], k2=int(cfg["k2"])),
            challenge=bytes.fromhex(p["challenge"]),
            node_id=bytes.fromhex(ident["node_id"]),
            commitment=bytes.fromhex(ident["commitment"]),
            scrypt_n=int(cfg["scrypt_n"]), total_labels=total))
    for m in counts:
        got = verifier.verify_many(items[:m], params, seed=b"warm-up")
        if not all(got):
            raise RuntimeError(f"warm-up: pool proofs rejected at "
                               f"{m} proofs: {got}")
    lanes = sorted({scrypt.shape_bucket(k3 * m) for m in counts})
    pows = []
    max_items = concurrent * max(r["n_atx"] for r in gen["requests"])
    b = 8                               # ops/pow.verify_many min_device
    while b <= scrypt.shape_bucket(max_items):
        reqs = [(it.challenge, it.node_id, params.pow_difficulty,
                 it.proof.pow_nonce) for it in (items * b)[:b]]
        if not all(k2pow.verify_many(reqs)):
            raise RuntimeError("warm-up: pool k2pow witnesses rejected")
        pows.append(b)
        b *= 2
    setup["warm_shapes_s"] = time.perf_counter() - t0
    setup["warm_proof_counts"] = len(counts)
    setup["warm_label_lanes"] = lanes
    setup["warm_pow_lanes"] = pows


def _reference_post_verdict(cfg, pool, doc, k3, post_seed) -> bool:
    pr = doc["proof"]
    return reference.verify_post(
        indices=pr["indices"], nonce=pr["nonce"], pow_nonce=pr["pow_nonce"],
        challenge=bytes.fromhex(doc["challenge"]),
        node_id=bytes.fromhex(doc["node_id"]),
        commitment=bytes.fromhex(doc["commitment"]),
        scrypt_n=doc["scrypt_n"], total_labels=doc["total_labels"],
        k1=int(cfg["k1"]), k2=int(cfg["k2"]), k3=k3,
        pow_difficulty=bytes.fromhex(cfg["pow_difficulty"]),
        seed=post_seed)


def run(run) -> dict:
    from spacemesh_tpu.post.prover import ProofParams
    from spacemesh_tpu.utils import metrics
    from spacemesh_tpu.verifyd.server import VerifydServer
    from spacemesh_tpu.verifyd.service import VerifydService

    cfg, tr = run.config, run.traffic
    setup = {"import_and_chip_open_s": time.perf_counter() - run.t_start}
    fallbacks0 = dict(metrics.runtime_fallbacks.sample())
    pool, how = atxpool.load_or_build(cfg, run.cache, log)
    setup["pool_" + ("build_s" if how["built"] else "load_s")] = \
        how["seconds"]
    t = time.perf_counter()
    gen = run.generator().generate(run, pool)
    setup["generate_requests_s"] = time.perf_counter() - t
    k3, post_seed = gen["k3"], gen["post_seed"]
    params = ProofParams(k1=int(cfg["k1"]), k2=int(cfg["k2"]), k3=k3,
                         pow_difficulty=bytes.fromhex(cfg["pow_difficulty"]))
    window_s = run.window_s
    warm_s, drain_s = float(tr["warm_s"]), float(tr.get("drain_s", 15.0))
    workers = inspect.signature(VerifydService.__init__) \
        .parameters["workers"].default
    # requests in the farm at once: one per scheduler worker, and in a
    # closed loop no more than there are clients
    concurrent = min(workers, len(gen["clients"])
                     if tr["loop"] == "closed" else workers)
    _warm_shapes(cfg, pool, params, k3, gen, concurrent, setup)

    out = run.fresh_dir("loadgen")
    bodies_file = out / "bodies.bin"
    spec_reqs, off = [], 0
    with open(bodies_file, "wb") as f:
        for r, body in zip(gen["requests"], gen["bodies"]):
            f.write(body)
            spec_reqs.append({"client": r["client"], "due": r["due"],
                              "offset": off, "length": len(body)})
            off += len(body)
    win = tracewin.TraceWindow(run.trace, run.fresh_dir("trace"),
                               keep=run.args.keep_trace)
    box: dict = {}

    async def serve() -> None:
        t = time.perf_counter()
        server = VerifydServer(listen="127.0.0.1:0", post_params=params,
                               post_seed=post_seed)
        try:
            port = await server.start()
            setup["server_start_s"] = time.perf_counter() - t
            t0 = time.perf_counter() + warm_s + CHILD_START_S
            spec = {"url": f"http://127.0.0.1:{port}", "t0": t0,
                    "window_s": window_s, "warm_s": warm_s,
                    "drain_s": drain_s, "loop": tr["loop"],
                    "clients": gen["clients"],
                    "bodies_file": str(bodies_file),
                    "requests": spec_reqs,
                    "results_file": str(out / "results.jsonl")}
            with open(out / "spec.json", "w") as f:
                json.dump(spec, f)
            box["t0"] = t0
            marker = win.hold(run.clock, window_s, at=t0)
            proc = await asyncio.create_subprocess_exec(
                sys.executable, str(run.bench / "lib" / "loadgen.py"),
                str(out / "spec.json"))
            try:
                rc = await asyncio.wait_for(
                    proc.wait(), warm_s + window_s + drain_s + 60)
            finally:
                if proc.returncode is None:
                    proc.kill()
                    await proc.wait()
            # the thread also collects the profiler's data, which takes
            # a while for millions of events; never leave it running
            marker.join(timeout=600)
            if marker.is_alive():
                raise RuntimeError("the window thread did not end")
            if rc != 0:
                raise RuntimeError(f"load generator exited {rc}")
            box["stats"] = server.service.stats_doc()
            box["tune"] = {k: dict(server.service.tuner.rates(k))
                           for k in ("post", "pow", "sig", "membership")}
        finally:
            await server.close()

    asyncio.run(serve())
    win.finish()
    t0 = box["t0"]
    t_end = t0 + window_s
    compiled = run.clock.window_report(win.clock0, win.clock1,
                                       window_s)

    # --- reduce the generator's record ----------------------------------
    recs = {}
    gen_errors = []
    with open(out / "results.jsonl") as f:
        for line in f:
            rec = json.loads(line)
            if rec["i"] < 0:
                gen_errors.append(rec.get("error"))
            else:
                recs[rec["i"]] = rec
    open_loop = tr["loop"] == "open"
    measured = []           # (request index, record)
    for i, r in enumerate(gen["requests"]):
        rec = recs.get(i)
        if open_loop:
            if 0.0 <= r["due"] < window_s:
                measured.append((i, rec or {"error": "never sent"}))
        elif rec is not None and "done" in rec \
                and t0 <= rec["done"] <= t_end:
            measured.append((i, rec))
        elif rec is not None and "done" not in rec:
            measured.append((i, rec))
    wrong = []
    failed = ok_atx = 0
    lat_ms, late_ms, done_at = [], [], []
    for i, rec in measured:
        want = gen["requests"][i]["want"]
        if "verdicts" not in rec:
            failed += 1
            continue
        if rec["verdicts"] != want:
            wrong.append(i)
        ok_atx += gen["requests"][i]["n_atx"]
        lat_ms.append(1e3 * (rec["done"] - rec["due"]))
        late_ms.append(1e3 * (rec["sent"] - rec["due"]))
        done_at.append((rec["done"], gen["requests"][i]["n_atx"]))
    # warm-up verdicts count for `correct` too
    for i, rec in recs.items():
        if "verdicts" in rec and rec["verdicts"] != gen["requests"][i]["want"] \
                and i not in wrong:
            wrong.append(i)
    if open_loop:
        proofs_per_s = ok_atx / window_s
    else:
        # first to last completion inside the window: a window edge that
        # cuts a request does not quantise the rate
        done_at.sort()
        proofs_per_s = None
        if len(done_at) >= 2 and done_at[-1][0] > done_at[0][0]:
            proofs_per_s = sum(n for _t, n in done_at[1:]) \
                / (done_at[-1][0] - done_at[0][0])
    end_to_end = {"setup_s": (t0 - run.t_start, "s"),
                  "proofs_per_s": (proofs_per_s, "proofs/s"),
                  "p50_ms": (stats.median(lat_ms), "ms")}

    # --- correct: outside the window ------------------------------------
    checks: dict = {"requests_measured": len(measured),
                    "requests_failed": failed,
                    "wrong_verdict_requests": wrong[:8],
                    "generator_errors": gen_errors[:4],
                    "compiles_in_window": compiled}
    rng = random.Random(f"benchmark/reference/{run.seed}")
    candidates = [(i, k) for i, rec in measured if "verdicts" in rec
                  for k in range(gen["requests"][i]["n_atx"])]
    picks = rng.sample(candidates, min(REFERENCE_ATXS, len(candidates)))

    def check_one(pick):
        i, k = pick
        body = json.loads(gen["bodies"][i])
        doc = body["items"][k * atx_stream.ITEMS_PER_ATX + 2]
        want = _reference_post_verdict(cfg, pool, doc, k3, post_seed)
        got = recs[i]["verdicts"][k * atx_stream.ITEMS_PER_ATX + 2]
        return want == got

    with ThreadPoolExecutor(max_workers=8) as ex:
        ref_ok = list(ex.map(check_one, picks))
    checks["reference_atxs_checked"] = len(picks)
    checks["reference_verdicts_equal"] = all(ref_ok) and bool(picks)
    moved = {str(k): v for k, v in metrics.runtime_fallbacks.sample().items()
             if v != fallbacks0.get(k, 0)}
    checks["runtime_fallbacks_moved"] = moved
    checks["service"] = {k: box["stats"].get(k) for k in
                         ("requests", "admitted_items", "resolved_items",
                          "pending_peak", "shed", "farm", "tuner")}
    checks["tuner_post_rates"] = box["tune"].get("post")
    correct = (not wrong and not gen_errors and checks[
        "reference_verdicts_equal"] and not moved
        and compiled["ok"] and proofs_per_s is not None
        and bool(lat_ms))

    n = int(cfg["scrypt_n"])
    device_atx = sum(1 for i, rec in measured if "verdicts" in rec
                     for f in gen["requests"][i]["atx"] if f["on_device"])
    return {
        "correct": correct,
        "attempted": len(measured),
        "failed": failed,
        "end_to_end": end_to_end,
        "program_bytes": shapes.romix_v_bytes(
            n, max(setup.get("warm_label_lanes") or [0])),
        "checks": checks,
        "setup_parts": {**setup, "compile": win.clock0},
        "trace_data": win.data,
        "window_s": window_s,
        "gap_spans": GAP_SPANS,
        "idle_label": "no request open",
        "spans": win.spans(),
        "counters": {"scrypt_n": n, "k3": k3,
                     "device_checked_proofs": device_atx,
                     "service": box["stats"]},
        "generator": {"latency_ms": lat_ms,
                      "late_ms": late_ms if open_loop else [],
                      "requests": len(measured), "failed": failed},
    }

