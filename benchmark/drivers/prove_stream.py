"""One post-service on the chip answering GenProof after GenProof: a
closed loop of WHOLE proofs, one client, no think time.

The system under test is ``post/prover``: request i is
``Prover(data_dir, ProofParams(k1, k2, k3, pow_difficulty))
.prove(challenge_i)`` with no other argument (the construction a
``PostClient`` does per challenge), k2pow search included, timed from
outside as a client times it. The driver hands the program nothing a
client would not: no hook, no ``stop()``, no environment variable (it
refuses to start if a ``SPACEMESH_PROVE_*`` variable is set). What the
configuration says of batch, nonce group, passes and scan step is
COMPARED with what the default ``Prover`` reports, never imposed.

The store is a fixture: the configuration's identity, made once per
checkout by the system's own ``initializer.initialize`` under
``.cache/benchmark/fixtures/``. ``--seed`` draws the challenges.

Set-up: the fixture (built or found); one whole warm-up proof on a
challenge of its own (every program compiled or fetched). Window: opens
when the next request is sent, lasts ``--seconds`` (``trace_seconds``
when traced); the request in flight when it closes is finished and NOT
counted. A program that compiles anew in every proof (PR 27's parent:
two ``jit(scan)`` in ``ops/pow.prefix_state``) runs the cell to its end
and reads ``correct`` false by ``compiles_in_window``.

``p50_ms``: median, over the requests that began and ended inside the
window, of request sent -> proof object returned: the latency of the
request of rank ceil(n/2), one that was made (:func:`median_request`).
One proof in five needs a second pass and costs double, so between the
two middle requests of an even count there can lie a whole pass, and
their midpoint is a latency no request had. ``attempted``: those
requests, ``failed``: those of them that raised or returned no proof. A
window that ends with no finished request is an error with its own
message: the driver never prints ``attempted`` 0.

``correct`` (outside the window, on what the timed path returned):
EVERY proof of the run passes ``lib/prove_reference.check`` (K2 indices
ascending, in range, each qualifying over the bytes on disk; k2pow
witness by ``hashlib``) and ``post/verifier.verify`` at K3 = K2; for the
first proof of the window and one more drawn by ``--seed`` the plain
reference prover's full answer (the lowest winning nonce and its first
K2 indices, ``lib/prove_reference.prove`` on spawned processes that
never import JAX) equals the proof; nothing compiled inside the window;
``runtime_fallbacks`` did not move; the default ``Prover`` has the
configuration's shapes and its scan step is the configuration's
(``pallas`` on 1 device on the chip).
"""

from __future__ import annotations

import hashlib
import json
import os
import random
import shutil
import sys
import time
from pathlib import Path

from lib import prove_reference, tracewin

GAP_SPANS = ("prove.read_wait", "prove.convert", "prove.upload",
             "prove.enqueue", "prove.retire", "prove.k2pow")
STORE_SCHEMA = 1
_STORE_KEYS = ("num_units", "store_labels", "store_scrypt_n",
               "max_file_size", "fixture_seed")
MAX_ERRORS = 3          # a loop that only fails is not worth its window


def log(*a) -> None:
    print("benchmark:", *a, file=sys.stderr, flush=True)


def _derive(seed, tag: str) -> bytes:
    return hashlib.sha256(f"benchmark/prove/{seed}/{tag}".encode()).digest()


def median_request(lat_ms: list) -> float:
    """The lower median: the latency of the request of rank ceil(n/2)."""
    return sorted(lat_ms)[(len(lat_ms) - 1) // 2]


def store_key(cfg: dict) -> str:
    doc = json.dumps([STORE_SCHEMA] + [cfg[k] for k in _STORE_KEYS])
    return hashlib.sha256(doc.encode()).hexdigest()[:16]


def load_or_build_store(cfg: dict, cache: Path):
    """-> (data_dir, {"built": bool, "seconds": float}). The store of
    the configuration's identity, complete: built by the system's own
    init into a scratch directory and renamed into place when done."""
    from spacemesh_tpu.post import initializer
    from spacemesh_tpu.post.data import PostMetadata

    t0 = time.perf_counter()
    total, units = int(cfg["store_labels"]), int(cfg["num_units"])
    final = cache / "fixtures" / f"prove-store-{store_key(cfg)}"
    if final.exists():
        try:
            if PostMetadata.load(final).labels_written == total:
                return final, {"built": False,
                               "seconds": time.perf_counter() - t0}
        except (OSError, ValueError) as e:
            log(f"store fixture unreadable ({e}); rebuilding")
        shutil.rmtree(final)
    work = final.with_name(final.name + ".work")
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    fs = cfg["fixture_seed"]
    meta, _res = initializer.initialize(
        work, node_id=_derive(fs, "node"),
        commitment=_derive(fs, "commitment"), num_units=units,
        labels_per_unit=total // units,
        scrypt_n=int(cfg["store_scrypt_n"]),
        max_file_size=int(cfg["max_file_size"]),
        batch_size=int(cfg["fixture_init_batch"]))
    if meta.labels_written != total:
        raise RuntimeError(f"fixture init wrote {meta.labels_written} of "
                           f"{total} labels")
    work.rename(final)
    return final, {"built": True, "seconds": time.perf_counter() - t0}


def run(run) -> dict:
    set_vars = sorted(k for k in os.environ
                      if k.startswith("SPACEMESH_PROVE_"))
    if set_vars:
        raise SystemExit(f"benchmark: {set_vars} set: the cell runs the "
                         "Prover's defaults and nothing else")
    from spacemesh_tpu.post import verifier
    from spacemesh_tpu.post.prover import ProofParams, Prover
    from spacemesh_tpu.utils import metrics

    cfg = run.config
    seconds = run.window_s
    setup = {"import_and_chip_open_s": time.perf_counter() - run.t_start}
    fallbacks0 = dict(metrics.runtime_fallbacks.sample())
    data_dir, how = load_or_build_store(cfg, run.cache)
    setup["store_" + ("build_s" if how["built"] else "load_s")] = \
        how["seconds"]
    files = prove_reference.store_files(data_dir)
    total = int(cfg["store_labels"])
    fs = cfg["fixture_seed"]
    node_id, commitment = _derive(fs, "node"), _derive(fs, "commitment")
    k1, k2 = int(cfg["k1"]), int(cfg["k2"])
    diff = bytes.fromhex(cfg["pow_difficulty"])
    params = ProofParams(k1=k1, k2=k2, k3=int(cfg["k3"]),
                         pow_difficulty=diff)

    def new_prover():
        return Prover(data_dir, params)      # and no other argument

    probe = new_prover()
    _step, mesh, impl = probe.scan_step()
    shape = {"batch_labels": probe.batch_labels,
             "nonce_group": probe.nonce_group,
             "window_groups": probe.window_groups,
             "inflight": probe.inflight, "readers": probe.readers,
             "scan_step": impl}
    devices_used = mesh.size if mesh is not None else 1

    records: list = []      # every request of the run, warm-up first

    def request(i) -> None:
        challenge = _derive(run.seed, f"challenge-{i}")
        rec = {"i": i, "challenge": challenge, "proof": None,
               "error": None, "sent": time.perf_counter()}
        try:
            prover = new_prover()
            rec["proof"] = prover.prove(challenge)
            st = prover.last_stats
            rec["stats"] = {"passes": st.windows,
                            "labels_swept": st.labels_swept,
                            "early_exited": st.early_exited}
        except Exception as e:  # noqa: BLE001 - a failed request is counted
            rec["error"] = f"{type(e).__name__}: {e}"
        rec["done"] = time.perf_counter()
        records.append(rec)

    request("warmup")
    setup["warmup_proof_s"] = records[0]["done"] - records[0]["sent"]
    if records[0]["proof"] is None:
        raise RuntimeError(f"the warm-up proof failed: {records[0]['error']}")

    # --- the window: held by its own thread, requests on this one --------
    win = tracewin.TraceWindow(run.trace, run.fresh_dir("trace"),
                               keep=run.args.keep_trace)
    marker = win.hold(run.clock, seconds)
    while win.t0 is None:               # profiler on, annotation open
        if not marker.is_alive():
            raise RuntimeError("the window thread ended before it opened")
        time.sleep(0.0005)
    t_lo, t_hi = win.t0, win.t0 + seconds
    i = 0
    while True:         # the request in flight at t_hi is the last one
        request(i)
        i += 1
        if time.perf_counter() >= t_hi or sum(
                1 for r in records if r["error"]) >= MAX_ERRORS:
            break
    marker.join(timeout=seconds + 600)
    if marker.is_alive():
        raise RuntimeError("the window thread did not end")
    win.finish()        # after the request in flight: its spans are whole
    compiled = run.clock.window_report(win.clock0, win.clock1, seconds)

    inside = [r for r in records[1:]
              if r["sent"] >= t_lo and r["done"] <= t_hi]
    if not inside:
        raise RuntimeError(
            f"the window of {seconds} s ended with no finished request "
            f"({len(records) - 1} sent; the first took "
            f"{records[1]['done'] - records[1]['sent']:.1f} s): nothing to "
            "report, and attempted 0 is never printed")
    ok = [r for r in inside if r["proof"] is not None]
    lat_ms = [1e3 * (r["done"] - r["sent"]) for r in ok]
    end_to_end = {"setup_s": (t_lo - run.t_start, "s"),
                  "p50_ms": (median_request(lat_ms), "ms")}

    # --- correct: outside the window --------------------------------------
    proved = [r for r in records if r["proof"] is not None]
    checks: dict = {
        "requests": len(records) - 1, "in_window": len(inside),
        "errors": [r["error"] for r in records if r["error"]][:4],
        "compiles_in_window": compiled,
        "default_prover": shape,
        "default_prover_as_configured": all(
            shape[k] == cfg[k] for k in shape),
        "devices_used": devices_used,
    }
    t = time.perf_counter()
    each = []
    for r in proved:
        p = r["proof"]
        c = prove_reference.check(
            files, r["challenge"], node_id, nonce=p.nonce,
            indices=p.indices, pow_nonce=p.pow_nonce, k1=k1, k2=k2,
            pow_difficulty=diff)
        c["verifies"] = verifier.verify(verifier.VerifyItem(
            proof=p, challenge=r["challenge"], node_id=node_id,
            commitment=commitment, scrypt_n=int(cfg["store_scrypt_n"]),
            total_labels=total), params, seed=b"benchmark-prove")
        each.append(c)
    checks["every_proof"] = {k: all(c[k] for c in each)
                             for k in ("shape", "qualify", "witness",
                                       "verifies")}
    checks["every_proof_s"] = time.perf_counter() - t
    rng = random.Random(f"benchmark/prove/reference/{run.seed}")
    sample = ok[:1] + rng.sample(ok[1:], min(1, len(ok) - 1))
    t = time.perf_counter()
    the_proof = []
    with prove_reference.worker_pool() as pool:
        for r in sample:
            nonce, indices = prove_reference.prove(
                files, r["challenge"], k1, k2, pool=pool)
            the_proof.append({
                "i": r["i"], "nonce": r["proof"].nonce,
                "reference_nonce": nonce,
                "equal": (nonce == r["proof"].nonce and indices
                          == [int(j) for j in r["proof"].indices])})
    checks["the_proof"] = the_proof
    checks["the_proof_s"] = time.perf_counter() - t
    checks["proofs"] = [
        {"i": r["i"], "ms": round(1e3 * (r["done"] - r["sent"]), 1),
         "nonce": r["proof"].nonce, **r["stats"]} for r in proved][:24]
    moved = {str(k): v for k, v in metrics.runtime_fallbacks.sample().items()
             if v != fallbacks0.get(k, 0)}
    checks["runtime_fallbacks_moved"] = moved
    correct = (all(checks["every_proof"].values())
               and bool(the_proof) and all(x["equal"] for x in the_proof)
               and checks["default_prover_as_configured"]
               and devices_used == run.chips
               and not moved and compiled["ok"])

    return {
        "correct": correct,
        "attempted": len(inside),
        "failed": len(inside) - len(ok),
        "end_to_end": end_to_end,
        "program_bytes": 0,     # three batches in flight: what the
        #                         runtime reports is the whole of it
        "checks": checks,
        "setup_parts": {**setup, "compile": win.clock0},
        "trace_data": win.data,
        "window_s": win.t1 - win.t0,
        "gap_spans": GAP_SPANS,
        "idle_label": "no prove span open",
        "spans": win.spans(),
        "counters": {"total_labels": total, "default_prover": shape},
        "generator": {"latency_ms": lat_ms, "requests": len(inside),
                      "failed": len(inside) - len(ok)},
    }
