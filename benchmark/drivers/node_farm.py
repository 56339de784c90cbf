"""A node's OWN verification farm on the chip, fed a standing backlog by
sync workers in the same process: no verifyd, no HTTP, no scheduler.

The system under test is ``verify/farm.VerificationFarm`` built as
``node/app.py`` builds it: ``VerificationFarm(ed_verifier=...,
post_params=ProofParams(k1, k2, k3, pow_difficulty))`` plus a fixed
``post_seed`` from ``--seed`` (so the plain reference can sample what
the farm sampled) and NO other argument: ``max_batch`` 256, no batch
tuner, default lanes and deadlines. The driver COMPARES what the farm
it built reports with the configuration's ``farm_max_batch`` and
``farm_tuner`` and reads ``correct`` false if they moved; it sets
neither. Nor does it set how a batch reaches the device: a 256-proof
batch at K3 = 37 is 9,472 lanes, which ``post/verifier.verify_many``
cuts into lane tiles under ``ops/scrypt.lane_ceiling`` by itself. (A
rehearsal on the CPU, and only a rehearsal, replaces that function by
the tiny configuration's ``rehearse_lane_ceiling`` so that tiny batches
tile too.)

The workers are coroutines on this process's event loop, one request
outstanding each (``generators/atx_backlog``): a request is
``atx_per_request`` ATXs = four farm items each, all submitted on the
traffic's lane in one loop turn, and the worker waits for every verdict
before it takes the next request from the one queue.

Set-up, in this order: the WIDEST device shape first (one
``verify_many`` of ``max_batch`` copies of a made-up proof with a true
k2pow witness, straight through ``post/verifier``: a program that
cannot run a full batch on this chip dies here, seconds after the chip
opens, with the compiler's own error); the pool of real proofs (built
once per checkout, ``lib/atxpool.py``); this run's requests; every
other shape the traffic can reach (the remainder buckets a batch's
host-rejected proofs can leave, the k2pow buckets); then ``warm_s``
seconds of the cell's own traffic through the farm. An exception from
the farm, in the warm-up or in the window, ends the run at once: a
farm that only fails is not worth its window.

``proofs_per_s``: ATXs whose four verdicts came back, between the
first and the last STEP of completions inside the window
(:func:`step_rate`): a POST batch returns four requests' verdicts
within milliseconds of each other, so completions come in steps of
``max_batch`` ATXs, and a rate from the first to the last completion
that counts three quarters of the first step over no time reads 7%
high in a window of eleven steps. ``attempted``: requests that
finished in the window; ``failed``: those of them without verdicts.

``correct`` (outside the window): every verdict of the run equals the
generator's ``want``; ``lib/reference.verify_post`` agrees on a seeded
sample of at least ``REFERENCE_ATXS`` ATXs from the window's own
verdicts that holds valid proofs and host-rejected ones; THE CHECK
BATCH (below) came back as the reference has it; no
``runtime_fallbacks`` moved; nothing compiled in the window; the farm
has the configuration's defaults; no label program was wider than the
ceiling the program works out (on one chip: the counter's ``lanes`` is
a program's whole width, mesh-wide where a batch is sharded, and this
cell shards none); every POST batch in the window was ``max_batch``
proofs (traced: ``n`` of every ``farm.batch`` span; untraced: the label
programs counted in the window pair up as one full tile and one
remainder, which only a batch of 222 proofs or more leaves).

The check batch. The traffic's invalid ATXs never fail ON the device
(``generators/atx_backlog``), so the timed verdicts cannot show a lane
tile that answers wrongly. After the window has closed the workers
finish the requests they have out and send no more; when the farm is
empty the driver submits, to the SAME farm object, one more batch of
``max_batch`` POST proofs that all reach the device (the widest shape
of the warm-up: the same compiled programs at the same sizes), with a
swapped index in the proofs at both ends of each lane tile. All its
verdicts are held to the generator's ``want``, every swapped proof's
and ``REFERENCE_EACH`` valid ones' to ``reference.verify_post``, each
tile has to hold a proof that FAILED, and the label programs counted
across it have to be the tiles of one full batch.
"""

from __future__ import annotations

import asyncio
import hashlib
import random
import sys
import time
from concurrent.futures import ThreadPoolExecutor

from generators import atx_backlog, atx_stream
from lib import atxpool, reference, shapes, stats, tracewin

GAP_SPANS = ("farm.batch", "post.verify", "post.verify.checks",
             "post.verify.pack", "romix.upload", "romix.dispatch",
             "post.verify.threshold", "pow_verify.dispatch",
             "pow_verify.retire")
STAGE_SPANS = ("farm.batch", "post.verify", "post.verify.checks",
               "post.verify.pack", "romix.upload", "romix.dispatch",
               "device.flight", "post.verify.threshold")
REFERENCE_ATXS = 32
# of the sample, at least this many of each kind where the window has them
REFERENCE_EACH = 4


def log(*a) -> None:
    print("benchmark:", *a, file=sys.stderr, flush=True)


def step_rate(done_at: list):
    """ATXs per second from ``[(seconds, atxs)]`` completions that come
    in steps. Completions less than a quarter of the widest gap apart
    are one step (one batch returning); the rate is the ATXs of every
    step after the first over the time from the first step's last
    completion to the last step's. Evenly spaced completions are a step
    each, and the rule is first-to-last. -> (rate | None, steps)"""
    done_at = sorted(done_at)
    if len(done_at) < 2:
        return None, len(done_at)
    gaps = [b[0] - a[0] for a, b in zip(done_at, done_at[1:])]
    cut = max(gaps) / 4
    steps = [[done_at[0][0], done_at[0][1]]]      # [last time, atxs]
    for (t, n), gap in zip(done_at[1:], gaps):
        if gap > cut:
            steps.append([t, n])
        else:
            steps[-1][0] = t
            steps[-1][1] += n
    if len(steps) < 2 or steps[-1][0] <= steps[0][0]:
        return None, len(steps)
    return sum(n for _t, n in steps[1:]) / (steps[-1][0] - steps[0][0]), \
        len(steps)


def lane_tiles(lanes: int, ceiling: int) -> list:
    """The widths ``post/verifier`` cuts ``lanes`` lanes into under
    ``ceiling``: restated here (full tiles, then the rest in its power
    of two), to know which shapes to warm and to place a lane."""
    full, rest = divmod(lanes, ceiling)
    return [ceiling] * full + ([1 << (rest - 1).bit_length()] if rest
                               else [])


def _made_up_item(cfg: dict, k2: int):
    """A proof that passes every host check (K2 distinct in-range
    indices, a true k2pow witness found here by hashlib) and fails on
    the device: what the shape warm-up sends, before there is a pool."""
    from spacemesh_tpu.post import verifier
    from spacemesh_tpu.post.prover import Proof

    def h(tag: str) -> bytes:
        return hashlib.sha256(f"benchmark/node-farm/warm/{tag}"
                              .encode()).digest()

    diff = bytes.fromhex(cfg["pow_difficulty"])
    challenge, node_id = h("challenge"), h("node")
    witness = 0
    while not reference.k2pow_ok(challenge, node_id, diff, witness):
        witness += 1
    return verifier.VerifyItem(
        proof=Proof(nonce=0, indices=list(range(k2)), pow_nonce=witness,
                    k2=k2),
        challenge=challenge, node_id=node_id, commitment=h("commitment"),
        scrypt_n=int(cfg["scrypt_n"]),
        total_labels=int(cfg["store_units"])
        * int(cfg["store_labels_per_unit"]))


def _reference_post_verdict(cfg, item, k3: int, post_seed: bytes) -> bool:
    return reference.verify_post(
        indices=list(item.proof.indices), nonce=item.proof.nonce,
        pow_nonce=item.proof.pow_nonce, challenge=item.challenge,
        node_id=item.node_id, commitment=item.commitment,
        scrypt_n=item.scrypt_n, total_labels=item.total_labels,
        k1=int(cfg["k1"]), k2=int(cfg["k2"]), k3=k3,
        pow_difficulty=bytes.fromhex(cfg["pow_difficulty"]),
        seed=post_seed)


def _label_programs(metrics) -> dict:
    """{lanes: label programs post/verifier has enqueued so far}; empty
    for a program that does not count them."""
    counter = getattr(metrics, "post_verify_label_programs", None)
    if counter is None:
        return {}
    return {int(dict(k)["lanes"]): int(v)
            for k, v in counter.sample().items()}


def run(run) -> dict:
    from spacemesh_tpu.core.signing import EdVerifier
    from spacemesh_tpu.ops import pow as k2pow
    from spacemesh_tpu.ops import scrypt
    from spacemesh_tpu.post import verifier
    from spacemesh_tpu.post.prover import ProofParams
    from spacemesh_tpu.utils import metrics
    from spacemesh_tpu.verify.farm import FarmClosed, Lane, VerificationFarm

    cfg, tr = run.config, run.traffic
    setup = {"import_and_chip_open_s": time.perf_counter() - run.t_start}
    fallbacks0 = dict(metrics.runtime_fallbacks.sample())
    n, k2, k3 = int(cfg["scrypt_n"]), int(cfg["k2"]), int(cfg["k3"])
    if int(tr["k3"]) != k3:
        raise ValueError("the traffic's k3 is not the configuration's")
    max_batch = int(cfg["farm_max_batch"])
    params = ProofParams(k1=int(cfg["k1"]), k2=k2, k3=k3,
                         pow_difficulty=bytes.fromhex(cfg["pow_difficulty"]))
    if run.rehearse and "rehearse_lane_ceiling" in cfg:
        forced = int(cfg["rehearse_lane_ceiling"])
        scrypt.lane_ceiling = lambda n, devices=None: forced
    window_s, warm_s = run.window_s, float(tr["warm_s"])

    # --- the widest shape first: a full batch of proofs in one call ------
    # (max_batch of them; a rehearsal's few workers cannot fill that)
    a, workers = int(tr["atx_per_request"]), int(tr["workers"])
    must_fill = bool(tr.get("full_batches", True))
    batch = min(max_batch, 1 << ((workers * a).bit_length() - 1))
    if must_fill and batch != max_batch:
        raise ValueError("full_batches asks for workers x atx_per_request "
                         f">= max_batch {max_batch}")
    t = time.perf_counter()
    made_up = _made_up_item(cfg, k2)
    verifier.verify_many([made_up] * batch, params, seed=b"warm-up")
    setup["warm_widest_s"] = time.perf_counter() - t
    # a program without the function has no ceiling to hold it to
    ceiling = scrypt.lane_ceiling(n) if hasattr(scrypt, "lane_ceiling") \
        else None
    cap = ceiling or 1 << 62            # no ceiling: one tile, any width

    pool, how = atxpool.load_or_build(cfg, run.cache, log)
    setup["pool_" + ("build_s" if how["built"] else "load_s")] = \
        how["seconds"]
    t = time.perf_counter()
    gen = run.generator().generate(
        run, pool, check=(batch, lane_tiles(batch * k3, cap)))
    setup["generate_requests_s"] = time.perf_counter() - t
    requests, post_seed, check = \
        gen["requests"], gen["post_seed"], gen["check"]
    lane = Lane[gen["lane"].upper()]

    # --- every other shape the traffic can reach --------------------------
    t = time.perf_counter()
    per_req = [sum(1 for f in r["atx"] if f["on_device"]) for r in requests]
    m = max(batch // a, 1)              # whole requests in a full batch
    warmed = {tuple(lane_tiles(batch * k3, cap))}
    # a batch holds whole requests: m of them where every batch is full,
    # else any power of two up to m
    for reqs in ([m] if must_fill else
                 [1 << e for e in range(m.bit_length())]):
        for count in range(reqs * min(per_req), reqs * max(per_req) + 1):
            widths = tuple(lane_tiles(count * k3, cap))
            if count and widths not in warmed:
                verifier.verify_many([made_up] * count, params,
                                     seed=b"warm-up")
                warmed.add(widths)
    pows, b = [], 8                     # ops/pow.verify_many min_device
    while b <= max_batch:
        if not all(k2pow.verify_many(
                [(made_up.challenge, made_up.node_id, params.pow_difficulty,
                  made_up.proof.pow_nonce)] * b)):
            raise RuntimeError("warm-up: a true k2pow witness was rejected")
        pows.append(b)
        b *= 2
    setup["warm_shapes_s"] = time.perf_counter() - t
    setup["warm_label_tiles"] = sorted(warmed)
    setup["warm_pow_lanes"] = pows

    win = tracewin.TraceWindow(run.trace, run.fresh_dir("trace"),
                               keep=run.args.keep_trace)
    records: list = []
    box: dict = {"errors": [], "programs": []}

    async def serve() -> None:
        farm = VerificationFarm(ed_verifier=EdVerifier(),
                                post_params=params, post_seed=post_seed)
        box["farm"] = {"max_batch": farm.max_batch,
                       "tuner": getattr(farm, "_tuner", None)}
        todo = iter(range(len(requests)))
        stopping = False

        async def worker() -> None:
            while not stopping:
                i = next(todo, None)
                if i is None:
                    box["errors"].append("ran out of prepared requests")
                    return
                rec = {"i": i, "sent": time.perf_counter()}
                records.append(rec)
                try:
                    rec["verdicts"] = list(await asyncio.gather(*(
                        farm.submit(it, lane) for it in requests[i]["items"])))
                except FarmClosed:
                    if stopping:
                        return
                    raise
                rec["done"] = time.perf_counter()

        t0 = time.perf_counter() + warm_s
        box["t0"] = t0
        marker = win.hold(run.clock, window_s, at=t0)
        tasks = [asyncio.ensure_future(worker()) for _ in range(workers)]
        try:
            for edge in (t0, t0 + window_s):
                done, _ = await asyncio.wait(
                    tasks, timeout=max(edge - time.perf_counter(), 0),
                    return_when=asyncio.FIRST_EXCEPTION)
                for task in done:       # fatal at once
                    if task.exception() is not None:
                        raise RuntimeError(
                            "the farm raised") from task.exception()
                box["programs"].append(_label_programs(metrics))
            # after the window: the workers finish what they have out,
            # then the check batch goes through the same, empty, farm
            stopping = True
            for task in (await asyncio.wait(tasks))[0]:
                if task.exception() is not None:
                    raise RuntimeError(
                        "the farm raised") from task.exception()
            box["programs"].append(_label_programs(metrics))
            box["check_verdicts"] = list(await asyncio.gather(*(
                farm.submit(it, lane) for it in check["items"])))
            box["programs"].append(_label_programs(metrics))
        finally:
            stopping = True
            for task in tasks:
                task.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            box["stats"] = dict(farm.stats)
            await farm.aclose()
        # the thread also collects the profiler's data
        marker.join(timeout=600)
        if marker.is_alive():
            raise RuntimeError("the window thread did not end")

    asyncio.run(serve())
    win.finish()
    t0 = box["t0"]
    t_end = t0 + window_s
    compiled = run.clock.window_report(win.clock0, win.clock1, window_s)

    # --- reduce the workers' record ---------------------------------------
    measured = [r for r in records if "done" in r and t0 <= r["done"] <= t_end]
    wrong = [r["i"] for r in records
             if "verdicts" in r and r["verdicts"] != requests[r["i"]]["want"]]
    failed = sum(1 for r in measured if "verdicts" not in r)
    done_at = [(r["done"], requests[r["i"]]["n_atx"]) for r in measured
               if "verdicts" in r]
    proofs_per_s, steps = step_rate(done_at)
    if not measured:
        raise RuntimeError(
            f"the window of {window_s} s ended with no finished request "
            f"({len(records)} sent): nothing to report, and attempted 0 "
            "is never printed")
    end_to_end = {"setup_s": (t0 - run.t_start, "s"),
                  "proofs_per_s": (proofs_per_s, "proofs/s")}

    # --- correct: outside the window --------------------------------------
    before, after, check0, check1 = box["programs"]
    in_window = {w: after.get(w, 0) - before.get(w, 0) for w in after
                 if after.get(w, 0) != before.get(w, 0)}
    widest = max(check1, default=0)     # of the whole run
    spans = win.spans()
    post_batches = [s["args"].get("n") for s in spans
                    if s["name"] == "farm.batch"
                    and s["args"].get("kind") == "post"]
    dispatched = [s["args"]["batch"] for s in spans
                  if s["name"] == "romix.dispatch" and "batch" in s["args"]]
    full = in_window.get(ceiling, 0)
    rests = sum(v for w, v in in_window.items() if w != ceiling)
    if not must_fill:
        batches_full = True
    elif run.trace:
        batches_full = bool(post_batches) and all(
            x == batch for x in post_batches)
    else:       # one full tile and one remainder a batch (see the top);
        #         a window edge may fall between the two enqueues
        batches_full = full > 0 and abs(full - rests) <= 1
    checks: dict = {
        "requests_sent": len(records), "requests_measured": len(measured),
        "steps_in_window": steps,
        "wrong_verdict_requests": wrong[:8],
        "generator_errors": box["errors"][:4],
        "compiles_in_window": compiled,
        "farm": {"max_batch": box["farm"]["max_batch"],
                 "tuner": repr(box["farm"]["tuner"]),
                 "stats": box["stats"]},
        "farm_as_configured": (box["farm"]["max_batch"] == max_batch
                               and box["farm"]["tuner"] is None
                               and cfg["farm_tuner"] is None),
        "lane_ceiling": ceiling,
        "label_programs_in_window": {str(w): v
                                     for w, v in sorted(in_window.items())},
        "widest_label_program": widest,
        "none_above_the_ceiling": ceiling is not None
        and widest <= ceiling and all(x <= ceiling for x in dispatched),
        "post_batches_in_window": {"spans": len(post_batches),
                                   "sizes": sorted(set(post_batches))[:8]},
        "every_post_batch_full": batches_full,
    }
    if run.trace:       # where a batch's time goes on the host (PERF.md 5)
        stages: dict = {}
        for sp in spans:
            if sp["inside"] and sp["name"] in STAGE_SPANS and (
                    sp["name"] != "farm.batch"
                    or sp["args"].get("kind") == "post"):
                stages.setdefault(sp["name"], []).append(sp["dur_us"] / 1e3)
        checks["stage_median_ms"] = {k: round(stats.median(v), 3)
                                     for k, v in sorted(stages.items())}
    inside = {r["i"]: r for r in measured if "verdicts" in r}
    kinds: dict = {}
    for i, r in inside.items():
        for k, f in enumerate(requests[i]["atx"]):
            post_ok = requests[i]["want"][
                k * atx_stream.ITEMS_PER_ATX + atx_backlog.POST_ITEM]
            if not f["on_device"]:
                kind = "host_rejected"
            else:
                kind = "valid" if post_ok else "failed_on_device"
            kinds.setdefault(kind, []).append((i, k))
    rng = random.Random(f"benchmark/reference/{run.seed}")
    picks = []
    for kind in sorted(kinds):
        rng.shuffle(kinds[kind])
        picks += kinds[kind][:REFERENCE_EACH]
    rest = [p for kind in sorted(kinds) for p in kinds[kind][REFERENCE_EACH:]]
    picks += rng.sample(rest, min(max(REFERENCE_ATXS - len(picks), 0),
                                  len(rest)))

    # of the check batch: every swapped proof, and a few valid ones
    got, tile = box["check_verdicts"], check["tile"]
    valid = [q for q in range(len(got)) if q not in tile]
    check_picks = sorted(tile) + rng.sample(
        valid, min(REFERENCE_EACH, len(valid)))

    def window_one(pick) -> bool:
        i, k = pick
        at = k * atx_stream.ITEMS_PER_ATX + atx_backlog.POST_ITEM
        want = _reference_post_verdict(
            cfg, requests[i]["items"][at].item, k3, post_seed)
        return want == inside[i]["verdicts"][at]

    def check_one(q) -> bool:
        return got[q] == _reference_post_verdict(
            cfg, check["items"][q].item, k3, post_seed)

    t = time.perf_counter()
    with ThreadPoolExecutor(max_workers=8) as ex:
        ref_ok = list(ex.map(window_one, picks))
        check_ok = list(ex.map(check_one, check_picks))
    checks["reference_s"] = time.perf_counter() - t
    checks["reference_atxs_checked"] = len(picks)
    checks["reference_sample"] = {k: min(len(v), REFERENCE_EACH)
                                  for k, v in sorted(kinds.items())}
    checks["reference_verdicts_equal"] = all(ref_ok) and bool(picks)
    checks["reference_sample_covers"] = (
        len(picks) >= min(REFERENCE_ATXS, sum(map(len, kinds.values())))
        and "valid" in kinds and "host_rejected" in kinds)
    widths = lane_tiles(batch * k3, cap)
    programs = {w: check1.get(w, 0) - check0.get(w, 0) for w in check1
                if check1.get(w, 0) != check0.get(w, 0)}
    failed_in = sorted({t for q, t in tile.items() if not got[q]})
    checks["check_batch"] = {
        "proofs": len(got), "tile_widths": widths,
        "label_programs": {str(w): v for w, v in sorted(programs.items())},
        "was_one_full_batch": programs == {
            w: widths.count(w) for w in set(widths)},
        "swapped_by_tile": {str(t): sum(1 for x in tile.values() if x == t)
                            for t in range(len(widths))},
        "tiles_with_a_failed_proof": failed_in,
        "every_tile_failed_one": failed_in == list(range(len(widths))),
        "verdicts_equal_want": got == check["want"],
        "reference_proofs_checked": len(check_picks),
        "reference_verdicts_equal": all(check_ok) and bool(check_picks),
    }
    check_batch_ok = all(checks["check_batch"][k] for k in (
        "was_one_full_batch", "every_tile_failed_one",
        "verdicts_equal_want", "reference_verdicts_equal"))
    moved = {str(k): v for k, v in metrics.runtime_fallbacks.sample().items()
             if v != fallbacks0.get(k, 0)}
    checks["runtime_fallbacks_moved"] = moved
    correct = (not wrong and not box["errors"] and not failed
               and checks["reference_verdicts_equal"]
               and checks["reference_sample_covers"] and check_batch_ok
               and not moved
               and compiled["ok"] and proofs_per_s is not None
               and checks["farm_as_configured"]
               and checks["none_above_the_ceiling"] and batches_full)

    return {
        "correct": correct,
        "attempted": len(measured),
        "failed": failed,
        "end_to_end": end_to_end,
        "program_bytes": shapes.romix_v_bytes(n, widest),
        "checks": checks,
        "setup_parts": {**setup, "compile": win.clock0},
        "trace_data": win.data,
        "window_s": window_s,
        "gap_spans": GAP_SPANS,
        "idle_label": "no farm batch open",
        "spans": spans,
        "counters": {"scrypt_n": n, "k3": k3, "lane_ceiling": ceiling,
                     "label_programs_in_window": in_window,
                     "farm": box["stats"]},
        "generator": {"requests": len(measured), "failed": failed,
                      "steps": steps},
    }
