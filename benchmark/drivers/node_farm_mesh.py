"""A node's OWN verification farm on a four-chip host: ``node_farm``'s
cell with every POST batch lane-sharded over the chips.

The system under test is the same ``verify/farm.VerificationFarm``,
built as ``node/app.py`` builds it (``max_batch`` 256, no tuner, a
fixed ``post_seed``; compared, never set), fed by the same sync workers
(``generators/atx_backlog``). Nothing sets the mesh either: on an
accelerator ``parallel/mesh.auto_mesh`` takes every visible device, so
``post/verifier`` cuts a batch under the MESH's ceiling (chips x
``ops/scrypt.lane_ceiling``) and shards each tile in equal slices, one
a chip. The driver reads that tile rule from the program
(``post/verifier._lane_tiles`` and ``auto_mesh``) and restates none: a
256-proof batch at K3 = 37 is one 16,384-lane program under the rule
this cell was added with (4,096 lanes a chip; the last chip holds only
padding). A rehearsal on the CPU, and only a rehearsal, forces four
host devices (``run.py``), sets ``SPACEMESH_MESH=on`` (the CPU never
shards by itself) and replaces ``ops/scrypt.lane_ceiling`` by the tiny
configuration's ``rehearse_lane_ceiling``, as ``node_farm`` does.

Set-up, the window, ``proofs_per_s`` (``node_farm.step_rate``), the
reference sample of the window's verdicts and the check batch's place
after the window are ``node_farm``'s. A traced run differs in one thing:
the profiler covers only the window's first ``device_trace_seconds``
(the traffic's) and stops while the farm runs on, so no chip's device
trace buffer fills; the program's spans cover all of the window. On
four chips the profiler's stop did not end in the two traced runs whose
device trace ran on for 5.5 s or more (PERF.md section 6). ``correct``
holds every condition of ``node_farm`` with a mesh's ceiling and tile
rule, and besides:

- ``post_verify_mesh_devices`` (the chips the widest label program of
  the last verify flight ran on) equals the cell's ``chips`` after the
  window and after the check batch;
- no label program counted (``post_verify_label_programs_total{lanes}``:
  a program's whole, mesh-wide width) or dispatched is wider than
  ``chips`` x ``lane_ceiling``;
- every POST batch in the window was ``max_batch`` proofs: traced, ``n``
  of every ``farm.batch`` span; untraced, every label program counted in
  the window has a width that a full batch of this traffic leaves under
  the program's tile rule (under the rule above: 16,384 lanes, which a
  batch with fewer than 222 proofs on the device never leaves);
- THE CHECK BATCH carries its swapped indices in the proofs at both ends
  of each CHIP's share of real lanes in each tile, every chip that
  holds real lanes holds a proof that FAILED, and its verdicts are held
  to the generator's ``want`` and, for every swapped proof and a few
  valid ones, to ``lib/reference.verify_post``.

A program that does not record the chips of a verify flight (no
``post_verify_mesh_devices``) cannot be held to its mesh: the driver
exits at once, before any warm-up.
"""

from __future__ import annotations

import asyncio
import collections
import os
import random
import time
from concurrent.futures import ThreadPoolExecutor

from drivers import node_farm as nf
from generators import atx_backlog, atx_stream
from lib import atxpool, shapes, stats, tracewin


def chip_slices(tiles: list, chips_of) -> list:
    """``(first lane, lanes)`` of every chip's slice of every tile, in
    lane order: a tile of ``width`` lanes on k chips is k slices of
    width/k consecutive lanes (the lane axis shards in equal blocks)."""
    out = []
    for at, width in tiles:
        k = chips_of(width)
        out += [(at + c * (width // k), width // k) for c in range(k)]
    return out


def run(run) -> dict:
    import jax

    from spacemesh_tpu.core.signing import EdVerifier
    from spacemesh_tpu.ops import pow as k2pow
    from spacemesh_tpu.ops import scrypt
    from spacemesh_tpu.parallel import mesh as pmesh
    from spacemesh_tpu.post import verifier
    from spacemesh_tpu.post.prover import ProofParams
    from spacemesh_tpu.utils import metrics
    from spacemesh_tpu.verify.farm import FarmClosed, Lane, VerificationFarm

    if not hasattr(metrics, "post_verify_mesh_devices"):
        raise SystemExit(
            "benchmark: this program does not record the chips a verify "
            "flight ran on (utils/metrics.post_verify_mesh_devices), so "
            "the cell cannot hold it to its mesh")
    cfg, tr = run.config, run.traffic
    setup = {"import_and_chip_open_s": time.perf_counter() - run.t_start}
    fallbacks0 = dict(metrics.runtime_fallbacks.sample())
    n, k2, k3 = int(cfg["scrypt_n"]), int(cfg["k2"]), int(cfg["k3"])
    if int(tr["k3"]) != k3:
        raise ValueError("the traffic's k3 is not the configuration's")
    max_batch = int(cfg["farm_max_batch"])
    params = ProofParams(k1=int(cfg["k1"]), k2=k2, k3=k3,
                         pow_difficulty=bytes.fromhex(cfg["pow_difficulty"]))
    if run.rehearse:
        os.environ["SPACEMESH_MESH"] = "on"
        if "rehearse_lane_ceiling" in cfg:
            forced = int(cfg["rehearse_lane_ceiling"])
            scrypt.lane_ceiling = lambda n, devices=None: forced
    window_s, warm_s = run.window_s, float(tr["warm_s"])
    device_s = min(window_s, float(tr["device_trace_seconds"]))

    def mesh_devices() -> int:
        return int(sum(metrics.post_verify_mesh_devices.sample().values()))

    def chips_of(width: int) -> int:
        mesh = pmesh.auto_mesh(width)
        return 1 if mesh is None else mesh.size

    def widths(lanes: int) -> tuple:
        """The label programs ``lanes`` lanes run as, by the program's
        own rule."""
        return tuple(w for _at, w in verifier._lane_tiles(lanes, n))

    # --- the widest shape first: a full batch of proofs in one call ------
    a, workers = int(tr["atx_per_request"]), int(tr["workers"])
    must_fill = bool(tr.get("full_batches", True))
    batch = min(max_batch, 1 << ((workers * a).bit_length() - 1))
    if must_fill and batch != max_batch:
        raise ValueError("full_batches asks for workers x atx_per_request "
                         f">= max_batch {max_batch}")
    t = time.perf_counter()
    made_up = nf._made_up_item(cfg, k2)
    verifier.verify_many([made_up] * batch, params, seed=b"warm-up")
    setup["warm_widest_s"] = time.perf_counter() - t
    ceiling = scrypt.lane_ceiling(n, jax.devices()[:run.chips])
    cap = run.chips * ceiling
    check_tiles = verifier._lane_tiles(batch * k3, n)
    slices = chip_slices(check_tiles, chips_of)

    pool, how = atxpool.load_or_build(cfg, run.cache, nf.log)
    setup["pool_" + ("build_s" if how["built"] else "load_s")] = \
        how["seconds"]
    t = time.perf_counter()
    gen = run.generator().generate(
        run, pool, check=(batch, [w for _at, w in slices]))
    setup["generate_requests_s"] = time.perf_counter() - t
    requests, post_seed, check = \
        gen["requests"], gen["post_seed"], gen["check"]
    lane = Lane[gen["lane"].upper()]

    # --- every other shape the traffic can reach --------------------------
    t = time.perf_counter()
    per_req = [sum(1 for f in r["atx"] if f["on_device"]) for r in requests]
    m = max(batch // a, 1)              # whole requests in a full batch
    warmed = {widths(batch * k3)}
    full_widths: set = set()            # what a full batch of them leaves
    for reqs in ([m] if must_fill else
                 [1 << e for e in range(m.bit_length())]):
        for count in range(reqs * min(per_req), reqs * max(per_req) + 1):
            shape = widths(count * k3) if count else ()
            if reqs == m:
                full_widths |= set(shape)
            if count and shape not in warmed:
                verifier.verify_many([made_up] * count, params,
                                     seed=b"warm-up")
                warmed.add(shape)
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
    nf.log(f"set-up done at {time.perf_counter() - run.t_start:.1f} s; "
           f"the window opens in {warm_s} s")

    win = tracewin.TraceWindow(run.trace, run.fresh_dir("trace"),
                               keep=run.args.keep_trace)
    records: list = []
    box: dict = {"errors": [], "programs": [], "mesh_devices": []}

    def snapshot() -> None:
        box["programs"].append(nf._label_programs(metrics))

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
        # the profiler covers the window's first device_s seconds and
        # stops while the farm runs on: past that a chip's trace buffer
        # fills, and on four chips the profiler's stop then did not end
        marker = win.hold(run.clock, device_s, at=t0)
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
                snapshot()
            box["clock1"] = run.clock.snapshot()
            box["mesh_devices"].append(mesh_devices())
            nf.log("the window closed: draining, then the check batch")
            # after the window: the workers finish what they have out,
            # then the check batch goes through the same, empty, farm
            stopping = True
            for task in (await asyncio.wait(tasks))[0]:
                if task.exception() is not None:
                    raise RuntimeError(
                        "the farm raised") from task.exception()
            snapshot()
            box["check_verdicts"] = list(await asyncio.gather(*(
                farm.submit(it, lane) for it in check["items"])))
            snapshot()
            box["mesh_devices"].append(mesh_devices())
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
    nf.log("check batch back; the profiler's data collected"
           if run.trace else "check batch back")
    t0 = box["t0"]
    t_end = t0 + window_s
    win.t1 = win.t0 + window_s          # the host spans' window: all of it
    compiled = run.clock.window_report(win.clock0, box["clock1"], window_s)

    # --- reduce the workers' record ---------------------------------------
    measured = [r for r in records if "done" in r and t0 <= r["done"] <= t_end]
    wrong = [r["i"] for r in records
             if "verdicts" in r and r["verdicts"] != requests[r["i"]]["want"]]
    failed = sum(1 for r in measured if "verdicts" not in r)
    done_at = [(r["done"], requests[r["i"]]["n_atx"]) for r in measured
               if "verdicts" in r]
    proofs_per_s, steps = nf.step_rate(done_at)
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
    if not must_fill:
        batches_full = True
    elif run.trace:
        batches_full = bool(post_batches) and all(
            x == batch for x in post_batches)
    else:
        batches_full = bool(in_window) and set(in_window) <= full_widths
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
        "lane_ceiling_per_chip": ceiling,
        "mesh_ceiling": cap,
        "post_verify_mesh_devices": box["mesh_devices"],
        "mesh_devices_equal_chips": bool(box["mesh_devices"]) and all(
            d == run.chips for d in box["mesh_devices"]),
        "label_programs_in_window": {str(w): v
                                    for w, v in sorted(in_window.items())},
        "full_batch_widths": sorted(full_widths),
        "widest_label_program": widest,
        "none_above_the_ceiling": widest <= cap
        and all(x <= cap for x in dispatched),
        "post_batches_in_window": {"spans": len(post_batches),
                                   "sizes": sorted(set(post_batches))[:8]},
        "every_post_batch_full": batches_full,
    }
    if run.trace:       # where a batch's time goes on the host (PERF.md 5)
        stages: dict = {}
        for sp in spans:
            if sp["inside"] and sp["name"] in nf.STAGE_SPANS and (
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
        picks += kinds[kind][:nf.REFERENCE_EACH]
    rest = [p for kind in sorted(kinds)
            for p in kinds[kind][nf.REFERENCE_EACH:]]
    picks += rng.sample(rest, min(max(nf.REFERENCE_ATXS - len(picks), 0),
                                  len(rest)))

    # of the check batch: every swapped proof, and a few valid ones
    got, where = box["check_verdicts"], check["tile"]   # proof -> slice
    valid = [q for q in range(len(got)) if q not in where]
    check_picks = sorted(where) + rng.sample(
        valid, min(nf.REFERENCE_EACH, len(valid)))

    def window_one(pick) -> bool:
        i, k = pick
        at = k * atx_stream.ITEMS_PER_ATX + atx_backlog.POST_ITEM
        want = nf._reference_post_verdict(
            cfg, requests[i]["items"][at].item, k3, post_seed)
        return want == inside[i]["verdicts"][at]

    def check_one(q) -> bool:
        return got[q] == nf._reference_post_verdict(
            cfg, check["items"][q].item, k3, post_seed)

    t = time.perf_counter()
    with ThreadPoolExecutor(max_workers=8) as ex:
        ref_ok = list(ex.map(window_one, picks))
        check_ok = list(ex.map(check_one, check_picks))
    checks["reference_s"] = time.perf_counter() - t
    checks["reference_atxs_checked"] = len(picks)
    checks["reference_sample"] = {k: min(len(v), nf.REFERENCE_EACH)
                                  for k, v in sorted(kinds.items())}
    checks["reference_verdicts_equal"] = all(ref_ok) and bool(picks)
    checks["reference_sample_covers"] = (
        len(picks) >= min(nf.REFERENCE_ATXS, sum(map(len, kinds.values())))
        and "valid" in kinds and "host_rejected" in kinds)
    tile_widths = [w for _at, w in check_tiles]
    programs = {w: check1.get(w, 0) - check0.get(w, 0) for w in check1
                if check1.get(w, 0) != check0.get(w, 0)}
    # a slice holds real lanes where it starts below the batch's last
    holding = [s for s, (lo, _w) in enumerate(slices) if lo < batch * k3]
    failed_in = sorted({s for q, s in where.items() if not got[q]})
    checks["check_batch"] = {
        "proofs": len(got), "tile_widths": tile_widths,
        "chip_slices": slices,
        "label_programs": {str(w): v for w, v in sorted(programs.items())},
        "was_one_full_batch": programs == dict(
            collections.Counter(tile_widths)),
        "swapped_by_slice": {str(s): sum(1 for x in where.values() if x == s)
                             for s in range(len(slices))},
        "slices_with_real_lanes": holding,
        "slices_with_a_failed_proof": failed_in,
        "every_chip_failed_one": bool(holding) and failed_in == holding,
        "verdicts_equal_want": got == check["want"],
        "reference_proofs_checked": len(check_picks),
        "reference_verdicts_equal": all(check_ok) and bool(check_picks),
    }
    check_batch_ok = all(checks["check_batch"][k] for k in (
        "was_one_full_batch", "every_chip_failed_one",
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
               and checks["mesh_devices_equal_chips"]
               and checks["none_above_the_ceiling"] and batches_full)

    return {
        "correct": correct,
        "attempted": len(measured),
        "failed": failed,
        "end_to_end": end_to_end,
        # V on the fullest chip: the widest program's slice of it
        "program_bytes": shapes.romix_v_bytes(
            n, widest // chips_of(widest)) if widest else 0,
        "checks": checks,
        "setup_parts": {**setup, "compile": win.clock0},
        "trace_data": win.data,
        "window_s": window_s,
        "gap_spans": nf.GAP_SPANS,
        "idle_label": "no farm batch open",
        "spans": spans,
        "counters": {"scrypt_n": n, "k3": k3, "chips": run.chips,
                     "lane_ceiling": ceiling, "mesh_ceiling": cap,
                     "label_programs_in_window": in_window,
                     "farm": box["stats"]},
        "generator": {"requests": len(measured), "failed": failed,
                      "steps": steps},
    }
