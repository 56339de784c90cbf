"""POST initialization, as a smesher runs it, for a window of time.

The system under test is ``post/initializer``: ``open_or_create_meta``
+ ``Initializer(...).run()`` (what ``initializer.initialize`` and
``python -m spacemesh_tpu.post init`` do) over the configuration's
identity geometry, with the CLI's defaults for everything the
configuration does not name. The driver keeps the ``Initializer``
object only to call its public ``stop()`` when the window ends.

Window: opens at the first retired batch (the warm-up: one batch
through dispatch -> fetch -> write, every program compiled or fetched),
lasts ``--seconds``. ``labels_per_s`` is taken from the durable cursor
(the metadata's ``labels_written``, read after each completed metadata
save): labels between two advances inside the window over the time
between them, so a window edge that cuts a batch does not quantise the
rate (:func:`cursor_rate` says which two).
"""

from __future__ import annotations

import hashlib
import random
import threading
import time
from pathlib import Path

from lib import reference, shapes, tracewin

GAP_SPANS = ("init.dispatch", "init.fetch", "init.write_stall",
             "init.save_meta", "init.drain_stall", "romix.dispatch")


def _derive(seed: int, tag: str) -> bytes:
    return hashlib.sha256(f"benchmark/init/{seed}/{tag}".encode()).digest()


def cursor_rate(advances: list, batch: int):
    """Labels per second of the durable cursor, from its advances
    ``[(seconds, labels_written)]`` inside the window.

    A save checkpoints whatever prefix the writer pool has flushed at
    that instant, and the pool writes one shard stripe at a time: on
    four chips the newest batch is in the cursor with three of its four
    stripes in one save and all four in the next (one chip has one
    stripe, so the offset never varies there). Taken between the first
    and the last advance, the rate moves by a stripe over the window
    from run to run (8,192 labels over 38 s = 1.8% on four chips)
    though the work is the same. So the two advances are the pair
    FARTHEST APART THAT LEFT THE CURSOR AT THE SAME OFFSET WITHIN A
    BATCH: whole batches lie between them, and the times of saves are
    paced by the device. With no such pair: the first and the last.
    -> (labels/s | None, {"from", "to", "same_offset"})"""
    best = None
    for i, (ti, ci) in enumerate(advances):
        for j in range(len(advances) - 1, i, -1):
            tj, cj = advances[j]
            if cj > ci and (cj - ci) % batch == 0:
                if best is None or tj - ti > best[0]:
                    best = (tj - ti, cj - ci, i, j)
                break
    same = best is not None
    if best is None and len(advances) >= 2:
        (t0, c0), (t1, c1) = advances[0], advances[-1]
        best = (t1 - t0, c1 - c0, 0, len(advances) - 1)
    if best is None or best[0] <= 0:
        return None, {}
    return best[1] / best[0], {"from": best[2], "to": best[3],
                               "same_offset": same}


class CursorWatch(threading.Thread):
    """Polls the durable cursor. An advance is recorded when the
    program's metadata-save counter has moved (the save is complete, so
    a restart would read it) and ``labels_written`` is higher than at
    the last record."""

    def __init__(self, meta, saves_counter, period_s: float = 0.001):
        super().__init__(name="bench-cursor", daemon=True)
        self.meta = meta
        self.saves = saves_counter
        self.period_s = period_s
        self.advances: list = []     # (perf_counter, labels_written)
        self._halt = threading.Event()

    def _saves(self) -> float:
        return float(sum(self.saves.sample().values()))

    def run(self) -> None:
        seen_saves = self._saves()
        seen_cursor = self.meta.labels_written
        while not self._halt.is_set():
            n = self._saves()
            if n != seen_saves:
                seen_saves = n
                cur = self.meta.labels_written
                if cur > seen_cursor:
                    seen_cursor = cur
                    self.advances.append((time.perf_counter(), cur))
            time.sleep(self.period_s)

    def halt(self) -> None:
        self._halt.set()
        self.join(timeout=5)


def run(run) -> dict:
    from spacemesh_tpu.post import initializer
    from spacemesh_tpu.post.data import LabelStore, PostMetadata
    from spacemesh_tpu.utils import metrics

    cfg = run.config
    seconds = run.window_s
    n = int(cfg["scrypt_n"])
    batch = int(cfg["init_batch"])
    node_id, commitment = _derive(run.seed, "node"), _derive(run.seed,
                                                             "commitment")
    data_dir = run.fresh_dir("post")
    fallbacks0 = dict(metrics.runtime_fallbacks.sample())
    setup = {"import_and_chip_open_s": time.perf_counter() - run.t_start}

    meta = initializer.open_or_create_meta(
        data_dir, node_id=node_id, commitment=commitment,
        num_units=int(cfg["num_units"]),
        labels_per_unit=int(cfg["labels_per_unit"]), scrypt_n=n,
        max_file_size=int(cfg["max_file_size"]))
    win = tracewin.TraceWindow(run.trace, run.fresh_dir("trace"),
                               keep=run.args.keep_trace)
    state = {"marker": None, "retired_in_window": 0}
    init_box: list = []

    def progress(done: int, total: int) -> None:
        # main thread, after every retire (the fetch frontier); the
        # first one opens the window, which ends with stop()
        if state["marker"] is None:
            state["marker"] = win.hold(run.clock, seconds,
                                       on_end=init_box[0].stop)
        elif win.t0 is not None and win.t1 is None:
            state["retired_in_window"] += 1

    kw = {}
    for key in ("inflight", "writers"):
        if cfg.get(key) is not None:
            kw[key] = int(cfg[key])
    init = initializer.Initializer(data_dir, meta, batch_size=batch,
                                   progress=progress, **kw)
    init_box.append(init)
    watch = CursorWatch(meta, metrics.post_pipeline_meta_saves)
    watch.start()
    try:
        res = init.run()
    finally:
        watch.halt()
    marker = state["marker"]
    if marker is None:
        raise RuntimeError("init retired no batch")
    marker.join(timeout=seconds + 600)
    if marker.is_alive():
        raise RuntimeError("the window thread did not end")
    win.finish()
    setup["to_first_retired_batch_s"] = win.t0 - run.t_start \
        - setup["import_and_chip_open_s"]
    compiled = run.clock.window_report(win.clock0, win.clock1,
                                       seconds)

    # --- the end-to-end metric, from the cursor ------------------------
    t_lo, t_hi = win.t0, win.t0 + seconds
    inside = [(t, c) for t, c in watch.advances if t_lo <= t <= t_hi]
    labels_per_s, rate_from = cursor_rate(inside, batch)
    end_to_end = {"setup_s": (win.t0 - run.t_start, "s"),
                  "labels_per_s": (labels_per_s, "labels/s")}

    # --- correct: outside the window ------------------------------------
    checks: dict = {}
    cursor = meta.labels_written
    on_disk = PostMetadata.load(data_dir)
    checks["cursor"] = cursor
    checks["meta_on_disk_equals_cursor"] = on_disk.labels_written == cursor
    disk_bytes = sum(p.stat().st_size
                     for p in Path(data_dir).glob("postdata_*.bin"))
    checks["bytes_on_disk_equal_cursor"] = \
        disk_bytes == cursor * reference.LABEL_BYTES
    store = LabelStore(data_dir, meta)
    try:
        raw = store.read_labels(0, cursor) if cursor else b""
    finally:
        store.close()
    rng = random.Random(run.seed)
    sample = rng.sample(range(cursor), min(32, cursor))
    lb = reference.LABEL_BYTES
    checks["sampled_labels_equal_hashlib_scrypt"] = all(
        raw[i * lb:(i + 1) * lb] == reference.label(commitment, i, n)
        for i in sample)
    host_min = reference.vrf_min_index(raw) if cursor else -1
    checks["vrf_nonce_equals_host_minimum"] = \
        host_min == res.vrf_nonce == on_disk.vrf_nonce
    checks["vrf_label_equals_hashlib_scrypt"] = cursor > 0 and (
        raw[host_min * lb:(host_min + 1) * lb]
        == reference.label(commitment, host_min, n))
    devices_used = int(sum(metrics.post_mesh_devices.sample().values()))
    checks["post_mesh_devices_equals_chips"] = devices_used == run.chips
    moved = {str(k): v for k, v in metrics.runtime_fallbacks.sample().items()
             if v != fallbacks0.get(k, 0)}
    checks["runtime_fallbacks_moved"] = moved
    checks["compiles_in_window"] = compiled
    checks["cursor_advances_in_window"] = [
        (round(t - t_lo, 4), c) for t, c in inside]
    checks["labels_per_s_between"] = rate_from
    correct = (all(v for k, v in checks.items()
                   if k.endswith(("_cursor", "_scrypt", "_minimum",
                                  "_chips")))
               and not moved and compiled["ok"]
               and (labels_per_s is not None or run.trace))

    stats = res.stats.as_dict() if res.stats else {}
    lanes_per_chip = -(-batch // run.chips)
    return {
        "correct": correct,
        "attempted": state["retired_in_window"],
        "failed": 0,
        "end_to_end": end_to_end,
        "program_bytes": shapes.romix_v_bytes(n, lanes_per_chip),
        "checks": checks,
        "setup_parts": {**setup, "compile": win.clock0},
        "trace_data": win.data,
        "window_s": win.t1 - win.t0,
        "gap_spans": GAP_SPANS,
        "idle_label": "no init span open",
        "spans": win.spans(),
        "counters": {"pipeline_stats_whole_run": stats,
                     "scrypt_n": n, "batch": batch, "chips": run.chips},
        "generator": {},
    }
