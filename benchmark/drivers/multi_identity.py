"""Many smesher identities initialized together on one node, for a
window of time.

The system under test is ``post/initializer.initialize_many``: what
``python -m spacemesh_tpu.post init --identities`` runs, ONE
``runtime/scheduler.TenantScheduler`` with one tenant and one packed
``submit_init`` per identity, over the configuration's geometry, with
the program's defaults for everything the configuration does not name
(the pack width among them: read back, never set, outside a rehearsal).
Every identity is submitted at t0; the driver sets the function's
``stop`` event when the window ends, and every job then checkpoints what
is durable while the packs in flight drain.

Window: opens at the first retired pack (the warm-up: one pack through
compose -> dispatch -> retire, the label program compiled or fetched),
lasts ``--seconds``. ``labels_per_s`` is taken from the durable cursors:
the SUM of the tenants' ``labels_written`` as their metadata files hold
it, read after each completed tenant checkpoint (the counter
``runtime_tenant_checkpoints_total`` moved), between two advances
inside the window by ``drivers/init_pipeline.cursor_rate`` with the
pack width as the batch.
"""

from __future__ import annotations

import hashlib
import json
import random
import threading
import time
from pathlib import Path

from drivers.init_pipeline import cursor_rate
from lib import reference, shapes, tracewin

GAP_SPANS = ("runtime.pack.dispatch", "runtime.pack.retire",
             "runtime.segment", "runtime.checkpoint", "romix.dispatch")
META_FILE = "postdata_metadata.json"


def _derive(fixture_seed: int, seed: int, i: int, tag: str) -> bytes:
    return hashlib.sha256(
        f"benchmark/multi-identity/{fixture_seed}/{seed}/{i}/{tag}"
        .encode()).digest()


def durable_cursor(data_dir: Path) -> int:
    """``labels_written`` as the identity's metadata file holds it (0
    before its first save). Read as plain JSON: ``PostMetadata.load``
    also sweeps staging files, which a save in flight is using."""
    try:
        return int(json.loads((data_dir / META_FILE).read_text())
                   ["labels_written"])
    except FileNotFoundError:
        return 0


class CursorWatch(threading.Thread):
    """Polls the tenants' durable cursors. An advance is recorded when
    the program's tenant-checkpoint counter has moved (a save is
    complete, so a restart would read it) and the sum of the tenants'
    on-disk ``labels_written`` is higher than at the last record."""

    def __init__(self, dirs, counter, period_s: float = 0.001):
        super().__init__(name="bench-cursors", daemon=True)
        self.dirs = list(dirs)
        self.counter = counter
        self.period_s = period_s
        self.advances: list = []     # (perf_counter, summed cursor)
        self._halt = threading.Event()

    def _count(self) -> float:
        return float(sum(self.counter.sample().values()))

    def run(self) -> None:
        seen_n, seen_cursor = self._count(), 0
        while not self._halt.is_set():
            n = self._count()
            if n != seen_n:
                seen_n, t = n, time.perf_counter()
                cur = sum(durable_cursor(d) for d in self.dirs)
                if cur > seen_cursor:
                    seen_cursor = cur
                    self.advances.append((t, cur))
            time.sleep(self.period_s)

    def halt(self) -> None:
        self._halt.set()
        self.join(timeout=5)


def _check_tenant(d: Path, meta, commitment: bytes, neighbour: bytes,
                  segments: list, n: int, rng: random.Random) -> dict:
    """The tenant's store against the plain reference: both ends of
    each of its segments and 4 seeded indices below its cursor equal
    scrypt under its own commitment and not under its neighbour's; its
    VRF nonce is the argmin over the stored [0, cursor)."""
    from spacemesh_tpu.post.data import LabelStore

    cursor = meta.labels_written
    lb = reference.LABEL_BYTES
    out = {"cursor": cursor,
           "meta_on_disk_equals_frontier":
               cursor == (segments[-1][1] if segments else 0),
           "bytes_on_disk_equal_cursor": sum(
               p.stat().st_size for p in d.glob("postdata_*.bin"))
           == cursor * lb}
    if not cursor:
        out["vrf_nonce_is_minimum"] = meta.vrf_nonce is None
        return out
    store = LabelStore(d, meta)
    try:
        raw = store.read_labels(0, cursor)
    finally:
        store.close()
    sample = {i for s, e in segments if e <= cursor for i in (s, e - 1)}
    sample |= set(rng.sample(range(cursor), min(4, cursor)))
    own = all(raw[i * lb:(i + 1) * lb] == reference.label(commitment, i, n)
              for i in sorted(sample))
    other = any(raw[i * lb:(i + 1) * lb] == reference.label(neighbour, i, n)
                for i in sorted(sample))
    out["sampled"] = len(sample)
    out["labels_equal_own_scrypt"] = own and not other
    out["vrf_nonce_is_minimum"] = \
        meta.vrf_nonce == reference.vrf_min_index(raw)
    return out


def run(run) -> dict:
    from spacemesh_tpu.ops import scrypt
    from spacemesh_tpu.post import initializer
    from spacemesh_tpu.utils import metrics

    cfg = run.config
    seconds = run.window_s
    n = int(cfg["scrypt_n"])
    count = int(cfg["identities"])
    root = run.fresh_dir("post")
    fixture = int(cfg["fixture_seed"])
    ids = [(root / f"id{i:02d}", _derive(fixture, run.seed, i, "node"),
            _derive(fixture, run.seed, i, "commitment"))
           for i in range(count)]
    fallbacks0 = dict(metrics.runtime_fallbacks.sample())
    setup = {"import_and_chip_open_s": time.perf_counter() - run.t_start}

    win = tracewin.TraceWindow(run.trace, run.fresh_dir("trace"),
                               keep=run.args.keep_trace)
    stop = threading.Event()
    state = {"marker": None, "retired_in_window": 0}
    segments: list = [[] for _ in ids]     # per tenant: (start, end)

    def progress(i: int, done: int, total: int) -> None:
        # the packer's thread, after each retired segment; the first one
        # opens the window, which ends by setting ``stop``
        prev = segments[i][-1][1] if segments[i] else 0
        segments[i].append((prev, done))
        if state["marker"] is None:
            state["marker"] = win.hold(run.clock, seconds, on_end=stop.set)
        elif win.t0 is not None and win.t1 is None:
            state["retired_in_window"] += 1

    kw = {}
    if cfg.get("pack_lanes") is not None:    # a rehearsal's small packs
        kw["pack_lanes"] = int(cfg["pack_lanes"])
    watch = CursorWatch([d for d, _, _ in ids],
                        metrics.runtime_tenant_checkpoints)
    watch.start()
    try:
        res = initializer.initialize_many(
            ids, num_units=int(cfg["num_units"]),
            labels_per_unit=int(cfg["labels_per_unit"]), scrypt_n=n,
            max_file_size=int(cfg["max_file_size"]), progress=progress,
            stop=stop, **kw)
    finally:
        watch.halt()
    marker = state["marker"]
    if marker is None:
        raise RuntimeError("the scheduler retired no pack")
    marker.join(timeout=seconds + 600)
    if marker.is_alive():
        raise RuntimeError("the window thread did not end")
    win.finish()
    setup["to_first_retired_pack_s"] = win.t0 - run.t_start \
        - setup["import_and_chip_open_s"]
    compiled = run.clock.window_report(win.clock0, win.clock1, seconds)

    # --- the end-to-end metric, from the durable cursors ---------------
    t_lo, t_hi = win.t0, win.t0 + seconds
    inside = [(t, c) for t, c in watch.advances if t_lo <= t <= t_hi]
    labels_per_s, rate_from = cursor_rate(inside, res.pack_lanes)
    end_to_end = {"setup_s": (win.t0 - run.t_start, "s"),
                  "labels_per_s": (labels_per_s, "labels/s")}

    # --- correct: outside the window ------------------------------------
    rng = random.Random(run.seed)
    tenants = [
        _check_tenant(d, meta, commit, ids[(i + 1) % count][2],
                      segments[i], n, rng)
        for i, ((d, _, commit), meta) in enumerate(zip(ids, res.metas))]
    ceiling = scrypt.lane_ceiling(n) * run.chips
    checks: dict = {
        "pack_lanes": res.pack_lanes,
        "tenants_registered": res.tenants,
        "widest_pack": res.widest_pack,
        "chip_ceiling": ceiling,
        "all_tenants_registered": res.tenants >= count,
        "no_pack_over_the_ceiling": res.widest_pack <= ceiling,
        "tenants": tenants,
        "summed_cursor": sum(t["cursor"] for t in tenants),
    }
    tenants_ok = all(v for t in tenants for k, v in t.items()
                     if k not in ("cursor", "sampled"))
    moved = {str(k): v for k, v in metrics.runtime_fallbacks.sample().items()
             if v != fallbacks0.get(k, 0)}
    checks["runtime_fallbacks_moved"] = moved
    checks["compiles_in_window"] = compiled
    checks["cursor_advances_in_window"] = [
        (round(t - t_lo, 4), c) for t, c in inside]
    checks["labels_per_s_between"] = rate_from
    correct = (tenants_ok and checks["all_tenants_registered"]
               and checks["no_pack_over_the_ceiling"]
               and not moved and compiled["ok"]
               and (labels_per_s is not None or run.trace))

    return {
        "correct": correct,
        "attempted": state["retired_in_window"],
        "failed": 0,
        "end_to_end": end_to_end,
        "program_bytes": shapes.romix_v_bytes(
            n, -(-res.pack_lanes // run.chips)),
        "checks": checks,
        "setup_parts": {**setup, "compile": win.clock0},
        "trace_data": win.data,
        "window_s": win.t1 - win.t0,
        "gap_spans": GAP_SPANS,
        "idle_label": "no runtime span open",
        "spans": win.spans(),
        "counters": {"pack_lanes": res.pack_lanes, "tenants": res.tenants,
                     "widest_pack": res.widest_pack, "scrypt_n": n,
                     "chips": run.chips},
        "generator": {},
    }
