"""A backlog of ATXs for an in-process farm: the requests of one run as
farm request OBJECTS (no wire), built from ``generators/atx_stream``'s
ATX builder, so an ATX is the same four items in the same order
(signature, poet membership, POST proof, k2pow witness) with the same
ways of being invalid. Parameters (traffic file):

  loop              "closed" only: each worker sends its next request
                    when the last returned
  workers           sync workers, one request outstanding each
  atx_per_request   ATXs in one request
  closed_requests_per_worker_per_s
                    requests prepared per worker per second of run (an
                    upper bound on what the system can take)
  lane              "gossip" | "sync" | "block"
  k3                the verifier's K3 (the farm's ``post_params``)
  invalid_share     share of ATXs made invalid: ATX j of the run is
                    invalid when j mod round(1/share) is half of that
  invalid_modes     which ways, cycled (``atx_stream.MODES``)
  warm_s            seconds of the same traffic before the window

Requests are taken from ONE queue in order, whichever worker is free:
ATX j of a run takes pool proof j mod len(pool), so the proofs
outstanding at any moment are consecutive in the pool and two copies of
one proof are in flight together only if more than len(pool) ATXs are
outstanding.

Besides the timed traffic the generator makes ONE CHECK BATCH, for the
driver to send through the same farm after the window has closed
(:func:`check_batch`): the POST proofs of the cycle's next ATXs, all of
which reach the device, with a swapped index (the one way of
``atx_stream.MODES`` that fails ON the device) in the proofs whose
failing lane lies lowest and highest in each lane tile of the batch.
None of the traffic's own invalid ATXs fails on the device (three ways
are rejected on the host, two leave the POST proof valid), so a wrong
verdict from one tile of a batch would pass every comparison of the
timed verdicts; the check batch is what holds each tile to the plain
reference, and it is outside the window so that the timed mix stays the
traffic file's.
"""

from __future__ import annotations

import bisect
import itertools
import json
import random

from generators import atx_stream
from lib import reference

POST_ITEM = 2       # position of the POST proof among an ATX's items
# swapped proofs at each end of each lane tile of the check batch
CHECK_EACH_END = 2


def check_batch(frag, pool: dict, seed: int, j0: int, k3: int,
                post_seed: bytes, size: int, widths: list) -> dict:
    """``size`` POST proofs, those of ATXs ``j0``... of the cycle, as ONE
    farm batch whose lanes the verifier cuts into tiles of ``widths``
    lanes: proof q holds lanes q x K3 onwards (every proof reaches the
    device), and its swapped index, where it carries one, is the lane
    at that index's place in the verifier's K3 subset. In each tile the
    ``CHECK_EACH_END`` proofs whose swap lane is lowest and those whose
    is highest are swapped: the tile's first and last lanes, near
    enough. -> {"items": [POST request objects], "want": [bool],
    "tile": {position: tile of its failing lane}}"""
    from spacemesh_tpu.verifyd import protocol

    proofs, idents = pool["proofs"], pool["identities"]
    edges = list(itertools.accumulate(widths))
    lane_of = {}
    for q in range(size):
        p = proofs[(j0 + q) % len(proofs)]
        idx = list(p["indices"])
        idx[p["swap_pos"]] = p["swap_index"]
        sampled = reference.k3_subset(
            idx, k3, post_seed, bytes.fromhex(p["challenge"]),
            bytes.fromhex(idents[p["identity"]]["node_id"]))
        if p["swap_index"] in sampled:
            lane_of[q] = q * len(sampled) + sampled.index(p["swap_index"])
    tile = {}
    for t in range(len(widths)):
        inside = sorted((lane, q) for q, lane in lane_of.items()
                        if bisect.bisect_right(edges, lane) == t)
        for _lane, q in inside[:CHECK_EACH_END] + inside[-CHECK_EACH_END:]:
            tile[q] = t
    items, want = [], []
    for q in range(size):
        fr, w, _f = frag.atx(seed, j0 + q,
                             "swapped_index" if q in tile else None, k3,
                             post_seed)
        items.append(protocol.request_from_doc(json.loads(fr[POST_ITEM])))
        want.append(w[POST_ITEM])
    return {"items": items, "want": want, "tile": tile}


def generate(run, pool: dict, check: tuple | None = None) -> dict:
    """-> {"requests": [...], "post_seed", "k3", "workers", "lane"} and,
    where ``check`` = (proofs, tile widths) is given, "check": that
    :func:`check_batch`. A request: {"items": [farm request objects,
    ``ITEMS_PER_ATX`` an ATX], "n_atx", "want": [bool an item], "atx":
    [facts an ATX]}."""
    from spacemesh_tpu.verifyd import protocol

    tr, cfg, seed = run.traffic, run.config, run.seed
    if tr["loop"] != "closed":
        raise ValueError(f"unknown loop {tr['loop']!r}")
    rng = random.Random(f"benchmark/atx-backlog/{seed}")
    frag = atx_stream._Pool(pool, cfg)
    k3 = int(tr["k3"])
    post_seed = atx_stream._h(seed, "k3-seed")
    a, workers = int(tr["atx_per_request"]), int(tr["workers"])
    span_s = float(tr["warm_s"]) + run.window_s
    count = workers * (int(float(tr["closed_requests_per_worker_per_s"])
                           * span_s) + 2)
    modes = list(tr["invalid_modes"])
    for m in modes:
        if m not in atx_stream.MODES:
            raise ValueError(f"unknown invalid mode {m!r}")
    every = round(1 / float(tr["invalid_share"])) \
        if float(tr["invalid_share"]) > 0 and modes else 0
    # where the pool's cycle starts is the seed's: every run verifies
    # the same proofs, in an order of its own
    first = rng.randrange(len(frag))
    requests = []
    j = bad = 0
    for _ in range(count):
        items, want, facts = [], [], []
        for _ in range(a):
            mode = None
            if every and j % every == every // 2:
                mode = modes[bad % len(modes)]
                bad += 1
            fr, w, f = frag.atx(seed, first + j, mode, k3, post_seed)
            items.extend(protocol.request_from_doc(json.loads(x))
                         for x in fr)
            want.extend(w)
            facts.append(f)
            j += 1
        requests.append({"items": items, "n_atx": a, "want": want,
                         "atx": facts})
    out = {"requests": requests, "post_seed": post_seed, "k3": k3,
           "workers": workers, "lane": tr["lane"]}
    if check is not None:
        out["check"] = check_batch(frag, pool, seed, first + j, k3,
                                   post_seed, *check)
    return out
