"""The one general generator of ATX verification traffic.

Reads a traffic mix's parameters and ``--seed`` and returns the
requests of one run: who sends what, when, and what every verdict has
to be. One ATX is four items, in this order: the identity's signature
over a fresh message, the poet membership of its challenge, the POST
proof, and the proof's k2pow witness. Parameters (traffic file):

  loop              "open" (arrivals on a schedule) | "closed" (each
                    client sends its next request when the last returned)
  rate_atx_per_s    open loop: offered ATXs per second, a fixed number
  arrivals          "poisson_fixed_count": a Poisson process GIVEN its
                    count, i.e. round(rate x seconds / atx_per_request)
                    request times drawn uniformly over the window and
                    sorted (and likewise over the warm-up), so every
                    seed offers the window the same amount of work; the
                    count does not vary as a free Poisson count would
  clients           number of client identities
  client_zipf_s     open loop: client i gets a share ~ 1/(i+1)^s
  atx_per_request   ATXs in one HTTP request
  lane              "gossip" | "sync" | "block"
  k3                the verifier's K3 for this regime (server parameter)
  invalid_share     share of ATXs made invalid
  invalid_modes     which ways, cycled: other_challenge, index_out_of_range,
                    bad_pow_nonce, forged_signature, wrong_leaf, swapped_index
  warm_s            seconds of the same traffic before the window
  drain_s           open loop: how long after the window a request may
                    still complete before it counts as failed
  closed_requests_per_client_per_s
                    closed loop: bodies prepared per client per second
                    of run (an upper bound on what the system can take)

ATX j of a run takes pool proof j mod len(pool), in order, so two
copies of one proof are in flight together only if more than len(pool)
ATXs are outstanding.
"""

from __future__ import annotations

import hashlib
import json
import random

from lib import reference

ITEMS_PER_ATX = 4
MODES = ("other_challenge", "index_out_of_range", "bad_pow_nonce",
         "forged_signature", "wrong_leaf", "swapped_index")


def _h(seed: int, tag: str) -> bytes:
    return hashlib.sha256(f"benchmark/atx/{seed}/{tag}".encode()).digest()


class _Pool:
    """Per-proof wire fragments, serialized once."""

    def __init__(self, pool: dict, cfg: dict):
        from spacemesh_tpu.core.signing import EdSigner

        self.pool = pool
        self.cfg = cfg
        self.total = int(pool["total_labels"])
        self.diff = bytes.fromhex(cfg["pow_difficulty"])
        self.signers = [EdSigner(seed=bytes.fromhex(i["key"]))
                        for i in pool["identities"]]
        self._valid = [self._fragments(p) for p in pool["proofs"]]

    def __len__(self) -> int:
        return len(self.pool["proofs"])

    def post_doc(self, p: dict, challenge: str | None = None,
                 indices: list | None = None,
                 pow_nonce: int | None = None) -> dict:
        ident = self.pool["identities"][p["identity"]]
        return {"kind": "post",
                "challenge": challenge or p["challenge"],
                "node_id": ident["node_id"],
                "commitment": ident["commitment"],
                "scrypt_n": int(self.cfg["scrypt_n"]),
                "total_labels": self.total,
                "proof": {"nonce": p["nonce"],
                          "indices": indices or p["indices"],
                          "pow_nonce": (p["pow_nonce"] if pow_nonce is None
                                        else pow_nonce),
                          "k2": int(self.cfg["k2"])}}

    def pow_doc(self, p: dict, challenge: str | None = None,
                pow_nonce: int | None = None) -> dict:
        ident = self.pool["identities"][p["identity"]]
        return {"kind": "pow", "challenge": challenge or p["challenge"],
                "node_id": ident["node_id"],
                "difficulty": self.cfg["pow_difficulty"],
                "nonce": p["pow_nonce"] if pow_nonce is None else pow_nonce}

    def membership_doc(self, p: dict, member: str | None = None) -> dict:
        return {"kind": "membership",
                "member": member or p["member"],
                "root": self.pool["poet"]["root"],
                "leaf_count": self.pool["poet"]["leaf_count"],
                "proof": {"leaf_index": p["leaf_index"],
                          "nodes": p["leaf_nodes"]}}

    def _fragments(self, p: dict) -> tuple:
        return (json.dumps(self.membership_doc(p)),
                json.dumps(self.post_doc(p)), json.dumps(self.pow_doc(p)))

    def atx(self, seed: int, j: int, mode: str | None, k3: int,
            post_seed: bytes):
        """-> (four JSON fragments, four expected verdicts, facts)"""
        from spacemesh_tpu.core.signing import Domain

        k = j % len(self)
        p = self.pool["proofs"][k]
        ident = self.pool["identities"][p["identity"]]
        signer = self.signers[p["identity"]]
        msg = _h(seed, f"msg-{j}")
        sig = signer.sign(Domain.ATX, msg)
        member, post, pow_ = self._valid[k]
        want = [True, True, True, True]
        on_device = True          # does its POST item reach the device
        if mode == "forged_signature":
            msg = _h(seed, f"forged-{j}")
            want[0] = False
        elif mode == "wrong_leaf":
            member = json.dumps(self.membership_doc(
                p, member=_h(seed, f"not-a-member-{j}").hex()))
            want[1] = False
        elif mode == "other_challenge":
            ch = _h(seed, f"other-challenge-{j}")
            post = json.dumps(self.post_doc(p, challenge=ch.hex()))
            pow_ = json.dumps(self.pow_doc(p, challenge=ch.hex()))
            pow_ok = reference.k2pow_ok(ch, bytes.fromhex(ident["node_id"]),
                                        self.diff, p["pow_nonce"])
            # the proof's indices were found under another challenge; if
            # the witness happened to hold, the label check still fails
            # for any index that does not qualify under this one, which
            # the reference would have to evaluate: not drawn (1 in 4700)
            if pow_ok:
                return self.atx(seed, j, None, k3, post_seed)
            want[2], want[3], on_device = False, False, False
        elif mode == "index_out_of_range":
            idx = list(p["indices"])
            idx[0] = self.total + 17
            post = json.dumps(self.post_doc(p, indices=idx))
            want[2], on_device = False, False
        elif mode == "bad_pow_nonce":
            nonce = p["pow_nonce"] + 1
            ch, nid = bytes.fromhex(p["challenge"]), \
                bytes.fromhex(ident["node_id"])
            while reference.k2pow_ok(ch, nid, self.diff, nonce):
                nonce += 1
            post = json.dumps(self.post_doc(p, pow_nonce=nonce))
            pow_ = json.dumps(self.pow_doc(p, pow_nonce=nonce))
            want[2], want[3], on_device = False, False, False
        elif mode == "swapped_index":
            idx = list(p["indices"])
            idx[p["swap_pos"]] = p["swap_index"]
            post = json.dumps(self.post_doc(p, indices=idx))
            sampled = reference.k3_subset(
                idx, k3, post_seed, bytes.fromhex(p["challenge"]),
                bytes.fromhex(ident["node_id"]))
            want[2] = (p["swap_index"] not in sampled) \
                or bool(p["swap_qualifies"])
        sig_doc = json.dumps({"kind": "sig", "domain": int(Domain.ATX),
                              "public_key": ident["node_id"],
                              "msg": msg.hex(), "signature": sig.hex()})
        return (sig_doc, member, post, pow_), want, \
            {"pool": k, "mode": mode, "on_device": on_device}


def generate(run, pool: dict) -> dict:
    """-> {"requests": [...], "bodies": [bytes], "post_seed": bytes}.
    A request: {"client", "due" (s from the window's start; closed loop:
    None), "n_atx", "want": [bool], "atx": [facts]}; ``bodies[i]`` is the
    HTTP body of request i."""
    tr, cfg, seed = run.traffic, run.config, run.seed
    rng = random.Random(f"benchmark/atx/{seed}")
    frag = _Pool(pool, cfg)
    k3 = int(tr["k3"])
    post_seed = _h(seed, "k3-seed")
    a = int(tr["atx_per_request"])
    clients = [f"client-{i}" for i in range(int(tr["clients"]))]
    warm_s, window_s = float(tr["warm_s"]), run.window_s
    span_s = warm_s + window_s
    if tr["loop"] == "open":
        if tr["arrivals"] != "poisson_fixed_count":
            raise ValueError(f"unknown arrivals {tr['arrivals']!r}")
        # the window and the warm-up each get their own fixed count, so
        # every seed offers the window exactly the same amount of work
        rate = float(tr["rate_atx_per_s"]) / a
        dues = sorted(
            [rng.uniform(-warm_s, 0.0) for _ in range(round(rate * warm_s))]
            + [rng.uniform(0.0, window_s)
               for _ in range(round(rate * window_s))])
        count = len(dues)
        s = float(tr.get("client_zipf_s", 0.0))
        weights = [1.0 / (i + 1) ** s for i in range(len(clients))]
        who = rng.choices(range(len(clients)), weights=weights, k=count)
    elif tr["loop"] == "closed":
        per_client = int(float(tr["closed_requests_per_client_per_s"])
                         * span_s) + 2
        count = per_client * len(clients)
        dues = [None] * count
        who = [i % len(clients) for i in range(count)]
    else:
        raise ValueError(f"unknown loop {tr['loop']!r}")
    modes = list(tr["invalid_modes"])
    for m in modes:
        if m not in MODES:
            raise ValueError(f"unknown invalid mode {m!r}")
    every = round(1 / float(tr["invalid_share"])) \
        if float(tr["invalid_share"]) > 0 and modes else 0
    requests, bodies = [], []
    j = bad = 0
    for r in range(count):
        parts, want, facts = [], [], []
        for _ in range(a):
            mode = None
            if every and j % every == every // 2:
                mode = modes[bad % len(modes)]
                bad += 1
            fr, w, f = frag.atx(seed, j, mode, k3, post_seed)
            parts.extend(fr)
            want.extend(w)
            facts.append(f)
            j += 1
        body = ('{"client": "%s", "lane": "%s", "items": [%s]}'
                % (clients[who[r]], tr["lane"], ", ".join(parts)))
        bodies.append(body.encode())
        requests.append({"client": clients[who[r]], "due": dues[r],
                         "n_atx": a, "want": want, "atx": facts})
    return {"requests": requests, "bodies": bodies, "clients": clients,
            "post_seed": post_seed, "k3": k3}

