"""The prover against the benchmark's plain reference prover
(``benchmark/lib/prove_reference.py``: numpy and hashlib, nothing of
``spacemesh_tpu``) at mainnet's K1=26 / K2=37, on small seeded stores at
N=2, for both scan steps (XLA, and Pallas under interpret). At these
parameters a nonce wins about one time in forty, so the winner is
decided near the end of a pass: the regime the k1=64 / k2=16 fixtures
never reach. The challenges were picked by running the reference over
``sha256(b"prove-ref-challenge-<i>")`` once; each case asserts what it
was picked for."""

import hashlib
import sys
from pathlib import Path

import pytest

from spacemesh_tpu.post import initializer
from spacemesh_tpu.post.prover import ProofParams, Prover

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
K1, K2 = 26, 37
DIFFICULTY = bytes.fromhex("0fffffffffffffff" + "00" * 24)
NODE = hashlib.sha256(b"prove-ref-node").digest()
BATCH = 2048


def _ref():
    sys.path.insert(0, str(BENCH))
    try:
        from lib import prove_reference
    finally:
        sys.path.remove(str(BENCH))
    return prove_reference


def _challenge(i: int) -> bytes:
    return hashlib.sha256(b"prove-ref-challenge-%d" % i).digest()


@pytest.fixture(scope="module")
def stores(tmp_path_factory):
    """A: 2^14 labels, whole batches: one flight of eight. B: 20,000
    labels: a flight of eight batches of 2,048 and a ragged one of two,
    whose second holds 1,568."""
    out = {}
    for name, per_unit in (("A", 4096), ("B", 5000)):
        d = tmp_path_factory.mktemp(f"prove-ref-{name}")
        initializer.initialize(
            d, node_id=NODE,
            commitment=hashlib.sha256(
                b"prove-ref-commitment-" + name.encode()).digest(),
            num_units=4, labels_per_unit=per_unit, scrypt_n=2,
            batch_size=4096)
        out[name] = (d, _ref().store_files(d))
    return out


# store, challenge, nonce_group, window_groups -> winner, passes, and the
# label whose flight's edge ends the pass early (512-label batches there:
# a flight is 4,096 labels under XLA; the Pallas lane tile makes it 8,192)
CASES = {
    # nonce 9 of the first 16: one pass, decided at the store's end
    "winner_in_first_window": ("A", 5, 16, 1, 9, 1, None),
    # nonce 21: the first 16 nonces fail their whole pass, a second runs
    "second_pass": ("A", 2, 16, 1, 21, 2, None),
    # nonce 62 = 2 x 31 is the LOWEST nonce of the third window, so
    # nothing below it has to be ruled out: the pass ends with the
    # FLIGHT that holds its 37th hit (label 11,923): 4,096 labels early
    # where a flight is 4,096 labels, at the store's end where it is 8,192
    "early_exit_lowest_of_window": ("A", 14, 31, 1, 62, 3, 11923),
    # the last batch holds 1,568 labels; its flight is padded to 16,384
    "ragged_last_batch": ("B", 3, 16, 1, 13, 1, None),
}


@pytest.mark.parametrize("step", ["xla", "pallas"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_prover_returns_the_reference_proof(stores, case, step):
    store, i, ng, wg, winner, passes, decided = CASES[case]
    data_dir, files = stores[store]
    challenge = _challenge(i)
    want = _ref().prove(files, challenge, K1, K2)
    assert want[0] == winner                     # what the case was picked for
    prover = Prover(data_dir, ProofParams(k1=K1, k2=K2, k3=K2,
                                          pow_difficulty=DIFFICULTY),
                    batch_labels=BATCH if decided is None else 512,
                    nonce_group=ng, window_groups=wg,
                    use_pallas=step == "pallas", mesh=None)
    assert prover.scan_step()[2] == step
    proof = prover.prove(challenge)
    assert (proof.nonce, proof.indices) == want
    st = prover.last_stats
    assert st.windows == passes
    total = prover.meta.total_labels
    if decided is not None:
        flight = prover.flight_batches(None) * prover.batch_labels
        assert flight == {"xla": 4096, "pallas": 8192}[step]
        swept = (passes - 1) * total + -(-(decided + 1) // flight) * flight
        assert st.early_exited and st.labels_swept == swept
        assert swept == {"xla": 2 * 16384 + 12288, "pallas": 3 * 16384}[step]
    else:
        assert st.labels_swept == passes * total
    ok = _ref().check(files, challenge, NODE, nonce=proof.nonce,
                      indices=proof.indices, pow_nonce=proof.pow_nonce,
                      k1=K1, k2=K2, pow_difficulty=DIFFICULTY)
    assert ok == {"shape": True, "qualify": True, "witness": True}


@pytest.fixture(scope="module")
def good(stores):
    _d, files = stores["A"]
    challenge = _challenge(5)
    nonce, indices = _ref().prove(files, challenge, K1, K2)
    pow_nonce = next(n for n in range(1 << 16) if hashlib.sha256(
        challenge + NODE + n.to_bytes(8, "little")).digest() < DIFFICULTY)
    return files, challenge, dict(nonce=nonce, indices=indices,
                                  pow_nonce=pow_nonce)


def _swapped(p):
    out = next(j for j in range(16384) if j not in p["indices"]
               and p["indices"][3] < j < p["indices"][4])
    return dict(p, indices=p["indices"][:4] + [out] + p["indices"][5:])


BAD = {
    # an index that is in range and in order but is not a hit
    "one_index_swapped": (_swapped, "qualify"),
    "one_index_out_of_order": (lambda p: dict(p, indices=(
        p["indices"][:7] + [p["indices"][8], p["indices"][7]]
        + p["indices"][9:])), "shape"),
    "wrong_nonce": (lambda p: dict(p, nonce=p["nonce"] + 1), "qualify"),
    "bad_k2pow_witness": (lambda p: dict(p, pow_nonce=next(
        n for n in range(1 << 16) if hashlib.sha256(
            _challenge(5) + NODE + n.to_bytes(8, "little")).digest()
        >= DIFFICULTY)), "witness"),
}


@pytest.mark.parametrize("how", sorted(BAD))
def test_reference_check_rejects(good, how):
    files, challenge, proof = good
    kw = dict(k1=K1, k2=K2, pow_difficulty=DIFFICULTY)
    fine = _ref().check(files, challenge, NODE, **proof, **kw)
    assert fine == {"shape": True, "qualify": True, "witness": True}
    change, fails = BAD[how]
    got = _ref().check(files, challenge, NODE, **change(proof), **kw)
    assert not got[fails]
    assert all(v for k, v in got.items()
               if k != fails and not (fails == "shape" and k == "qualify"))
