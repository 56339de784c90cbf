"""ROMix kernel autotuner: race/persist/override semantics + cross-impl
bit-exactness (ops/autotune.py, ops/scrypt.py tuned dispatch).

The decision surface under test (docs/ROMIX_KERNEL.md):

  env (SPACEMESH_ROMIX / SPACEMESH_ROMIX_CHUNK)  >  persisted winner
  >  race (persisted)  >  static default

plus the Pallas contract: the kernel is in NO raced or persisted set
(Mosaic refuses it on TPU — ROADMAP S4), an explicit SPACEMESH_ROMIX=pallas
request is the only way to it and RAISES the kernel's own error when it
cannot run, and nothing ever degrades from one impl to another.
"""

import hashlib
import json

import jax.numpy as jnp
import numpy as np
import pytest

from spacemesh_tpu.ops import autotune, scrypt
from spacemesh_tpu.ops import romix_pallas as rp

N = 16


@pytest.fixture
def tuner(tmp_path, monkeypatch):
    """Fresh autotune world: private cache file, racing enabled, no
    overrides, no memoized measurements."""
    path = tmp_path / "romix_autotune.json"
    monkeypatch.setenv(autotune.ENV_CACHE, str(path))
    monkeypatch.delenv(autotune.ENV_IMPL, raising=False)
    monkeypatch.delenv(autotune.ENV_CHUNK, raising=False)
    monkeypatch.delenv(autotune.ENV_AUTOTUNE, raising=False)
    autotune.reset_memo()
    return path


def _seed(path, key, impl, chunk, rate=123.0):
    doc = {}
    if path.exists():
        doc = json.loads(path.read_text())
    doc[key] = {"impl": impl, "chunk": chunk, "labels_per_sec": rate}
    path.write_text(json.dumps(doc))


def test_race_on_miss_then_cache_hit(tuner):
    d = autotune.decide(N, 64, platform="cpu")
    assert d.source == "race"
    assert d.impl in autotune.IMPLS
    # the winner was persisted with the expected key
    doc = json.loads(tuner.read_text())
    key = autotune._key("cpu", N, 64)
    assert key in doc and doc[key]["impl"] == d.impl
    assert doc[key]["raced"], "race measurements should be recorded"

    # a fresh process (memos cleared) must NOT re-race: cache hit
    autotune.reset_memo()

    def boom(*a, **k):  # pragma: no cover - only on regression
        raise AssertionError("re-raced despite persisted winner")

    orig = autotune._race_measurements
    try:
        autotune._race_measurements = boom
        d2 = autotune.decide(N, 64, platform="cpu")
    finally:
        autotune._race_measurements = orig
    assert d2.source == "cache"
    assert (d2.impl, d2.chunk) == (d.impl, d.chunk)


def test_corrupt_cache_ignored(tuner, monkeypatch):
    tuner.write_text("{not json at all")
    monkeypatch.setenv(autotune.ENV_AUTOTUNE, "off")
    d = autotune.decide(N, 32, platform="cpu")
    assert d.source == "default"  # fell through, did not raise
    # and a rewrite heals the file
    autotune._store(autotune._key("cpu", N, 32),
                    {"impl": "xla", "chunk": None, "labels_per_sec": 1.0})
    assert json.loads(tuner.read_text())


def test_autotune_off_uses_default(tuner, monkeypatch):
    monkeypatch.setenv(autotune.ENV_AUTOTUNE, "off")
    d = autotune.decide(N, 64, platform="cpu")
    assert d.source == "default"
    assert not tuner.exists(), "default decisions are not persisted"


def test_env_impl_beats_cached_winner(tuner, monkeypatch):
    _seed(tuner, autotune._key("cpu", N, 64), "xla-rows", 2)
    assert autotune.decide(N, 64, platform="cpu").impl == "xla-rows"
    monkeypatch.setenv(autotune.ENV_IMPL, "xla")
    d = autotune.decide(N, 64, platform="cpu")
    assert (d.impl, d.source) == ("xla", "env")
    # env impl == cached impl inherits the cached chunk
    monkeypatch.setenv(autotune.ENV_IMPL, "xla-rows")
    assert autotune.decide(N, 64, platform="cpu").chunk == 2


def test_env_chunk_beats_cached_winner(tuner, monkeypatch):
    _seed(tuner, autotune._key("cpu", N, 64), "xla-rows", 2)
    monkeypatch.setenv(autotune.ENV_CHUNK, "8")
    d = autotune.decide(N, 64, platform="cpu")
    assert (d.impl, d.chunk, d.source) == ("xla-rows", 8, "env")
    monkeypatch.setenv(autotune.ENV_CHUNK, "0")  # explicit unchunked
    assert autotune.decide(N, 64, platform="cpu").chunk is None
    # a chunk as wide as the batch is normalized away
    monkeypatch.setenv(autotune.ENV_CHUNK, "64")
    assert autotune.decide(N, 64, platform="cpu").chunk is None


def test_bad_env_values_rejected(tuner, monkeypatch):
    monkeypatch.setenv(autotune.ENV_IMPL, "cuda")
    with pytest.raises(ValueError, match="SPACEMESH_ROMIX"):
        autotune.decide(N, 64, platform="cpu")
    monkeypatch.delenv(autotune.ENV_IMPL)
    monkeypatch.setenv(autotune.ENV_CHUNK, "-3")
    with pytest.raises(ValueError, match="SPACEMESH_ROMIX_CHUNK"):
        autotune.decide(N, 64, platform="cpu")


def test_garbage_cache_entry_ignored(tuner, monkeypatch):
    monkeypatch.setenv(autotune.ENV_AUTOTUNE, "off")
    _seed(tuner, autotune._key("cpu", N, 64), "not-an-impl", "nope")
    d = autotune.decide(N, 64, platform="cpu")
    assert d.source == "default"  # invalid entry treated as a miss


# --- cross-impl bit-exactness -------------------------------------------

UNALIGNED = (1, 7, 128, 1000)


@pytest.mark.parametrize("batch", UNALIGNED)
def test_xla_impl_sweep_bit_exact(batch):
    """Word-major, contiguous-row, and chunked variants agree on
    unaligned batch sizes (chunk 16 forces pad-and-trim at 7 and 1000)."""
    x = jnp.asarray(autotune.calibration_block(batch))
    want = np.asarray(scrypt.romix_tuned(x, n=N, impl="xla", chunk=None,
                                         interpret=False))
    for impl, chunk in (("xla-rows", None), ("xla", 16), ("xla-rows", 16)):
        got = np.asarray(scrypt.romix_tuned(x, n=N, impl=impl, chunk=chunk,
                                            interpret=False))
        assert (got == want).all(), f"{impl}/chunk={chunk} diverged at B={batch}"


@pytest.mark.parametrize("batch", (1, 7))
def test_pallas_padded_bit_exact(batch):
    """The lane-padding wrapper makes the Pallas kernel agree on batches
    below the tile (interpret mode executes every DMA in Python, so the
    wider sweep lives in tests/test_romix_pallas.py)."""
    x = jnp.asarray(autotune.calibration_block(batch))
    want = np.asarray(scrypt.romix_tuned(x, n=N, impl="xla", chunk=None,
                                         interpret=False))
    got = np.asarray(rp.romix_pallas_padded(x, n=N, lane_tile=8,
                                            interpret=True))
    assert (got == want).all(), f"pallas pad diverged at B={batch}"


def test_labels_env_override_end_to_end(tuner, monkeypatch):
    """A forced impl+chunk flows through the fused label pipeline and
    still matches hashlib ground truth."""
    monkeypatch.setenv(autotune.ENV_IMPL, "xla-rows")
    monkeypatch.setenv(autotune.ENV_CHUNK, "4")
    commitment = hashlib.sha256(b"autotune-e2e").digest()
    got = scrypt.scrypt_labels(commitment, np.arange(7, dtype=np.uint64),
                               n=N)
    for i in (0, 3, 6):
        want = hashlib.scrypt(commitment, salt=int(i).to_bytes(8, "little"),
                              n=N, r=1, p=1, dklen=16)
        assert bytes(got[i]) == want, f"label {i} mismatch"


# --- pallas failure contract --------------------------------------------


def _break_pallas(monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("mosaic exploded")

    monkeypatch.setattr(rp, "romix_pallas_padded", boom)


def test_explicit_pallas_request_raises_on_failure(tuner, monkeypatch):
    _break_pallas(monkeypatch)
    monkeypatch.setenv(autotune.ENV_IMPL, "pallas")
    commitment = hashlib.sha256(b"pallas-must-raise").digest()
    with pytest.raises(RuntimeError, match="mosaic exploded"):
        # unique (n, batch) shape so the jit cache cannot satisfy the
        # call without re-entering the (broken) pallas dispatch
        scrypt.scrypt_labels(commitment, np.arange(5, dtype=np.uint64), n=4)


def test_pallas_is_never_selected_unasked(tuner, monkeypatch):
    """The new contract in place of fall-back-and-count: no platform's
    raced grid holds a pallas row, and a winners file that names pallas
    (hand-edited, or written by an older build) is not served — so a
    broken Pallas kernel cannot be reached, and cannot be 'recovered
    from', without SPACEMESH_ROMIX=pallas."""
    for platform in ("cpu", "tpu", "gpu"):
        grid = autotune.candidates(platform, 8192, 8192, mesh_cap=8)
        assert grid and all(impl != "pallas" for impl, _, _ in grid), \
            platform
    _break_pallas(monkeypatch)
    # decisions are keyed by the BUCKETED batch — the executable shape a
    # 6-lane call actually runs at (ops/scrypt.py shape_bucket)
    _seed(tuner, autotune._key("cpu", 4, scrypt.shape_bucket(6)),
          "pallas", None)
    d = autotune.decide(4, scrypt.shape_bucket(6), platform="cpu")
    assert d.impl != "pallas" and d.source == "race"
    commitment = hashlib.sha256(b"pallas-not-served").digest()
    got = scrypt.scrypt_labels(commitment, np.arange(6, dtype=np.uint64),
                               n=4)
    want = hashlib.scrypt(commitment, salt=(2).to_bytes(8, "little"),
                          n=4, r=1, p=1, dklen=16)
    assert bytes(got[2]) == want
