"""Pallas ROMix kernel: bit-exact vs the XLA path + hashlib.

Interpret mode on CPU (the kernel's DMA orchestration runs in the
Pallas interpreter). On TPU Mosaic refuses the kernel's VMEM layout
(ROADMAP S4), so SPACEMESH_ROMIX=pallas — the only way onto this path —
raises there; these tests keep the algorithm checked until a layout
that compiles replaces it.
"""

import hashlib

import jax.numpy as jnp
import numpy as np
import pytest

from spacemesh_tpu.ops import scrypt
from spacemesh_tpu.ops.romix_pallas import LANE_TILE, romix_pallas

N = 16
B = 16  # small: the interpreter executes every DMA in Python


def _random_block(b):
    rng = np.random.RandomState(7)
    return jnp.asarray(rng.randint(0, 2**32, size=(32, b), dtype=np.uint64)
                       .astype(np.uint32))


def test_pallas_romix_matches_xla_gather_path():
    x = _random_block(B)
    want = np.asarray(scrypt.romix_r1(x, N))
    got = np.asarray(romix_pallas(x, n=N, lane_tile=B, interpret=True))
    assert (want == got).all(), "contiguous-row kernel diverged from XLA"


def test_pallas_romix_tiles_the_batch():
    tile = 8
    x = _random_block(tile * 2)  # two grid steps share the V scratch
    want = np.asarray(scrypt.romix_r1(x, N))
    got = np.asarray(romix_pallas(x, n=N, lane_tile=tile, interpret=True))
    assert (want == got).all(), "per-tile scratch reuse broke a grid step"


def test_flagged_pipeline_is_bit_exact_vs_hashlib(monkeypatch):
    """End-to-end labels through the SPACEMESH_ROMIX=pallas flag equal
    hashlib.scrypt ground truth (the repo's canonical oracle)."""
    monkeypatch.setenv("SPACEMESH_ROMIX", "pallas")
    commitment = hashlib.sha256(b"romix-race-commitment").digest()
    indices = np.arange(LANE_TILE, dtype=np.uint64)  # full lane tile
    got = scrypt.scrypt_labels(commitment, indices, n=N)
    for i in (0, 1, LANE_TILE - 1):
        want = hashlib.scrypt(commitment, salt=int(i).to_bytes(8, "little"),
                              n=N, r=1, p=1, dklen=16)
        assert bytes(got[i]) == want, f"label {i} mismatch"


def test_flag_pads_when_batch_does_not_tile(monkeypatch):
    """An explicit pallas request with a non-tiling batch PADS the lanes
    up to the tile (romix_pallas_padded) instead of silently falling
    back to XLA — explicit requests never degrade (ops/autotune.py)."""
    monkeypatch.setenv("SPACEMESH_ROMIX", "pallas")
    commitment = hashlib.sha256(b"romix-fallback").digest()
    got = scrypt.scrypt_labels(commitment, np.arange(3, dtype=np.uint64),
                               n=N)
    want = hashlib.scrypt(commitment, salt=(2).to_bytes(8, "little"),
                          n=N, r=1, p=1, dklen=16)
    assert bytes(got[2]) == want


def test_bad_batch_rejected():
    with pytest.raises(ValueError, match="multiple"):
        romix_pallas(_random_block(12), n=N, lane_tile=8, interpret=True)
