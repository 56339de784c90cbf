"""The one ROMix (ops/scrypt.py ``romix_r1``) against a plain reference.

The reference below is RFC 7914's ROMix for r=1 written the obvious way
in numpy — scalar-word Salsa20/8, V as a Python list of blocks, one
``V[j]`` pick per lane — and shares nothing with the kernel's
diagonal-vector Salsa, its (N, 32, B) scratch or its fused gather.
End-to-end labels against ``hashlib.scrypt`` live in tests/test_scrypt.py.
"""

import pathlib
import re

import jax.numpy as jnp
import numpy as np
import pytest

from spacemesh_tpu.ops import scrypt

N = 16
M32 = np.uint64(0xFFFFFFFF)


def _block(batch: int) -> np.ndarray:
    rng = np.random.RandomState(7)
    return rng.randint(0, 2**32, size=(32, batch),
                       dtype=np.uint64).astype(np.uint32)


def _ref_salsa20_8(b):
    """(16, B) u32 -> (16, B) u32; words as u64 so adds cannot overflow."""
    x = [w.astype(np.uint64) for w in b]

    def rotl(v, k):
        v &= M32
        return ((v << np.uint64(k)) | (v >> np.uint64(32 - k))) & M32

    def quarter(a, b_, c, d):
        x[b_] ^= rotl(x[a] + x[d], 7)
        x[c] ^= rotl(x[b_] + x[a], 9)
        x[d] ^= rotl(x[c] + x[b_], 13)
        x[a] ^= rotl(x[d] + x[c], 18)

    for _ in range(4):
        quarter(0, 4, 8, 12)
        quarter(5, 9, 13, 1)
        quarter(10, 14, 2, 6)
        quarter(15, 3, 7, 11)
        quarter(0, 1, 2, 3)
        quarter(5, 6, 7, 4)
        quarter(10, 11, 8, 9)
        quarter(15, 12, 13, 14)
    return np.stack([(x[i] + b[i]) & M32 for i in range(16)]
                    ).astype(np.uint32)


def _ref_blockmix(x):
    y0 = _ref_salsa20_8(x[:16] ^ x[16:])
    y1 = _ref_salsa20_8(x[16:] ^ y0)
    return np.concatenate([y0, y1])


def _ref_romix(x, n, mix_phase=True):
    v = []
    for _ in range(n):
        v.append(x)
        x = _ref_blockmix(x)
    if not mix_phase:
        return x
    lanes = np.arange(x.shape[1])
    for _ in range(n):
        j = x[16] % np.uint32(n)
        vj = np.stack(v)[j, :, lanes].T  # lane l reads V[j[l]][:, l]
        x = _ref_blockmix(x ^ vj)
    return x


# widths off every tile and bucket edge: one lane, a prime, one full
# 128-lane tile, and a ragged width of several tiles
UNALIGNED = (1, 7, 128, 1000)


@pytest.mark.parametrize("batch", UNALIGNED)
def test_xla_impl_sweep_bit_exact(batch):
    """ROMix equals the reference at ragged widths, whole and with the
    mix phase compiled out (the profiler's fill/mix split)."""
    x = _block(batch)
    got = np.asarray(scrypt._stage_romix_xla(jnp.asarray(x), n=N))
    assert np.array_equal(got, _ref_romix(x, N)), f"diverged at B={batch}"
    fill = np.asarray(scrypt._stage_romix_xla(jnp.asarray(x), n=N,
                                              mix_phase=False))
    assert np.array_equal(fill, _ref_romix(x, N, mix_phase=False)), \
        f"fill phase diverged at B={batch}"


def test_no_module_reads_a_deleted_kernel_variable():
    """SPACEMESH_ROMIX, _CHUNK, _AUTOTUNE and _CACHE selected a kernel
    that no longer exists: no source of the package (or of the two
    scripts beside it) may name them, so that none can come back through
    a stale ``os.environ.get``."""
    root = pathlib.Path(scrypt.__file__).resolve().parents[2]
    sources = [*root.joinpath("spacemesh_tpu").rglob("*.py"),
               root / "chip_smoke.py", root / "bench.py"]
    assert len(sources) > 100, "the walk found no package"
    stale = [f"{p.relative_to(root)}:{i}"
             for p in sources
             for i, line in enumerate(p.read_text().splitlines(), 1)
             if re.search(r"SPACEMESH_ROMIX|ops[./]autotune|romix_pallas",
                          line)]
    assert not stale, stale
