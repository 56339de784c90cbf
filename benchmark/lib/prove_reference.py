"""The plain reference prover: what THE proof of a challenge over a
store is, independent of the code under test. numpy and ``hashlib``
only (``lib/reference.py`` for Salsa20/8, written there from its
specification, the threshold and the k2pow rule); nothing of
``spacemesh_tpu``, no JAX.

A store is its label files in index order (``postdata_0.bin``,
``postdata_1.bin``, ...: 16-byte labels back to back, every file but
the last full), read here with plain ``open``/``read``. The label at
index ``i`` qualifies under ``nonce`` when

    salsa20_8(challenge(8 LE words) || nonce || lo32(i) || hi32(i) || 0
              || label(4 LE words))[0]  <  floor(k1 * 2^32 / total)

(``ops/proving.py``'s docstring). THE proof is the LOWEST nonce with at
least K2 qualifying labels over the whole store and that nonce's first
K2 qualifying indices, ascending, beside a k2pow witness.

:func:`prove` scans nonces upward, ``ahead`` at a time, each nonce in
blocks of labels so that it fits; with a :func:`worker_pool` the blocks
run on spawned processes, which import this module and numpy and never
JAX (the chip belongs to the parent). :func:`check` says whether a given
proof is well formed and every one of its indices qualifies over the
bytes on disk.
"""

from __future__ import annotations

import concurrent.futures
import multiprocessing
import os
from pathlib import Path

import numpy as np

from .reference import LABEL_BYTES, k2pow_ok, salsa20_8, threshold

BLOCK_LABELS = 1 << 15      # 2 MiB of u32 state: stays in a core's cache


def store_files(data_dir) -> list:
    """The store's label files in index order."""
    files = sorted(Path(data_dir).glob("postdata_*.bin"),
                   key=lambda p: int(p.stem.rsplit("_", 1)[1]))
    if [int(p.stem.rsplit("_", 1)[1]) for p in files] != \
            list(range(len(files))):
        raise ValueError(f"label files of {data_dir} are not 0..n-1")
    return [str(p) for p in files]


def total_labels(files: list) -> int:
    return sum(os.path.getsize(f) for f in files) // LABEL_BYTES


def read_labels(files: list, start: int, count: int) -> bytes:
    """``count`` labels from index ``start``, by plain file reads. Every
    file but the last holds the same number of labels."""
    per_file = os.path.getsize(files[0]) // LABEL_BYTES
    out = bytearray()
    while count > 0:
        fi, off = divmod(start, per_file)
        if fi >= len(files):
            raise ValueError(f"label {start} is past the store's end")
        take = min(count, per_file - off)
        with open(files[fi], "rb") as f:
            f.seek(off * LABEL_BYTES)
            chunk = f.read(take * LABEL_BYTES)
        if len(chunk) != take * LABEL_BYTES:
            raise ValueError(f"{files[fi]} is short of label "
                             f"{start + take - 1}")
        out += chunk
        start += take
        count -= take
    return bytes(out)


def proving_values(challenge: bytes, nonce: int, indices: np.ndarray,
                   raw: bytes) -> np.ndarray:
    """Proving-hash values (u32) of the labels in ``raw``, which sit at
    ``indices`` (uint64)."""
    n = len(raw) // LABEL_BYTES
    state = np.zeros((16, n), dtype=np.uint32)
    state[0:8] = np.frombuffer(challenge, dtype="<u4")[:, None]
    state[8] = nonce & 0xFFFFFFFF
    state[9] = (indices & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    state[10] = (indices >> np.uint64(32)).astype(np.uint32)
    state[12:16] = np.frombuffer(raw, dtype="<u4").reshape(n, 4).T
    return salsa20_8(state)[0]


def scan_block(files: list, challenge: bytes, nonce: int, thr: int,
               start: int, count: int) -> list:
    """Ascending qualifying indices of ``[start, start + count)``."""
    idx = np.arange(start, start + count, dtype=np.uint64)
    vals = proving_values(challenge, nonce, idx,
                          read_labels(files, start, count))
    return (start + np.nonzero(vals < thr)[0]).tolist()


def worker_pool(workers: int | None = None):
    """Spawned worker processes (never forked: the parent holds the
    chip and has threads)."""
    return concurrent.futures.ProcessPoolExecutor(
        max_workers=workers or max(1, min(12, (os.cpu_count() or 2) - 1)),
        mp_context=multiprocessing.get_context("spawn"))


def prove(files: list, challenge: bytes, k1: int, k2: int, *,
          ahead: int = 8, block: int = BLOCK_LABELS,
          max_nonces: int = 1 << 14, pool=None):
    """-> (nonce, indices): the lowest nonce with at least ``k2``
    qualifying labels over the whole store and its first ``k2``
    qualifying indices. Every nonce costs one pass of the store;
    ``ahead`` nonces are scanned together so that a pool stays busy
    (a winner among them makes the higher ones wasted work, never a
    different answer: the lowest is taken)."""
    total = total_labels(files)
    thr = threshold(k1, total)
    blocks = [(s, min(block, total - s)) for s in range(0, total, block)]
    for base in range(0, max_nonces, ahead):
        nonces = range(base, base + ahead)
        if pool is None:
            parts = [[scan_block(files, challenge, n, thr, s, c)
                      for s, c in blocks] for n in nonces]
        else:
            futs = [[pool.submit(scan_block, files, challenge, n, thr, s, c)
                     for s, c in blocks] for n in nonces]
            parts = [[f.result() for f in row] for row in futs]
        for n, row in zip(nonces, parts):
            hits = [i for part in row for i in part]  # block order = index order
            if len(hits) >= k2:
                return n, hits[:k2]
    raise ValueError(f"no nonce under {max_nonces} wins")


def check(files: list, challenge: bytes, node_id: bytes, *, nonce: int,
          indices: list, pow_nonce: int, k1: int, k2: int,
          pow_difficulty: bytes) -> dict:
    """Is this a well-formed proof whose every index qualifies? ->
    {"shape", "qualify", "witness"}: exactly K2 indices, ascending (so
    distinct) and in range; each under the threshold by the bytes on
    disk; the k2pow witness under the difficulty by ``hashlib.sha256``.
    (That the nonce is the LOWEST that wins is :func:`prove`'s to say.)"""
    total = total_labels(files)
    idx = [int(i) for i in indices]
    shape = (len(idx) == k2 and nonce >= 0
             and all(b > a for a, b in zip(idx, idx[1:]))
             and idx[0] >= 0 and idx[-1] < total)
    qualify = False
    if shape:
        raw = b"".join(read_labels(files, i, 1) for i in idx)
        vals = proving_values(challenge, nonce,
                              np.array(idx, dtype=np.uint64), raw)
        qualify = bool(np.all(vals < threshold(k1, total)))
    return {"shape": shape, "qualify": qualify,
            "witness": k2pow_ok(challenge, node_id, pow_difficulty,
                                pow_nonce)}
