"""Operations and bytes of the proving scan KERNEL, computed from its
shapes: counts of the work, the same whatever implements it, and a
floor of what any implementation executes.

One kernel call evaluates ``labels x nonces`` proving hashes
(``ops/proving.py``'s docstring). A proving hash is one Salsa20/8 core:
8 rounds of 4 quarter-rounds; a quarter-round is 4 steps of ``b ^=
rotl(a + d, r)``: an add, a rotate and a xor on u32 lanes. The VPU has
no rotate instruction, so a rotate is two shifts and an or: 5 u32 ops a
step, 20 a quarter-round, 8 x 4 x 20 = 640 a hash. Left out, so that
the count stays a floor: the feed-forward add of word 0, the threshold
compare, the hit-bit packing, and everything outside the kernel (mask
unpacking, compaction, merge: ``scan_kernel_share`` says how much of the
step program they are).

Bytes: a call reads each label once, 16 B. The two index words (8 B a
label today) are left out: a device that makes its own indices needs
none of them (ROADMAP S6). The 4 B of hit bits a label written back are
left out too.

The least time of a call is the larger of bytes over the HBM peak and
ops over the integer peak, both from ``lib/device.PEAKS`` as it stands.
At 16 nonces a label the op bound is the larger: 640 x 16 = 10,240 ops
against 16 B, i.e. 26 ns/kilolabel at 393 TOP/s against 20 ns/kilolabel
at 819 GB/s. THE INTEGER PEAK IN THE TABLE IS THE MXU'S int8 FIGURE: no
u32 peak of the v5e's vector unit is published, a u32 add on the VPU is
certainly slower than an int8 multiply-add on the MXU, and none is
invented here. A share computed against it therefore UNDERSTATES how
close the kernel is to what the VPU can do; it cannot read over 100%.
"""

from __future__ import annotations

SALSA_U32_OPS = 8 * 4 * 20      # one Salsa20/8 core, rotates as 3 ops
LABEL_BYTES = 16


def scan_ops(labels: int, nonces: int) -> int:
    """u32 operations of the proving hashes of ``labels x nonces``."""
    return labels * nonces * SALSA_U32_OPS


def scan_bytes(labels: int) -> int:
    """HBM bytes the scan of ``labels`` labels has to read."""
    return labels * LABEL_BYTES


def scan_least_s(labels: int, nonces: int, peaks: dict) -> dict:
    """The least seconds the chip could take, by each bound, and which
    bound holds (the larger)."""
    by = {"hbm": scan_bytes(labels) / peaks["hbm_bytes_per_s"],
          "ops": scan_ops(labels, nonces) / peaks["int8_op_per_s"]}
    bound = max(by, key=by.get)
    return {"bound": bound, "seconds": by[bound], **by}
