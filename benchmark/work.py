"""The least work one flush is: bytes that have to move and field
multiplications that have to be done to verify `rows` ed25519 signatures by
one random linear combination. Functions of the row count alone: no lane
bucket, window width or limb layout that a program chose enters, so a
program that pads less or picks a better window gains roofline share."""

from __future__ import annotations

# per row in: the public key, R, s and the 32-byte challenge scalar; out: a verdict
BYTES_IN_PER_ROW = 32 + 32 + 32 + 32
BYTES_OUT_PER_ROW = 1

# one point decompression: x = sqrt(u/v) by (u v^3)(u v^7)^((p-5)/8), an
# exponentiation by 2^252 - 3 (251 squarings, 11 multiplications in the usual
# chain) and 8 more products around it
DECOMPRESS_MULS = 251 + 11 + 8
# extended twisted Edwards, a = -1 (Hisil et al. 2008): unified addition 8M,
# doubling 4M + 4S
ADD_MULS = 8
DBL_MULS = 8
SCALAR_BITS_A = 253    # z*h mod L on a key lane, and the base point's lane
SCALAR_BITS_R = 128    # the random coefficient z on an R lane

# a 255-bit product as 32 x 32 byte products, each a multiply and an add: an
# upper bound on speed that no schedule of 32-bit vector work reaches
INT8_OPS_PER_MUL = 32 * 32 * 2


def flush_bytes(rows: int) -> int:
    return rows * (BYTES_IN_PER_ROW + BYTES_OUT_PER_ROW)


def pippenger_muls(rows: int, window: int) -> int:
    """Bucket method over rows+1 lanes with 253-bit scalars and rows lanes
    with 128-bit ones, at one window width: every lane adds into a bucket in
    each window its scalar reaches, each window folds its 2^w - 1 buckets by
    a running sum (2 additions a bucket), and the windows are joined by
    `window` doublings and one addition each."""
    win_a = -(-SCALAR_BITS_A // window)
    win_r = -(-SCALAR_BITS_R // window)
    adds = (rows + 1) * win_a + rows * win_r
    adds += 2 * ((1 << window) - 1) * win_a
    adds += win_a
    return adds * ADD_MULS + win_a * window * DBL_MULS


def flush_field_muls(rows: int) -> int:
    """Two decompressions a row (the key and R) and the cheapest Pippenger
    sum over the 2*rows + 1 points."""
    return 2 * rows * DECOMPRESS_MULS + min(
        pippenger_muls(rows, w) for w in range(1, 25)
    )


def least_seconds(rows: int, peaks: dict) -> tuple:
    """(seconds, which bound holds) on a chip with these peaks."""
    by_bytes = flush_bytes(rows) / peaks["hbm_bytes_per_s"]
    by_ops = flush_field_muls(rows) * INT8_OPS_PER_MUL / peaks["int8_ops_per_s"]
    return (by_ops, "compute") if by_ops >= by_bytes else (by_bytes, "bytes")
