#!/usr/bin/env python3
"""The verified-row memo's digest pass alone, native against the hashlib loop,
in one process and in alternating order: 10,000 rows of a live step's widths
(32-byte keys, 122-byte sign bytes, 64-byte signatures), then the native pass
at 1, 2, 4 and 8 threads. Prints medians in ms.
    python tools/proof/pr39/digest_micro.py [--rows 10000] [--reps 21]"""
import argparse
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))))

import numpy as np  # noqa: E402

from tendermint_tpu import native  # noqa: E402
from tendermint_tpu.crypto import batch  # noqa: E402


def median_ms(fn, reps):
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(ts)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=10_000)
    ap.add_argument("--reps", type=int, default=21)
    a = ap.parse_args()
    assert native.available(), "the native library did not build"
    rng = np.random.default_rng(39)
    n = a.rows
    pks, msgs, sigs = ([rng.bytes(w) for _ in range(n)] for w in (32, 122, 64))
    kts = ["ed25519"] * n
    memo = batch.VerifiedRowMemo(16)
    assert memo.digest_rows(pks, msgs, sigs, kts) == memo._digest_rows_py(pks, msgs, sigs, kts)
    nat, py = [], []
    for _ in range(a.reps):
        nat.append(median_ms(lambda: memo.digest_rows(pks, msgs, sigs, kts), 1))
        py.append(median_ms(lambda: memo._digest_rows_py(pks, msgs, sigs, kts), 1))
    print(f"cores {os.cpu_count()} prep_threads {native.prep_threads()} rows {n}")
    print(f"digest_rows native {statistics.median(nat):.3f} ms, hashlib loop "
          f"{statistics.median(py):.3f} ms, ratio {statistics.median(nat) / statistics.median(py):.3f}")
    print(f"joins and lengths {median_ms(lambda: [native._column(c, n) for c in (pks, msgs, sigs)], a.reps):.3f} ms")
    for threads in (1, 2, 4, 8):
        native._NTHREADS = threads
        ms = median_ms(lambda: native.memo_digest_batch(0, ["ed25519"], None, pks, msgs, sigs), a.reps)
        print(f"memo_digest_batch, {threads} threads: {ms:.3f} ms")


if __name__ == "__main__":
    main()
