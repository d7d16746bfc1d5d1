"""Light client (types/validator_set.py ValidatorSet.hash, under
light/verifier.py verify_adjacent_run's header checks): of the Merkle leaves
that a run's validator-set hashes asked for, the share answered from the memo
of leaf hashes by value (key type, key bytes, voting power) and so neither
encoded nor hashed again: `set_leaf_hits` over `set_leaves` on the program's
root span `light.verify_run` (the same two are on `light.header_checks`). A
chain that replaces one key of 100 a height reads 99 on a client's first walk
and 100 on a later lap of the ring; a program that hashes every leaf of every
set would read 0. Median over the accepted runs of the cell's size still in
the flight recorder's ring; None under 30 of them, or where the program
writes no such attribute."""

import statistics

import program_spans


def read(ctx):
    xs = []
    for e in program_spans.ring():
        attrs = e.get("attrs") or {}
        if (e["name"] == "light.verify_run" and attrs.get("rows") == ctx.rows
                and attrs.get("verdict") == "accepted" and attrs.get("set_leaves")):
            xs.append(100.0 * attrs["set_leaf_hits"] / attrs["set_leaves"])
    return statistics.median(xs) if len(xs) >= program_spans.MIN_CALLS else None
