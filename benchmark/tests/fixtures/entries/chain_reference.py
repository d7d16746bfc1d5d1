"""Entry driver `chain_reference`: a stand-in for a light client that verifies
a run of signed headers sequentially in one call, built on reference.py
alone. It keeps the whole protocol an entry driver keeps (README.md), so that
the harness's handling of a chain (a header and a validator set of its own
per commit, the trusted header before an item, the `broken_link` probe) can
be proven with no program underneath. One call walks the run's headers in
height order from the trusted header before them: each has to be the one its
predecessor committed to (reference.link_ok), the rows of all of them are
verified under the keys of each header's own set in flushes of at most
FLUSH_ROWS rows, the valid power is tallied header by header with that
height's powers, and the answer is in the words of the rule `adjacent_run`.

PROGRAM_CONTROLS plants the faults a chain cell can have."""

from __future__ import annotations

import contextlib
import time

from reference import FLAG_ABSENT, SignBytes, link_ok, verify_rows

FLUSH_ROWS = 128
_verify = [verify_rows]          # what checks the rows of a flush
_roots_set = [False]             # fault: every header's rows under the root's keys
_links_unchecked = [False]       # fault: no header is held to its predecessor
_roots_powers = [False]          # fault: every header tallied with the root's powers
_annotate = [None]
_last_call: list = []            # the flush readings of the last call
_first_header_msgs: set = set()  # the sign bytes of every item's first header


def configure(traffic: dict) -> None:
    pass


def native_ready() -> bool:
    return True


class State:
    def __init__(self, config, vals, items):
        self.root = vals  # the trusted root's set
        self.items = []   # per item: one dict a header
        for item in items:
            blocks = []
            for c in item if isinstance(item, list) else [item]:
                sb = SignBytes(config["chain_id"], c.height, c.round, c.block_hash,
                               c.parts_total, c.parts_hash)
                idx = [i for i, f in enumerate(c.flags) if f != FLAG_ABSENT]
                blocks.append({"commit": c, "idx": idx,
                               "msgs": [sb.of(c.timestamps[i]) for i in idx],
                               "sigs": [c.sigs[i] for i in idx]})
            _first_header_msgs.update(blocks[0]["msgs"])
            self.items.append(blocks)


def build(config, vals, items) -> State:
    return State(config, vals, items)


def _flushes(pubkeys, msgs, sigs) -> list:
    """The row mask, one flush of FLUSH_ROWS rows after another."""
    del _last_call[:]
    mask = []
    for at in range(0, len(pubkeys), FLUSH_ROWS):
        t0 = time.perf_counter()
        with _annotate[0]("bench:flush") if _annotate[0] else contextlib.nullcontext():
            got = [bool(x) for x in _verify[0](pubkeys[at:at + FLUSH_ROWS],
                                               msgs[at:at + FLUSH_ROWS],
                                               sigs[at:at + FLUSH_ROWS])]
        _last_call.append({"rows": len(got), "rows_valid": sum(got),
                           "total_ms": (time.perf_counter() - t0) * 1e3})
        mask += got
    return mask


def call(state: State, i: int) -> str:
    blocks = state.items[i]
    pubkeys = []
    for b in blocks:
        keys = (state.root if _roots_set[0] else b["commit"].vals).pubkeys
        pubkeys += [keys[j] for j in b["idx"]]
    mask = _flushes(pubkeys, [m for b in blocks for m in b["msgs"]],
                    [s for b in blocks for s in b["sigs"]])
    at = 0
    for k, b in enumerate(blocks):
        c = b["commit"]
        if not _links_unchecked[0] and not link_ok(
                c.prev.header, c.header, c.block_hash, c.height, c.vals.pubkeys, c.vals.powers):
            return f"broken link at block #{k}"
        own = state.root if _roots_powers[0] else c.vals
        valid = [j for ok, j in zip(mask[at:at + len(b["idx"])], b["idx"]) if ok]
        at += len(b["idx"])
        if sum(own.powers[j] for j in valid) * 3 <= own.total_power * 2:
            return f"not enough power at block #{k}"
    return "accepted"


def flush_reading() -> dict:
    """The sum over all the flushes of the last call, with the last one's
    own keys."""
    return {"backend": "cpu", "path": "cpu", "jax_path": None,
            "rows": sum(f["rows"] for f in _last_call),
            "rows_valid": sum(f["rows_valid"] for f in _last_call),
            "total_ms": sum(f["total_ms"] for f in _last_call),
            "flushes": len(_last_call),
            "prep_ms": None, "prep_overlap_ms": None, "transfer_ms": None, "compile_ms": 0.0,
            "lane_bucket": None, "padding_lanes": None, "chunks": None, "chunk_lanes": None,
            "fused": None}


def flush_fault(r: dict, expect: dict, rows: int) -> str | None:
    if r["backend"] != expect["backend"]:
        return f"backend {r['backend']!r}"
    if r["path"] not in expect["paths"]:
        return f"path {r['path']!r}"
    if r["rows"] != rows:
        return f"{r['rows']} rows flushed"
    return None


def process_faults() -> list:
    return []


def mask(pubkeys, msgs, sigs) -> list:
    return _flushes(pubkeys, msgs, sigs)


def passes_clean(path: str, pubkeys, msgs, sigs) -> bool:
    return all(_flushes(pubkeys, msgs, sigs))


def rejects(path: str, pubkeys, msgs, sigs, bad: int) -> bool:
    got = _flushes(pubkeys, msgs, sigs)
    return not got[bad] and sum(got) == len(got) - 1


@contextlib.contextmanager
def flush_spans(annotate):
    _annotate[0] = annotate
    try:
        yield
    finally:
        _annotate[0] = None


def install_verifier(fn) -> None:
    """`fn(pubkeys, msgs, sigs) -> mask` in the verifier's place (--control),
    on the rows of a whole call."""
    global FLUSH_ROWS
    FLUSH_ROWS = 1 << 30
    _verify[0] = fn


def roots_set() -> None:
    """Planted fault: every header's signatures are verified under the keys
    of the trusted root's set, seat by seat, as if the set never changed."""
    _roots_set[0] = True


def links_unchecked() -> None:
    """Planted fault: signatures and tally alone; no header is held to what
    its predecessor committed to."""
    _links_unchecked[0] = True


def first_header_only() -> None:
    """Planted fault: only a run's first header is verified, the rows of the
    others are taken as signed."""
    def fault(pubkeys, msgs, sigs):
        first = [m in _first_header_msgs for m in msgs]
        got = iter(verify_rows(*([x for x, f in zip(xs, first) if f]
                                 for xs in (pubkeys, msgs, sigs))))
        return [next(got) if f else True for f in first]

    install_verifier(fault)


def roots_powers() -> None:
    """Planted fault: powers of the wrong height in the tally: every header's
    valid signatures are weighed, seat by seat, with the trusted root's
    powers against the root's total."""
    _roots_powers[0] = True


PROGRAM_CONTROLS = {"roots_set": roots_set, "links_unchecked": links_unchecked,
                    "first_header_only": first_header_only, "roots_powers": roots_powers}
