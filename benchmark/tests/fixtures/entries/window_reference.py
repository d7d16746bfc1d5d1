"""Entry driver `window_reference`: a stand-in for a program that verifies a
run of commits in one call, built on reference.verify_rows alone. It keeps
the whole protocol an entry driver keeps (README.md), so that the harness's
handling of an item of several commits, a stated stake and a named verdict
rule can be proven with no program underneath. One call gathers the rows of
every commit of the run, verifies them in flushes of at most FLUSH_ROWS rows
(so a call is more than one flush, and its reading the sum over them),
tallies the valid power block by block and answers in the words of the rule
`tally_valid_power`.

PROGRAM_CONTROLS plants the faults a windowed cell can have."""

from __future__ import annotations

import contextlib
import time

from reference import FLAG_ABSENT, SignBytes, verify_rows

FLUSH_ROWS = 128
_verify = [verify_rows]          # what checks the rows of a flush
_tally_by_head_count = [False]
_annotate = [None]
_last_call: list = []            # the flush readings of the last call
_seat: dict = {}                 # a key's seat in the validator set's order


def configure(traffic: dict) -> None:
    pass


def native_ready() -> bool:
    return True


class State:
    def __init__(self, config, vals, items):
        self.powers, self.total = vals.powers, vals.total_power
        self.n_vals = len(vals.powers)
        _seat.update({pk: i for i, pk in enumerate(vals.pubkeys)})
        self.items = []  # per item: one (signers, pubkeys, msgs, sigs) a block
        for item in items:
            blocks = []
            for c in item if isinstance(item, list) else [item]:
                sb = SignBytes(config["chain_id"], c.height, c.round, c.block_hash,
                               c.parts_total, c.parts_hash)
                idx = [i for i, f in enumerate(c.flags) if f != FLAG_ABSENT]
                blocks.append((idx, [vals.pubkeys[i] for i in idx],
                               [sb.of(c.timestamps[i]) for i in idx], [c.sigs[i] for i in idx]))
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
    mask = _flushes(*([x for b in blocks for x in b[k]] for k in (1, 2, 3)))
    at = 0
    for k, (idx, _, _, _) in enumerate(blocks):
        valid = [j for ok, j in zip(mask[at:at + len(idx)], idx) if ok]
        at += len(idx)
        if _tally_by_head_count[0]:
            tallied, total = len(valid), state.n_vals
        else:
            tallied, total = sum(state.powers[j] for j in valid), state.total
        if tallied * 3 <= total * 2:
            return f"refused at block #{k}"
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


def _block_starts(pubkeys) -> list:
    """Where each block of the rows starts: a block's rows stand in the
    set's order, so a new one starts where the seat does not rise."""
    seats = [_seat[pk] for pk in pubkeys]
    return [0] + [at for at in range(1, len(seats)) if seats[at] <= seats[at - 1]]


def _whole_call(fault):
    """A planted fault sees the rows of a whole call, not of one flush."""
    global FLUSH_ROWS
    FLUSH_ROWS = 1 << 30
    _verify[0] = fault


def install_verifier(fn) -> None:
    """`fn(pubkeys, msgs, sigs) -> mask` in the verifier's place (--control)."""
    _whole_call(fn)


def first_block_only() -> None:
    """Planted fault: only a run's first block is checked, the rest is taken
    as signed."""
    def fault(pubkeys, msgs, sigs):
        cut = (_block_starts(pubkeys) + [len(pubkeys)])[1]
        return verify_rows(pubkeys[:cut], msgs[:cut], sigs[:cut]) + [True] * (len(pubkeys) - cut)

    _whole_call(fault)


def head_count() -> None:
    """Planted fault: the valid signatures are counted, not weighed: more
    than 2/3 of the validators in the place of more than 2/3 of the power.
    Only a skewed stake can show it."""
    _tally_by_head_count[0] = True


def unseen_last_third() -> None:
    """Planted fault: the last third of a call's rows goes unseen."""
    def fault(pubkeys, msgs, sigs):
        cut = len(pubkeys) * 2 // 3 + 1
        return verify_rows(pubkeys[:cut], msgs[:cut], sigs[:cut]) + [True] * (len(pubkeys) - cut)

    _whole_call(fault)


def altered_last_block() -> None:
    """Planted fault: the answers of a run's last block altered where they
    are produced (each negated)."""
    def fault(pubkeys, msgs, sigs):
        got = verify_rows(pubkeys, msgs, sigs)
        cut = _block_starts(pubkeys)[-1]
        return got[:cut] + [not ok for ok in got[cut:]]

    _whole_call(fault)


PROGRAM_CONTROLS = {"first_block_only": first_block_only, "head_count": head_count,
                    "unseen_last_third": unseen_last_third,
                    "altered_last_block": altered_last_block}
