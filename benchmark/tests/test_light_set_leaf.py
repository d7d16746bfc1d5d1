"""The per-layer metric `light.set_leaf_hit_pct` (PR 35): its entry is the
last of `per_layer` and lists the light cell alone; its reader gives None on
a ring whose roots carry no `set_leaves` (the parent of the PR that added the
memo of leaf hashes) and the percentage on a recorded ring that does.

Run: python -m pytest benchmark/tests -q   (or python benchmark/selftest.py)"""

from __future__ import annotations

import types

import test_light_seq as seq  # the cell, its recorder of a rehearsed ring, its readers' names

NAME = "light.set_leaf_hit_pct"

# test_light_seq.py holds the readers that list the light cell alone to be its
# `LIGHT_NEW`, the eight of PR 34, and no PR but a `benchmark` one may edit
# that file. This is the ninth: named there too, every assertion of that file
# runs over all nine. (That file run alone, without this one, counts eight
# and says so.) A `benchmark` PR that writes the name into `LIGHT_NEW`
# deletes these two lines.
if NAME not in seq.LIGHT_NEW:
    seq.LIGHT_NEW.append(NAME)


def test_the_metric_is_the_last_entry_and_the_light_cells_alone():
    entry = seq.LIGHT_BM["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
                     "layer": "light client", "moves": "verify_ms_p50",
                     "workloads": [seq.LIGHT_CELL]}
    assert seq.spec.lint(seq.LIGHT_BM, seq.ROOT, seq.HERE) == []
    for cell in ("commit-10k.verify-commit", "commit-1024.verify-commit", "hub-175.catchup"):
        assert NAME not in {m["name"] for m in seq.spec.Cell(seq.LIGHT_BM, cell).per_layer}


def root_event(k: int, rows: int = 33300, verdict: str = "accepted", **counts) -> dict:
    attrs = {"headers": 333, "rows": rows, "sets": 333, "flushes": 1, "verdict": verdict, **counts}
    return {"name": "light.verify_run", "span": k, "root": k, "t0_ns": k * 10**9,
            "dur_ms": 400.0, "attrs": attrs}


def test_the_reader_on_handmade_rings(monkeypatch):
    import program_spans

    reader = seq.spec.Cell(seq.LIGHT_BM, seq.LIGHT_CELL).reader(NAME)

    def read(events, rows=33300):
        monkeypatch.setattr(program_spans, "ring", lambda: events)
        return reader.read(types.SimpleNamespace(rows=rows, traffic=seq.SEQUENCE_MIX, calls=[]))

    first_walk = dict(set_leaves=33300, set_leaf_hits=32967)  # one key of 100 new a height
    later_lap = dict(set_leaves=33300, set_leaf_hits=33300)
    assert read([]) is None
    assert read([root_event(k) for k in range(40)]) is None  # the parent: no such attribute
    assert read([root_event(k, **first_walk) for k in range(40)]) == 99.0
    assert read([root_event(k, **first_walk) for k in range(29)]) is None  # under thirty runs
    assert read([root_event(k, **(first_walk if k < 3 else later_lap)) for k in range(40)]) == 100.0
    assert read([root_event(k, **first_walk) for k in range(40)], rows=7) is None
    # a refused run, and a run that hashed no set, are not the cell's
    assert read([root_event(k, verdict="refused at height 9: ErrInvalidHeader", **first_walk)
                 for k in range(40)]) is None
    assert read([root_event(k, set_leaves=0, set_leaf_hits=0) for k in range(40)]) is None


def test_the_reader_on_a_recorded_ring():
    """A rehearsal's ring of 36 calls (6 headers of 4 validators a call, the
    ring's three items served in turn): the first lap hashes the chain's keys,
    every later call finds all 24 leaves, so the median reads 100."""
    out = seq.record_light(36)
    assert out["empty"][NAME] is None and out["other_size"][NAME] is None
    assert out["recorded"][NAME] == 100.0
    assert seq.record_light(12)["recorded"][NAME] is None
