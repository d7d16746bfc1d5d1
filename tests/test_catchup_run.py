"""ISSUE 29: BlocksyncReactor._verify_run_batched held to the plain rule
`tally_valid_power_run` (benchmark/references/, which imports nothing of the
program) on seeded keys, through the scheduler's catch-up lane and through
the direct branch; and the run's span tree: one tree per catch-up run across
the lane's hand-over to the dispatch thread, nothing constructed with the
recorder off."""

import importlib.util
import os
import threading
from types import SimpleNamespace

import numpy as np
import pytest

os.environ.setdefault("TMTPU_CRYPTO_BACKEND", "cpu")

from tendermint_tpu.blocksync import reactor as reactor_mod
from tendermint_tpu.blocksync.reactor import BlocksyncReactor
from tendermint_tpu.config.config import SchedulerConfig
from tendermint_tpu.crypto import gen_ed25519
from tendermint_tpu.crypto.scheduler import VerifyScheduler
from tendermint_tpu.libs import trace
from tendermint_tpu.libs.trace import Tracer
from tendermint_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader
from tendermint_tpu.types.block import Commit, CommitSig
from tendermint_tpu.types.validator_set import Validator, ValidatorSet

from test_prep_pipeline import needs_native, prep_cfg, small_rlc  # noqa: F401
from test_trace import _device_route

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")


def _load(path):
    spec = importlib.util.spec_from_file_location("ref_" + os.path.basename(path)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


reference = _load(os.path.join(BENCH, "reference.py"))
RULE = _load(os.path.join(BENCH, "references", "tally_valid_power_run.py")).verdict

CHAIN = "catchup-chain"
# a small skewed set: the three largest of 12 hold over half of 1,300
POWERS = [400, 230, 160, 120, 90, 70, 55, 45, 40, 35, 30, 25]
BLOCKS = 6
FIRST_HEIGHT = 40


class Net:
    """12 seeded validators and a run of 6 signed commits with absentees,
    as the program's objects and as the plain reference's rows."""

    def __init__(self, seed=29):
        rng = np.random.default_rng(seed)
        privs = [gen_ed25519(rng.integers(0, 256, 32, dtype=np.uint8).tobytes())
                 for _ in POWERS]
        self.vals = ValidatorSet([Validator(p.pub_key(), w) for p, w in zip(privs, POWERS)])
        by_addr = {p.pub_key().address(): p for p in privs}
        self.privs = [by_addr[v.address] for v in self.vals.validators]
        self.powers = [v.voting_power for v in self.vals.validators]
        self.total = sum(self.powers)
        self.blocks = []  # per block: dict(height, hash, header, absent, stamps, sigs)
        for b in range(BLOCKS):
            header = PartSetHeader(int(rng.integers(1, 9)), rng.bytes(32))
            # one or two of the smaller validators are absent, drawn afresh
            absent = set(rng.choice(range(4, 12), int(rng.integers(1, 3)), replace=False).tolist())
            blk = dict(height=FIRST_HEIGHT + b, hash=rng.bytes(32), header=header,
                       absent=absent, stamps=(1_700_000_000_000_000_000
                                              + rng.integers(1, 10**9, 12)).tolist())
            sb = reference.SignBytes(CHAIN, blk["height"], 0, blk["hash"], header.total,
                                     header.hash)
            blk["sigs"] = [b"" if i in absent else self.privs[i].sign(sb.of(blk["stamps"][i]))
                           for i in range(12)]
            self.blocks.append(blk)

    def run(self):
        """The (first, parts, second, enc) triples a reactor would gather."""
        out = []
        for blk in self.blocks:
            sigs = [
                CommitSig.absent_sig() if i in blk["absent"]
                else CommitSig(BlockIDFlag.COMMIT, v.address, blk["stamps"][i], blk["sigs"][i])
                for i, v in enumerate(self.vals.validators)
            ]
            commit = Commit(blk["height"], 0, BlockID(blk["hash"], blk["header"]), sigs)
            first = SimpleNamespace(hash=lambda h=blk["hash"]: h,
                                    header=SimpleNamespace(height=blk["height"]))
            out.append((first, SimpleNamespace(header=blk["header"]),
                        SimpleNamespace(last_commit=commit), None))
        return out

    def rows(self):
        """(signers, pubkeys, msgs, sigs, blocks) by the reference's encoder."""
        idx, pks, msgs, sigs, blocks = [], [], [], [], []
        for blk in self.blocks:
            sb = reference.SignBytes(CHAIN, blk["height"], 0, blk["hash"],
                                     blk["header"].total, blk["header"].hash)
            here = [i for i in range(12) if i not in blk["absent"]]
            idx += here
            pks += [self.vals.validators[i].pub_key.bytes() for i in here]
            msgs += [sb.of(blk["stamps"][i]) for i in here]
            sigs += [blk["sigs"][i] for i in here]
            blocks.append({"height": blk["height"], "rows": len(here)})
        return idx, pks, msgs, sigs, blocks

    def want(self):
        """(the plain reference's row mask, the rule's verdict over it)."""
        idx, pks, msgs, sigs, blocks = self.rows()
        mask = reference.verify_rows(pks, msgs, sigs)
        return mask, RULE(mask, idx, self.powers, self.total, blocks)

    def flip(self, b, i):
        sig = self.blocks[b]["sigs"][i]
        self.blocks[b]["sigs"][i] = sig[:33] + bytes([sig[33] ^ 0x20]) + sig[34:]


@pytest.fixture
def sched():
    s = VerifyScheduler(SchedulerConfig(catchup_max_wait=0.001), backend="cpu")
    yield s
    s.close()


def _reactor(net, scheduler):
    return BlocksyncReactor(SimpleNamespace(validators=net.vals, chain_id=CHAIN),
                            None, None, active=False, scheduler=scheduler)


def _verify(net, branch, sched, monkeypatch):
    """(_verify_run_batched's answer in the rule's words, the row mask the
    branch handed to the tally)."""
    seen = []
    if branch == "lane":
        inner = sched.verify_rows

        def lane(lane_name, *a, **kw):
            assert lane_name == "catchup"
            seen.append(inner(lane_name, *a, **kw))
            return seen[-1]

        monkeypatch.setattr(sched, "verify_rows", lane)
        bad = _reactor(net, sched)._verify_run_batched(net.run())
    else:
        inner = reactor_mod.verify_batch

        def direct(*a, **kw):
            seen.append(inner(*a, **kw))
            return seen[-1]

        monkeypatch.setattr(reactor_mod, "verify_batch", direct)
        bad = _reactor(net, None)._verify_run_batched(net.run())
    (mask,) = seen or [None]
    return ("accepted" if bad is None else f"refused at block #{bad}"), mask


BRANCHES = ["lane", "direct"]


@pytest.mark.parametrize("branch", BRANCHES)
def test_all_valid_is_accepted(branch, sched, monkeypatch):
    net = Net()
    mask, want = net.want()
    got, rows = _verify(net, branch, sched, monkeypatch)
    assert got == want == "accepted"
    assert [bool(x) for x in rows] == mask and all(mask)
    assert len(mask) == sum(12 - len(b["absent"]) for b in net.blocks) < 12 * BLOCKS


@pytest.mark.parametrize("branch", BRANCHES)
def test_one_flipped_bit_refuses_nothing(branch, sched, monkeypatch):
    net = Net()
    net.flip(2, 5)
    mask, want = net.want()
    got, rows = _verify(net, branch, sched, monkeypatch)
    assert got == want == "accepted"
    assert [bool(x) for x in rows] == mask and mask.count(False) == 1


@pytest.mark.parametrize("branch", BRANCHES)
@pytest.mark.parametrize("block", [0, 3, 5])
def test_largest_signers_invalid_refuses_at_that_block(branch, block, sched, monkeypatch):
    """One flipped bit in the signatures of a block's largest signers until
    no more than 2/3 of the power signed validly; nobody is absent who was
    not. By head count 10 or 11 of 12 still signed validly."""
    net = Net()
    valid = sum(net.powers[i] for i in range(12) if i not in net.blocks[block]["absent"])
    flipped = 0
    for i in range(12):
        if valid * 3 <= net.total * 2:
            break
        net.flip(block, i)
        valid -= net.powers[i]
        flipped += 1
    assert 1 <= flipped <= 2
    mask, want = net.want()
    got, rows = _verify(net, branch, sched, monkeypatch)
    assert got == want == f"refused at block #{block}"
    assert [bool(x) for x in rows] == mask and mask.count(False) == flipped


@pytest.mark.parametrize("branch", BRANCHES)
def test_one_block_short_of_power_refuses_at_that_block(branch, sched, monkeypatch):
    net = Net()
    net.blocks[4]["absent"] |= {0, 1}  # 630 of 1,300 leave: every row still valid
    for i in (0, 1):
        net.blocks[4]["sigs"][i] = b""
    mask, want = net.want()
    got, rows = _verify(net, branch, sched, monkeypatch)
    assert got == want == "refused at block #4"
    assert all(mask) and [bool(x) for x in rows] == mask


@pytest.mark.parametrize("branch", BRANCHES)
@pytest.mark.parametrize("fault", ["length", "block_id", "height"])
def test_structural_refusals(branch, fault, sched, monkeypatch):
    """A commit of another length than the set, or for another block or
    height than `first`, refuses the run at its index whatever was signed."""
    net = Net()
    run = net.run()
    first, parts, second, enc = run[3]
    if fault == "length":
        c = second.last_commit
        second = SimpleNamespace(last_commit=Commit(c.height, c.round, c.block_id,
                                                    c.signatures[:-1]))
    elif fault == "block_id":
        first = SimpleNamespace(hash=lambda: b"\x55" * 32, header=first.header)
    else:
        first = SimpleNamespace(hash=first.hash, header=SimpleNamespace(height=7))
    run[3] = (first, parts, second, enc)
    r = _reactor(net, sched if branch == "lane" else None)
    assert r._verify_run_batched(run) == 3
    assert net.want()[1] == "accepted"  # the signatures alone refuse nothing


def test_both_branches_row_for_row(sched, monkeypatch):
    net = Net(seed=31)
    net.flip(1, 0)
    net.flip(1, 7)
    net.flip(5, 11 if 11 not in net.blocks[5]["absent"] else 3)
    mask, want = net.want()
    lane = _verify(net, "lane", sched, monkeypatch)
    direct = _verify(net, "direct", sched, monkeypatch)
    assert lane[0] == direct[0] == want
    assert [bool(x) for x in lane[1]] == [bool(x) for x in direct[1]] == mask
    assert mask.count(False) == 3


def test_an_empty_run_and_a_run_without_rows():
    net = Net()
    r = _reactor(net, None)
    assert r._verify_run_batched([]) is None
    first, parts, second, enc = net.run()[0]
    c = second.last_commit
    nobody = SimpleNamespace(last_commit=Commit(
        c.height, c.round, c.block_id, [CommitSig.absent_sig()] * 12))
    assert r._verify_run_batched([(first, parts, nobody, enc)]) == 0


# -- the span tree


def _by_name(events):
    by = {}
    for e in events:
        by.setdefault(e["name"], []).append(e)
    return by


def test_one_tree_per_run_through_the_lane(sched, monkeypatch):
    net = Net()
    t = Tracer(ring_size=256)
    monkeypatch.setattr(trace, "tracer", t)
    r = _reactor(net, sched)
    assert r._verify_run_batched(net.run()) is None
    net.blocks[4]["absent"] |= {0, 1}
    assert r._verify_run_batched(net.run()) == 4
    events = t.dump()
    roots = [e for e in events if e["name"] == "catchup.verify_run"]
    assert [e["attrs"]["verdict"] for e in roots] == ["accepted", 4]
    assert {e["root"] for e in events} == {e["span"] for e in roots}
    for root in roots:
        mine = [e for e in events if e["root"] == root["span"]]
        by = _by_name(mine)
        assert mine[-1] is root  # written last: its children are whole before it
        assert mine[0]["name"] == "catchup.gather"
        assert sorted(e["name"] for e in mine if e["parent"] == root["span"]) == [
            "catchup.gather", "catchup.sign_bytes", "catchup.tally", "lane.flush", "lane.wait"]
        rows = root["attrs"]["rows"]
        assert root["attrs"]["blocks"] == BLOCKS and root["attrs"]["signers"] == 12
        assert by["catchup.gather"][0]["attrs"] == {"rows": rows}
        assert by["catchup.sign_bytes"][0]["attrs"] == {"rows": rows, "blocks": BLOCKS}
        assert by["lane.wait"][0]["attrs"] == {"lane": "catchup", "rows": rows}
        flush = by["lane.flush"][0]
        assert flush["attrs"] == {"lanes": "catchup", "rows": rows, "tickets": 1, "flushes": 1}
        (vb,) = by["verify_batch"]
        assert vb["parent"] == flush["span"] and vb["attrs"]["n"] == rows
        assert by["flush.record"][0]["parent"] == vb["span"]
        # the wait ends where the flush starts, and both lie inside the run
        wait = by["lane.wait"][0]
        assert root["t0_ns"] <= wait["t0_ns"] <= flush["t0_ns"]
        assert wait["t0_ns"] + wait["dur_ms"] * 1e6 <= flush["t0_ns"] + 1e3
        assert flush["t0_ns"] + flush["dur_ms"] * 1e6 <= root["t0_ns"] + root["dur_ms"] * 1e6 + 1e3


@needs_native
def test_the_device_route_hangs_in_the_runs_tree(small_rlc, prep_cfg, monkeypatch):
    """On the device route (host twins) the dispatch thread's verify_batch
    calls, the prep worker's chunks and the flush records all carry the root
    of the run that caused them; a run over the planner's chunk is several
    verify_batch calls under one lane.flush, which counts them."""
    _device_route(monkeypatch)
    prep_cfg["stream_floor"] = 16
    net = Net()
    rows = len(net.rows()[0])
    assert rows > 2 * small_rlc  # 31 rows a chunk: three verify_batch calls
    t = Tracer(ring_size=512)
    monkeypatch.setattr(trace, "tracer", t)
    s = VerifyScheduler(SchedulerConfig(catchup_max_wait=0.001), backend="jax")
    try:
        assert _reactor(net, s)._verify_run_batched(net.run()) is None
    finally:
        s.close()
    events = t.dump()
    (root,) = [e for e in events if e["name"] == "catchup.verify_run"]
    assert {e["root"] for e in events} == {root["span"]}
    by = _by_name(events)
    (flush,) = by["lane.flush"]
    assert flush["attrs"]["flushes"] == len(by["verify_batch"]) == -(-rows // small_rlc)
    assert all(e["parent"] == flush["span"] for e in by["verify_batch"])
    assert sum(e["attrs"]["n"] for e in by["verify_batch"]) == rows
    paths = [e["attrs"]["path"] for e in by["verify_batch"]]
    assert paths[:2] == ["rlc-pipelined"] * 2  # the ragged tail, under RLC_MIN, goes per row
    assert len(by["prep.chunk"]) >= 2 and len(by["flush.record"]) == len(by["verify_batch"])
    # the prep worker's and the dispatch thread's spans are all there
    assert {"prep.hash", "flush.sync", "batch_verify.flush"} <= set(by)


@pytest.mark.parametrize("branch", BRANCHES)
def test_recorder_off_constructs_no_span(branch, sched, monkeypatch):
    net = Net()
    t = Tracer(ring_size=64, enabled=False)
    monkeypatch.setattr(trace, "tracer", t)
    built = []
    monkeypatch.setattr(trace.Span, "__init__", lambda self, *a, **kw: built.append(a))
    got, _ = _verify(net, branch, sched, monkeypatch)
    assert got == "accepted"
    assert built == [] and t.dump() == []


def test_direct_branch_tree_has_no_lane_spans(monkeypatch):
    net = Net()
    t = Tracer(ring_size=64)
    monkeypatch.setattr(trace, "tracer", t)
    assert _reactor(net, None)._verify_run_batched(net.run()) is None
    events = t.dump()
    root = events[-1]
    assert root["name"] == "catchup.verify_run" and {e["root"] for e in events} == {root["span"]}
    assert sorted(e["name"] for e in events if e["parent"] == root["span"]) == [
        "catchup.gather", "catchup.sign_bytes", "catchup.tally", "verify_batch"]


def test_riders_of_one_flush_keep_their_own_waits(monkeypatch):
    """Two submits that share one combined flush: each tree holds its own
    `lane.wait`; the flush hangs under the older one's span."""
    t = Tracer(ring_size=64)
    monkeypatch.setattr(trace, "tracer", t)
    net = Net()
    _, pks, msgs, sigs, _ = net.rows()
    s = VerifyScheduler(SchedulerConfig(light_max_wait=0.2), backend="cpu")
    try:
        out = {}

        def rider(name, lo, hi):
            with trace.span("rider." + name):
                out[name] = s.verify_rows("light", pks[lo:hi], msgs[lo:hi], sigs[lo:hi])

        a = threading.Thread(target=rider, args=("a", 0, 10))
        a.start()
        while not s.stats()["lanes"]["light"]["queued_submits"]:
            pass
        b = threading.Thread(target=rider, args=("b", 10, 30))
        b.start()
        a.join()
        b.join()
    finally:
        s.close()
    assert out["a"].all() and out["b"].all() and len(out["b"]) == 20
    by = _by_name(t.dump())
    roots = {e["attrs"]["rows"]: e for e in by["lane.wait"]}
    ra, rb = by["rider.a"][0], by["rider.b"][0]
    assert roots[10]["parent"] == ra["span"] and roots[20]["parent"] == rb["span"]
    (flush,) = by["lane.flush"]
    assert flush["parent"] == ra["span"] and flush["attrs"]["tickets"] == 2
    assert flush["attrs"]["rows"] == 30 and by["verify_batch"][0]["root"] == ra["span"]


def test_interval_nests_and_costs_nothing_when_off(monkeypatch):
    t = Tracer(ring_size=16)
    monkeypatch.setattr(trace, "tracer", t)
    with trace.span("outer") as outer:
        pass
    trace.interval("waited", 1_000, 3_500_000, parent=outer, lane="catchup")
    trace.interval("alone", 5, 6)
    waited, alone = t.dump()[1:]
    assert waited["parent"] == outer.span_id and waited["root"] == outer.root
    assert waited["t0_ns"] == 1_000 and waited["dur_ms"] == pytest.approx(3.499)
    assert waited["attrs"] == {"lane": "catchup"}
    assert alone["parent"] is None and alone["root"] == alone["span"]
    t.configure(enabled=False)
    t.clear()
    trace.interval("waited", 1, 2, parent=outer)
    assert t.dump() == []


# -- the lane's wait timeout: every ticket leaves after it, but for one in
# -- flight whose inline copy would ride the same device


def _slow_lane(monkeypatch, seconds, slow_rows=0, **budgets):
    """A scheduler on the jax backend as a node builds it (`backend` None),
    whose verify_batch takes `seconds` for a flush of at least `slow_rows`
    rows (a cold compile, a recovery ladder), with a wait timeout far under
    that. Returns it and the list of verify_batch calls as (thread, rows)."""
    import time

    from tendermint_tpu.crypto import batch

    calls = []

    def slow(pubkeys, msgs, sigs, backend=None, key_types=None):
        calls.append((threading.current_thread().name, len(pubkeys)))
        if len(pubkeys) >= slow_rows:
            time.sleep(seconds)
        return np.ones(len(pubkeys), dtype=bool)

    monkeypatch.setattr(batch, "verify_batch", slow)
    monkeypatch.setattr(batch, "backend_default", lambda: "jax")
    cfg = dict(wait_timeout=0.05, catchup_max_wait=0.0, light_max_wait=0.0)
    return VerifyScheduler(SchedulerConfig(**{**cfg, **budgets})), calls


def _rows(n):
    return [b"k"] * n, [b"m"] * n, [b"s"] * n


DEVICE_ROWS = 300  # over batch._JAX_MIN_BATCH: an inline copy would ride the device


def test_a_device_sized_ticket_in_flight_is_waited_for_not_verified_twice(monkeypatch, caplog):
    s, calls = _slow_lane(monkeypatch, 0.4)
    try:
        with caplog.at_level("INFO", logger="tendermint_tpu.crypto.scheduler"):
            mask = s.verify_rows("catchup", *_rows(DEVICE_ROWS))
    finally:
        s.close()
    assert mask.all() and len(mask) == DEVICE_ROWS
    assert [n for _t, n in calls] == [DEVICE_ROWS] and s.fallbacks == 0  # no inline copy
    assert {r.levelname for r in caplog.records} == {"INFO"}  # one a timeout waited out
    assert "waiting for it" in caplog.records[0].getMessage()


@pytest.mark.parametrize("why", ["breaker_open", "cpu_backend"])
def test_a_device_sized_ticket_in_flight_leaves_where_its_inline_copy_runs_on_the_host(
        monkeypatch, why):
    """The wait stays bounded wherever the caller can free itself: with the
    breaker open, or on the cpu backend, an inline verify_batch never touches
    the device that holds the flush."""
    from tendermint_tpu.crypto import batch

    s, calls = _slow_lane(monkeypatch, 0.4, slow_rows=0)
    if why == "breaker_open":
        monkeypatch.setattr(batch.BREAKER, "allow_device", lambda: False)
    else:
        s.backend = "cpu"
    try:
        mask = s.verify_rows("catchup", *_rows(DEVICE_ROWS))
    finally:
        s.close()
    assert mask.all() and s.fallbacks == 1
    assert [n for _t, n in calls] == [DEVICE_ROWS] * 2  # the flush, and the caller's own


def test_a_small_ticket_riding_a_slow_catchup_flush_falls_back_on_the_host(monkeypatch, caplog):
    """A light-client ticket of 3 rows drained into the same combined flush
    as a catch-up run whose verify holds the dispatch thread (a cold compile,
    _bisect_recover): the RPC thread is out after `wait_timeout` by an inline
    verify of its own 3 rows, which under _JAX_MIN_BATCH runs on the host,
    while the catch-up caller, whose 300 rows would ride the device again,
    waits for the flush."""
    import time

    # the light ticket sits out its window; the run, held back by it, flushes
    # at its starvation floor (10 x 0.01 s) and takes the light ticket along
    s, calls = _slow_lane(monkeypatch, 1.0, slow_rows=DEVICE_ROWS, wait_timeout=0.3,
                          light_max_wait=5.0, catchup_max_wait=0.01)
    out = {}

    def caller(lane, n):
        t0 = time.monotonic()
        out[lane] = (s.verify_rows(lane, *_rows(n)), time.monotonic() - t0)

    light = threading.Thread(target=caller, args=("light", 3), name="rpc")
    run = threading.Thread(target=caller, args=("catchup", DEVICE_ROWS))
    try:
        with caplog.at_level("WARNING", logger="tendermint_tpu.crypto.scheduler"):
            light.start()
            while s.stats()["lanes"]["light"]["depth_rows"] != 3:
                pass
            run.start()
            light.join()
            assert run.is_alive()  # the flush both ride is still running
            run.join()
    finally:
        s.close()
    assert calls[0][1] == 3 + DEVICE_ROWS  # ONE combined flush took both tickets
    assert calls[1:] == [("rpc", 3)]  # the rider's own rows, on its own thread
    assert s.fallbacks == 1
    (light_mask, light_s), (run_mask, run_s) = out["light"], out["catchup"]
    assert light_mask.all() and len(light_mask) == 3 and light_s < 0.8
    assert run_mask.all() and len(run_mask) == DEVICE_ROWS and run_s >= 1.0
    assert "in a flush still running" in caplog.records[0].getMessage()


def test_a_ticket_still_queued_falls_back_inline(monkeypatch, caplog):
    """Behind a flush that holds the dispatch thread, a queued ticket misses
    the timeout, leaves the lane and is verified on its caller's thread,
    whatever its size."""
    s, calls = _slow_lane(monkeypatch, 0.5, slow_rows=DEVICE_ROWS)
    try:
        first = threading.Thread(target=s.verify_rows, args=("catchup", *_rows(DEVICE_ROWS)))
        first.start()
        while not calls:
            pass
        with caplog.at_level("WARNING", logger="tendermint_tpu.crypto.scheduler"):
            mask = s.verify_rows("light", *_rows(3))
        first.join()
    finally:
        s.close()
    assert mask.all() and len(mask) == 3
    assert sorted(n for _t, n in calls) == [3, DEVICE_ROWS] and s.fallbacks == 1
    assert s.stats()["lanes"]["light"]["flushes"] == 0  # the lane never flushed it
    assert "still queued" in caplog.records[0].getMessage()
