"""ISSUE 34: sequential light-client verification by runs. `Client` in
SEQUENTIAL mode fetches the heights between the trusted header and the target
in runs and verifies a run's signatures, each header's under the set of its
own height, in ONE flush. Its verdict, the class and words of the error it
raises and the heights its store holds are held to two oracles on seeded
chains of benchmark/data.py's generator: the one-header-at-a-time path
(`verifier.verify_adjacent` in a loop, what `_verify_sequential` was) and the
plain rule `benchmark/references/sequential_run.py` over `reference.py`'s
masks and `link_ok` (nothing of the program). The run bound is lowered so
that a call spans several runs and a fault can stand at a run's first and
last height. Also: through the scheduler's light lane the same; a run's span
tree; the streamed flush of three chunks on the device route (host twins)."""

import asyncio
import importlib.util
import os
import sys
from dataclasses import replace

import numpy as np
import pytest

os.environ.setdefault("TMTPU_CRYPTO_BACKEND", "cpu")

from tendermint_tpu.config.config import SchedulerConfig
from tendermint_tpu.crypto import batch, merkle, scheduler
from tendermint_tpu.crypto.scheduler import VerifyScheduler
from tendermint_tpu.libs import trace
from tendermint_tpu.libs.kvdb import MemDB
from tendermint_tpu.libs.trace import Tracer
from tendermint_tpu.light import Client, LightStore, TrustOptions, verifier
from tendermint_tpu.light import client as client_mod
from tendermint_tpu.light.client import SEQUENTIAL
from tendermint_tpu.light.provider import MockProvider
from tendermint_tpu.light.verifier import ErrOldHeaderExpired, LightError
from tendermint_tpu.types import validator_set
from tendermint_tpu.types.light import LightBlock

from test_prep_pipeline import needs_native, prep_cfg, small_rlc  # noqa: F401
from test_trace import _device_route

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import data  # noqa: E402
import reference  # noqa: E402


def _load(path):
    spec = importlib.util.spec_from_file_location("ref_" + os.path.basename(path)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


RULE = _load(os.path.join(BENCH, "references", "sequential_run.py")).verdict
ENTRY = _load(os.path.join(BENCH, "entries", "light_sequence.py"))

CONFIG = {"chain_id": "seq-run-chain", "validators": 8, "voting_power": 3, "headers": True,
          "validator_changes_per_height": 1, "absent_share": 0.0, "verdict_rule": "sequential_run"}
HEADERS = 14  # heights 2 .. 15 above the trusted root at 1
MIX = {"ring_commits": 1, "commits_per_call": HEADERS, "first_height": 2}
RUN_HEADERS = 4  # the lowered bound: runs of 4, 4, 4, 2 headers
PERIOD_NS = 24 * 3600 * 10**9
NOW_NS = data.BASE_TIME_NS + 3600 * 10**9


def chain(seed, config=CONFIG, mix=MIX):
    vals = data.make_validators(seed, config)
    (item,) = data.make_ring(seed, config, mix, vals)
    return vals, item


def plain_set_hash(vs) -> bytes:
    """ValidatorSet.hash() by the plain formula, no memo."""
    return merkle.hash_from_byte_slices([v.simple_bytes() for v in vs.validators])


def light_block(c, vals=None):
    """The program's LightBlock of a chain's commit, as the cell's entry
    driver builds it (`vals`: handed over with another set than the one that
    signed)."""
    lb = ENTRY.light_block(c, ENTRY.validator_set(c.vals))
    return lb if vals is None else LightBlock(lb.signed_header, ENTRY.validator_set(vals))


# -- the cases: a run with one thing wrong at block k


def sound(config, item, k, rng):
    return item


def broken_link(config, item, k, rng):
    """data.entry_probes' `broken_link` at a chosen block: its header made
    anew for a set in which a fresh key replaces the oldest, the headers
    above linked to it and signed again."""
    c = item[k]
    others = data._changed(c.vals, 1, rng)
    forged = [data._reheaded(config, c, c.prev, others, validators_hash=reference.validators_hash(
        others.pubkeys, others.powers))]
    for above in item[k + 1:]:
        forged.append(data._reheaded(config, above, forged[-1], above.vals,
                                     last_block_hash=forged[-1].block_hash))
    return item[:k] + forged


def short_power(config, item, k, rng):
    c = item[k]
    gone = set(rng.choice(c.present(), 3, replace=False).tolist())  # 5 of 8 sign: not over 2/3
    return data.with_commit(item, k, data._without(c, gone))


def flipped(count):
    def case(config, item, k, rng):
        c = item[k]
        sigs = list(c.sigs)
        for n, i in enumerate(rng.choice(c.present(), count, replace=False).tolist()):
            sigs[i] = data.flip_bit(sigs[i], "sR"[n % 2])
        return data.with_commit(item, k, replace(c, sigs=sigs))
    return case


CASES = {"sound": sound, "broken_link": broken_link, "short_power": short_power,
         "valid_power_short": flipped(3), "one_wrong_signature": flipped(1)}
# a run's first and last height, the call's first and last, and one inside
BLOCKS = [0, RUN_HEADERS - 1, RUN_HEADERS, 6, HEADERS - 1]


# -- the three ways to the same answer


def by_the_rule(config, vals, item) -> str:
    idx, pks, msgs, sigs = data.rows_of(config, vals, item)
    return RULE(reference.verify_rows(pks, msgs, sigs), idx, vals.powers, vals.total_power,
                data.blocks_of(item))


def outcome(err, store, first_height) -> dict:
    """What a path gave: the rule's words from the height it refused, the
    error's class and words, the heights its store holds."""
    if err is None:
        said = "accepted"
    else:
        k = store.heights()[-1] + 1 - first_height
        said = (f"not enough power at block #{k}" if "insufficient voting power" in str(err)
                else f"broken link at block #{k}")
    return {"said": said, "error": None if err is None else (type(err).__name__, str(err)),
            "heights": store.heights()}


def one_header_at_a_time(config, blocks, now_ns=NOW_NS) -> dict:
    """`_verify_sequential` as it was: verify_adjacent a height, each saved
    once verified."""
    store = LightStore(MemDB())
    store.save_light_block(blocks[0])
    err = None
    for prev, lb in zip(blocks, blocks[1:]):
        try:
            verifier.verify_adjacent(config["chain_id"], prev.signed_header, lb.signed_header,
                                     lb.validator_set, PERIOD_NS, now_ns, 10 * 10**9)
        except (LightError, ValueError) as e:
            err = e
            break
        store.save_light_block(lb)
    return outcome(err, store, blocks[1].height)


def by_runs(config, blocks, now_ns=NOW_NS, witnesses=False, store=None) -> dict:
    """The client in SEQUENTIAL mode from the trusted root, which its store
    holds already, to the last block's height."""
    store = store or LightStore(MemDB())
    store.save_light_block(blocks[0])
    provider = MockProvider(config["chain_id"], {lb.height: lb for lb in blocks})
    root = blocks[0]
    client = Client(config["chain_id"], TrustOptions(PERIOD_NS, root.height, root.hash()),
                    provider, [provider] if witnesses else [], store,
                    verification_mode=SEQUENTIAL)

    async def go():
        await client.initialize(now_ns)
        await client.verify_light_block_at_height(blocks[-1].height, now_ns)

    err = None
    try:
        asyncio.run(go())
    except (LightError, ValueError) as e:
        err = e
    return outcome(err, store, blocks[1].height)


@pytest.fixture
def runs_of_four(monkeypatch):
    monkeypatch.setattr(client_mod, "verify_run_rows",
                        lambda: RUN_HEADERS * CONFIG["validators"])


def blocks_of_case(seed, case, k, config=CONFIG, mix=MIX):
    vals, item = chain(seed, config, mix)
    item = CASES[case](config, item, k, np.random.default_rng([seed, 34]))
    return vals, item, [light_block(item[0].prev)] + [light_block(c) for c in item]


@pytest.mark.parametrize("case,k", [("sound", 0)] + [
    (case, k) for case in sorted(CASES) if case != "sound" for k in BLOCKS])
def test_runs_give_what_one_header_at_a_time_and_the_plain_rule_give(runs_of_four, case, k):
    vals, item, blocks = blocks_of_case(340 + k, case, k)
    want = one_header_at_a_time(CONFIG, blocks)
    got = by_runs(CONFIG, blocks)
    assert got == want
    assert got["said"] == by_the_rule(CONFIG, vals, item)
    if case in ("sound", "one_wrong_signature"):
        assert got["said"] == "accepted" and got["heights"] == list(range(1, HEADERS + 2))
    else:
        words = "broken link" if case == "broken_link" else "not enough power"
        assert got["said"] == f"{words} at block #{k}"
        assert got["error"][0] == "ErrInvalidHeader"
        # the store never holds a height at or past k
        assert got["heights"] == list(range(1, k + 2))


@pytest.mark.parametrize("memo", ["cold", "warm"])
@pytest.mark.parametrize("k", BLOCKS)
def test_a_set_that_is_not_the_headers_is_refused_at_its_height(runs_of_four, k, memo):
    """The primary hands over height k's header with another set than the one
    its validators_hash names: the host check of that header refuses it, the
    heights below it are verified and saved. (The target's own light block is
    held to its header before anything is fetched, as ever: verify_light_block
    calls its validate_basic first.) With every leaf of the chain in the
    validator sets' leaf memo as with none: the same height, error and words."""
    vals, item = chain(350 + k)
    others = data._changed(item[k].vals, 1, np.random.default_rng(k))
    blocks = [light_block(item[0].prev)] + [
        light_block(c, others if j == k else None) for j, c in enumerate(item)]
    validator_set._leaf_memo.clear()
    if memo == "warm":
        for lb in blocks:
            lb.validator_set.hash()
    got = by_runs(CONFIG, blocks)
    if k == HEADERS - 1:
        assert got["error"][0] == "ValueError" and "expected validators hash" in got["error"][1]
        assert got["heights"] == [1]
        return
    assert got == one_header_at_a_time(CONFIG, blocks)
    handed_over = blocks[k + 1]
    assert got["error"] == ("ErrInvalidHeader", (
        f"expected new header validators ({handed_over.header.validators_hash.hex()}) to match "
        f"those supplied ({plain_set_hash(handed_over.validator_set).hex()})"))
    assert got["heights"] == list(range(1, k + 2))
    # the plain rule, given the set that was handed over, calls the same block unlinked
    handed = data.with_commit(item, k, replace(item[k], vals=others))
    assert by_the_rule(CONFIG, vals, handed) == got["said"] == f"broken link at block #{k}"


def test_an_expired_root_refuses_the_first_height(runs_of_four):
    vals, item, blocks = blocks_of_case(360, "sound", 0)
    late = blocks[0].time_ns + PERIOD_NS
    got = by_runs(CONFIG, blocks, now_ns=late)
    want = one_header_at_a_time(CONFIG, blocks, now_ns=late)
    assert got["error"] == want["error"] and got["error"][0] == "ErrOldHeaderExpired"
    assert got["heights"] == want["heights"] == [1]
    with pytest.raises(ErrOldHeaderExpired):
        verifier.check_adjacent(CONFIG["chain_id"], blocks[0].signed_header,
                                blocks[1].signed_header, blocks[1].validator_set,
                                PERIOD_NS, late, 10 * 10**9)


@pytest.mark.parametrize("bound_headers", [1, 3, HEADERS, 10 * HEADERS])
def test_the_bound_only_cuts_the_runs(monkeypatch, bound_headers):
    """Whatever the bound, the same verdict and store; the runs are as long as
    it allows, and a run of one header is the one-header call (its spans)."""
    monkeypatch.setattr(client_mod, "verify_run_rows", lambda: bound_headers * 8)
    t = Tracer(ring_size=1024)
    monkeypatch.setattr(trace, "tracer", t)
    vals, item, blocks = blocks_of_case(370, "short_power", 9)
    got = by_runs(CONFIG, blocks, witnesses=True)
    assert got["said"] == "not enough power at block #9" and got["heights"] == list(range(1, 11))
    roots = [e for e in t.dump() if e["name"] == "light.verify_run"]
    singles = [e for e in t.dump() if e["name"] == "commit.verify"]
    if bound_headers == 1:
        assert not roots and len(singles) == 10  # nine accepted, the tenth refused
    else:
        want = [3, 3, 3, 3] if bound_headers == 3 else [HEADERS]
        assert [e["attrs"]["headers"] for e in roots] == want[:len(roots)]
        assert roots[-1]["attrs"]["verdict"] == "refused at height 11: ErrInvalidHeader"
        assert all(e["attrs"]["verdict"] == "accepted" for e in roots[:-1])
        assert not singles


def test_the_default_bound_is_three_planner_chunks():
    assert client_mod.VERIFY_RUN_CHUNKS == 3
    assert client_mod.verify_run_rows() == 3 * batch.planner_chunk_rows()
    if batch.planner_budget() == 24576:
        assert client_mod.verify_run_rows() == 36861 >= 33300  # the cell's call is ONE run


def test_the_target_is_saved_after_the_witnesses_and_a_lying_witness_stops_it(runs_of_four):
    vals, item, blocks = blocks_of_case(380, "sound", 0)
    forged = broken_link(CONFIG, item, HEADERS - 1, np.random.default_rng(1))
    store = LightStore(MemDB())
    primary = MockProvider(CONFIG["chain_id"], {lb.height: lb for lb in blocks})
    witness = MockProvider(CONFIG["chain_id"], {blocks[-1].height: light_block(forged[-1])})
    client = Client(CONFIG["chain_id"], TrustOptions(PERIOD_NS, 1, blocks[0].hash()), primary,
                    [witness], store, verification_mode=SEQUENTIAL)

    async def go():
        await client.initialize(NOW_NS)
        await client.verify_light_block_at_height(blocks[-1].height, NOW_NS)

    from tendermint_tpu.light import ErrConflictingHeaders

    with pytest.raises(ErrConflictingHeaders):
        asyncio.run(go())
    assert store.heights() == list(range(1, HEADERS + 1))  # every height but the target


# -- the event loop, a fetch that fails, a primary that was replaced


def test_a_run_is_verified_off_the_event_loop(runs_of_four, monkeypatch):
    """Checks, flush and store of a run park an executor thread: while one is
    verified the loop goes on serving its other tasks."""
    import threading

    vals, item, blocks = blocks_of_case(385, "sound", 0)
    loop_thread, ran_on, ticks_during = [], [], []
    inner = verifier.verify_adjacent_run
    ticks = [0]

    def slow_run(*a, **kw):
        ran_on.append(threading.get_ident())
        before = ticks[0]
        threading.Event().wait(0.05)  # time for twenty-odd ticks of the loop's ticker
        ticks_during.append(ticks[0] - before)
        return inner(*a, **kw)

    monkeypatch.setattr(verifier, "verify_adjacent_run", slow_run)
    store = LightStore(MemDB())
    store.save_light_block(blocks[0])
    provider = MockProvider(CONFIG["chain_id"], {lb.height: lb for lb in blocks})
    client = Client(CONFIG["chain_id"], TrustOptions(PERIOD_NS, 1, blocks[0].hash()), provider, [],
                    store, verification_mode=SEQUENTIAL)

    async def ticker():
        while True:
            ticks[0] += 1
            await asyncio.sleep(0.002)

    async def go():
        loop_thread.append(threading.get_ident())
        t = asyncio.ensure_future(ticker())
        await client.initialize(NOW_NS)
        await client.verify_light_block_at_height(blocks[-1].height, NOW_NS)
        t.cancel()

    asyncio.run(go())
    assert store.heights() == list(range(1, HEADERS + 2))
    assert len(ran_on) == 4 and loop_thread[0] not in ran_on
    assert all(n >= 5 for n in ticks_during), ticks_during


class FailingAt(MockProvider):
    """Serves its blocks until asked for `height`, which it cannot give."""

    def __init__(self, chain_id, blocks, height):
        super().__init__(chain_id, blocks)
        self.fails_at = height

    async def light_block(self, height):
        from tendermint_tpu.light.provider import ProviderError

        if height == self.fails_at:
            raise ProviderError(f"no block at {height}")
        return await super().light_block(height)


@pytest.mark.parametrize("fails_at", [2, 4, 6, 7, 10])
def test_a_fetch_that_fails_mid_run_keeps_what_was_fetched_before_it(runs_of_four, fails_at):
    """As when a header was verified before the next was fetched: the heights
    below the one the primary could not give are verified and saved, then the
    fetch's error is raised."""
    from tendermint_tpu.light.client import ErrNoWitnesses

    vals, item, blocks = blocks_of_case(386, "sound", 0)
    served = {lb.height: lb for lb in blocks}
    store = LightStore(MemDB())
    store.save_light_block(blocks[0])
    primary = FailingAt(CONFIG["chain_id"], served, None)
    client = Client(CONFIG["chain_id"], TrustOptions(PERIOD_NS, 1, blocks[0].hash()), primary, [],
                    store, verification_mode=SEQUENTIAL)

    async def go():
        await client.initialize(NOW_NS)
        target = await primary.light_block(blocks[-1].height)
        primary.fails_at = fails_at
        await client.verify_light_block(target, NOW_NS)

    with pytest.raises(ErrNoWitnesses):
        asyncio.run(go())
    assert store.heights() == list(range(1, fails_at))


@pytest.mark.parametrize("forged_from", [None, 7])
def test_the_first_header_a_new_primary_serves_is_a_run_of_its_own(
        runs_of_four, monkeypatch, forged_from):
    """The primary fails at height 7 and a witness is promoted: the heights
    the old primary served are one run (2-6), the witness's first header is
    verified alone (the one-header call), and its later ones in full runs
    again. A witness that serves a chain it cannot have signed is refused on
    that one header, with nothing of it saved."""
    t = Tracer(ring_size=1024)
    monkeypatch.setattr(trace, "tracer", t)
    vals, item, blocks = blocks_of_case(387, "sound", 0)
    served = {lb.height: lb for lb in blocks}
    theirs = dict(served)
    if forged_from is not None:
        k = forged_from - 2
        bad = flipped(3)(CONFIG, item, k, np.random.default_rng(5))
        theirs[forged_from] = light_block(bad[k])
    store = LightStore(MemDB())
    store.save_light_block(blocks[0])
    primary = FailingAt(CONFIG["chain_id"], served, None)
    witness = MockProvider(CONFIG["chain_id"], theirs)
    client = Client(CONFIG["chain_id"], TrustOptions(PERIOD_NS, 1, blocks[0].hash()), primary,
                    [witness], store, verification_mode=SEQUENTIAL)

    async def go():
        await client.initialize(NOW_NS)
        target = await primary.light_block(blocks[-1].height)
        primary.fails_at = 7
        await client.verify_light_block(target, NOW_NS)

    if forged_from is None:
        asyncio.run(go())
        assert store.heights() == list(range(1, HEADERS + 2))
    else:
        with pytest.raises(LightError, match="invalid commit"):
            asyncio.run(go())
        assert store.heights() == list(range(1, 7))
    assert client.primary is witness
    runs = [e["attrs"]["headers"] for e in t.dump() if e["name"] == "light.verify_run"]
    singles = [e["attrs"]["height"] for e in t.dump() if e["name"] == "commit.verify"]
    # 2-5 is a run (the bound); 6 is what was left of the old primary's next run when it failed;
    # 7 is the witness's first, alone; then 8-11 and 12-15
    assert singles == [6, 7]
    assert runs == ([4] if forged_from else [4, 4, 4])


# -- through the scheduler's light lane


@pytest.fixture
def light_lane():
    sched = VerifyScheduler(SchedulerConfig(light_max_wait=0.0), backend="cpu")
    scheduler.set_default(sched)
    yield sched
    scheduler.set_default(None)
    sched.close()


@pytest.mark.parametrize("case,k,flushes,headers", [
    ("sound", 0, 4, HEADERS),       # runs of 4, 4, 4 and 2 headers
    ("valid_power_short", 5, 2, 8),  # the second run holds block 5, whole
    ("broken_link", 4, 1, 4),       # the second run's first header: nothing gathered, no flush
    ("broken_link", 6, 2, 6),       # the second run stops gathering at the broken height
])
def test_with_a_scheduler_installed_the_run_rides_the_light_lane(
        runs_of_four, light_lane, case, k, flushes, headers):
    vals, item, blocks = blocks_of_case(390 + k, case, k)
    before = light_lane.stats()["lanes"]["light"]
    got = by_runs(CONFIG, blocks)
    after = light_lane.stats()["lanes"]["light"]
    assert got["said"] == by_the_rule(CONFIG, vals, item)
    assert after["flushes"] - before["flushes"] == flushes
    assert after["rows_total"] - before["rows_total"] == headers * 8
    assert light_lane.fallbacks == 0
    scheduler.set_default(None)  # and with none installed the same
    assert got == by_runs(CONFIG, blocks) == one_header_at_a_time(CONFIG, blocks)


# -- the span tree of a run

def stored_bytes(store, blocks) -> int:
    """The lengths of the records the store's db holds for these blocks."""
    return sum(len(store.db.get(b"lb/" + lb.height.to_bytes(8, "big"))) for lb in blocks)


STAGES = ["light.fetch", "light.header_checks", "light.gather", "light.sign_bytes",
          "verify_batch", "light.tally", "light.store"]
AROUND = ["light.load"] * 3 + ["light.target_checks", "light.witness", "light.save"]


def test_a_run_is_one_span_tree_with_one_span_a_stage(monkeypatch):
    t = Tracer(ring_size=1024)
    monkeypatch.setattr(trace, "tracer", t)
    vals, item, blocks = blocks_of_case(400, "sound", 0)
    store = LightStore(MemDB())
    assert by_runs(CONFIG, blocks, witnesses=True, store=store)["said"] == "accepted"
    events = t.dump()
    (root,) = [e for e in events if e["name"] == "light.verify_run"]
    mine = [e for e in events if e["root"] == root["span"]]
    assert len(events) <= 60
    # outside its one tree, the client's work around the run: a span a site,
    # each a root of its own, none a header (the store's reads at each of the
    # three entries the call passes through)
    around = [e for e in events if e["root"] != root["span"]]
    assert sorted(e["name"] for e in around) == sorted(AROUND)
    assert all(e["parent"] is None and e["root"] == e["span"] for e in around)
    assert [e for e in around if e["name"] == "light.witness"][0]["attrs"] == {"witnesses": 1}
    (target,) = [e for e in around if e["name"] == "light.target_checks"]
    (save,) = [e for e in around if e["name"] == "light.save"]
    assert target["t0_ns"] < root["t0_ns"] and save["t0_ns"] > root["t0_ns"]
    children = [e for e in mine if e["parent"] == root["span"]]
    assert [e["name"] for e in sorted(children, key=lambda e: e["t0_ns"])] == STAGES
    assert mine[0]["name"] == "light.fetch" and mine[-1] is root  # first written, and last
    by = {e["name"]: e for e in children}
    leaves = {k: v for k, v in by["light.header_checks"]["attrs"].items() if k != "headers"}
    assert set(leaves) == {"set_leaves", "set_leaf_hits"} and leaves["set_leaves"] == HEADERS * 8
    assert root["attrs"] == {"headers": HEADERS, "rows": HEADERS * 8, "sets": HEADERS,
                             "flushes": 1, "verdict": "accepted", **leaves}
    assert by["light.fetch"]["attrs"] == {"headers": HEADERS}
    assert by["light.fetch"]["t0_ns"] == root["t0_ns"]  # the root began when its fetch began
    assert by["light.header_checks"]["attrs"]["headers"] == HEADERS
    assert by["light.gather"]["attrs"] == {"rows": HEADERS * 8}
    assert by["light.sign_bytes"]["attrs"] == {"rows": HEADERS * 8, "headers": HEADERS}
    # the target is saved later; the bytes are the records the db holds
    assert by["light.store"]["attrs"] == {
        "headers": HEADERS - 1, "bytes": stored_bytes(store, blocks[1:-1])}
    for e in mine:
        assert e["t0_ns"] >= root["t0_ns"]
    assert root["dur_ms"] >= sum(e["dur_ms"] for e in children) * 0.99


def test_the_header_checks_count_the_leaves_asked_for_and_found(monkeypatch):
    """From a cleared memo a run hashes each distinct key once: 8 for its
    first set and the ONE key replaced at every height above; a second run of
    the same chain finds every leaf (what a later lap of the cell's ring reads)."""
    t = Tracer(ring_size=256)
    monkeypatch.setattr(trace, "tracer", t)
    vals, item, blocks = blocks_of_case(403, "sound", 0)
    distinct = {(v.pub_key.bytes(), v.voting_power) for lb in blocks[1:]
                for v in lb.validator_set.validators}
    assert len(distinct) == 8 + HEADERS - 1
    validator_set._leaf_memo.clear()
    for lap in range(2):
        with trace.span("light.verify_run") as root:
            verified, failure = verifier.verify_adjacent_run(
                CONFIG["chain_id"], blocks[0].signed_header, blocks[1:], PERIOD_NS, NOW_NS,
                10 * 10**9, batch.FlushAccumulator(), root)
        assert (verified, failure) == (HEADERS, None)
    checks = [e["attrs"] for e in t.dump() if e["name"] == "light.header_checks"]
    roots = [e["attrs"] for e in t.dump() if e["name"] == "light.verify_run"]
    want = [{"set_leaves": HEADERS * 8, "set_leaf_hits": HEADERS * 8 - len(distinct)},
            {"set_leaves": HEADERS * 8, "set_leaf_hits": HEADERS * 8}]
    assert checks == [dict(w, headers=HEADERS) for w in want]
    assert [{k: a[k] for k in w} for a, w in zip(roots, want)] == want


def test_a_refused_run_names_the_height_and_saves_what_stands_below(monkeypatch):
    t = Tracer(ring_size=1024)
    monkeypatch.setattr(trace, "tracer", t)
    vals, item, blocks = blocks_of_case(401, "broken_link", 5)
    store = LightStore(MemDB())
    got = by_runs(CONFIG, blocks, store=store)
    assert got["heights"] == list(range(1, 7))
    (root,) = [e for e in t.dump() if e["name"] == "light.verify_run"]
    assert root["attrs"]["verdict"] == "refused at height 7: ErrInvalidHeader"
    assert root["attrs"]["rows"] == 5 * 8 and root["attrs"]["sets"] == 5
    assert root["attrs"]["error"] == "ErrInvalidHeader"
    by = {e["name"]: e for e in t.dump() if e["parent"] == root["span"]}
    assert by["light.header_checks"]["attrs"]["headers"] == 5
    assert by["light.store"]["attrs"] == {"headers": 5, "bytes": stored_bytes(store, blocks[1:6])}


def test_nothing_is_constructed_with_the_recorder_off(monkeypatch):
    monkeypatch.setattr(trace, "tracer", Tracer(ring_size=64, enabled=False))
    built = []
    monkeypatch.setattr(trace.Span, "__init__", lambda self, *a, **kw: built.append(a))
    vals, item, blocks = blocks_of_case(402, "sound", 0)
    assert by_runs(CONFIG, blocks)["said"] == "accepted" and not built


# -- the streamed flush of a run on the device route (host twins)


@needs_native
@pytest.mark.parametrize("case,k", [("sound", 0), ("valid_power_short", 6)])
def test_a_run_over_the_planners_budget_is_one_streamed_flush_of_three_chunks(
        small_rlc, prep_cfg, monkeypatch, case, k):
    """31 rows a chunk (small_rlc): the default bound of three chunks holds 11
    headers of 8 rows, 88 rows = chunks of 31, 31 and 26 on the device route.
    Sound: path `rlc-streamed`, the record's `chunks` 3 and its padding;
    refused: the exact mask through `rlc-streamed-recovery`, the same verdict."""
    _device_route(monkeypatch)
    t = Tracer(ring_size=1024)
    monkeypatch.setattr(trace, "tracer", t)
    mix = dict(MIX, commits_per_call=11)
    vals, item, blocks = blocks_of_case(410 + k, case, k, mix=mix)
    assert client_mod.verify_run_rows() == 93
    trace.reset_stats()
    got = by_runs(CONFIG, blocks)
    assert got["said"] == by_the_rule(CONFIG, vals, item)
    events = t.dump()
    (root,) = [e for e in events if e["name"] == "light.verify_run"]
    assert root["attrs"]["rows"] == 88 and root["attrs"]["flushes"] == 1
    (rec,) = [e["attrs"] for e in events if e["name"] == "batch_verify.flush"]
    assert rec["n"] == 88 and rec["backend"] == "jax" and rec["chunks"] == 3
    assert rec["chunk_lanes"] == 64
    if case == "sound":
        assert got["said"] == "accepted" and rec["path"] == "rlc-streamed"
        assert rec["padding_lanes"] == 3 * 64 - (2 * 88 + 3) and rec["n_valid"] == 88
        assert rec["prep_overlap_ms"] is not None and rec["prep_ms"] > 0
        mine = [e for e in events if e["root"] == root["span"]]
        by = {}
        for e in mine:
            by.setdefault(e["name"], []).append(e)
        assert [e["attrs"]["chunk"] for e in by["prep.chunk"]] == [0, 1, 2]
        assert len(by["flush.prep_wait"]) == 3 and len(by["flush.sync"]) == 4
        assert by["rlc.streamed"][0]["parent"] == by["verify_batch"][0]["span"]
        # the twins stand in for 3 kernel.rlc_partial_submit and 6 dispatch spans
        assert len(mine) + 9 <= 60
    else:
        assert got["said"] == "not enough power at block #6"
        assert rec["path"] == "rlc-streamed-recovery" and rec["n_valid"] == 85
        assert got == one_header_at_a_time(CONFIG, blocks)
