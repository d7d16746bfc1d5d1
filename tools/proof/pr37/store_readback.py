#!/usr/bin/env python3
"""What the light client's store holds after one call of light-seq-100.sequence,
read back: the cell's chain from a seed as benchmark/entries/light_sequence.py
builds it, ONE call as that entry makes it (a fresh LightStore over a memory db
holding the trusted root, a SEQUENTIAL client, the provider as primary and
witness) over a db this script keeps, then a FRESH LightStore over that db and
every stored block compared with the served one field by field. The same
blocks then go through a LightStore over an SQLiteDB and are read back by
ANOTHER process (this script with --read-sqlite), compared by a digest of the
same fields. Prints one JSON line; exits 1 where anything differs.
    python tools/proof/pr37/store_readback.py [--seed N] [--rehearse VALIDATORS]
On the chip the call's flush is the cell's (rlc-streamed, on the device);
--rehearse N walks it on the CPU with N validators a set."""
import argparse, dataclasses, hashlib, json, os, subprocess, sys, tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
CELL = "light-seq-100.sequence"


def fields(lb) -> dict:
    """Everything a light block holds, as plain values."""
    header, commit, vs = lb.signed_header.header, lb.signed_header.commit, lb.validator_set

    def validator(v):
        return (v.pub_key.type_name(), v.pub_key.bytes(), v.address,
                v.voting_power, v.proposer_priority)

    return {
        "header": {f.name: getattr(header, f.name) for f in dataclasses.fields(header)},
        "commit": (commit.height, commit.round, commit.block_id.hash,
                   commit.block_id.part_set_header.total, commit.block_id.part_set_header.hash),
        "signatures": [(int(cs.block_id_flag), cs.validator_address, cs.timestamp_ns, cs.signature)
                       for cs in commit.signatures],
        "validators": [validator(v) for v in vs.validators],
        "proposer": validator(vs.proposer) if vs.proposer else None,
    }


def digest(lb) -> str:
    return hashlib.sha256(repr(fields(lb)).encode()).hexdigest()


def read_sqlite(path: str) -> int:
    """The other process: a fresh LightStore over the SQLite file, a digest a height."""
    from tendermint_tpu.libs.kvdb import SQLiteDB
    from tendermint_tpu.light import LightStore

    store = LightStore(SQLiteDB(path))
    print(json.dumps({str(h): digest(store.light_block(h)) for h in store.heights()}))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2147493701)
    ap.add_argument("--rehearse", type=int, default=0, metavar="N")
    ap.add_argument("--read-sqlite", default="", metavar="PATH")
    args = ap.parse_args()
    if args.read_sqlite:
        return read_sqlite(args.read_sqlite)
    if args.rehearse:
        os.environ.setdefault("TMTPU_CRYPTO_BACKEND", "cpu")

    import data, spec
    from tendermint_tpu.libs import trace
    from tendermint_tpu.libs.kvdb import MemDB, SQLiteDB
    from tendermint_tpu.light import Client, LightStore, TrustOptions
    from tendermint_tpu.light.client import SEQUENTIAL
    from tendermint_tpu.light.provider import MockProvider
    from tendermint_tpu.ops.aot_cache import configure_compile_cache
    from tendermint_tpu.types.light import light_block_to_json

    configure_compile_cache()
    cell = spec.Cell(spec.load_benchmark(ROOT), CELL)
    vals = data.make_validators(args.seed, cell.config, args.rehearse or None)
    ring = data.make_ring(args.seed, cell.config, dict(cell.traffic, ring_commits=1), vals)
    entry = cell.entry()
    entry.configure(cell.traffic)
    state = entry.build(cell.config, vals, ring)
    item = state.items[0]

    db = MemDB()
    store = LightStore(db)
    store.save_light_block(item.root)
    provider = MockProvider(state.chain_id, item.blocks)
    client = Client(state.chain_id,
                    TrustOptions(state.period_ns, item.root.height, item.root_hash),
                    provider, [provider], store, verification_mode=SEQUENTIAL)

    async def go():
        await client.initialize(state.now_ns)
        await client.verify_light_block_at_height(item.last_height, state.now_ns)

    trace.tracer.clear()
    state.loop.run_until_complete(go())
    (span,) = [e for e in trace.tracer.dump() if e["name"] == "light.store"]

    fresh = LightStore(db)
    keys = {h: b"lb/" + h.to_bytes(8, "big") for h in item.blocks}
    unequal = [h for h, served in item.blocks.items()
               if fresh.light_block(h) is served or fields(fresh.light_block(h)) != fields(served)]
    run = [h for h in item.blocks if h not in (item.root.height, item.last_height)]

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "light.db")
        sql = SQLiteDB(path)
        on_disk = LightStore(sql)
        for lb in item.blocks.values():
            on_disk.save_light_block(lb)
        sql.close()
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--read-sqlite", path],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, check=True)
    other = json.loads(child.stdout.strip().splitlines()[-1])
    want = {str(h): digest(lb) for h, lb in item.blocks.items()}

    out = {
        "cell": CELL, "seed": args.seed, "validators_a_set": len(item.root.validator_set),
        "served_blocks": len(item.blocks), "stored_heights": len(fresh.heights()),
        "heights_as_served": fresh.heights() == sorted(item.blocks),
        "read_back_equal_field_by_field": len(item.blocks) - len(unequal),
        "unequal_heights": unequal,
        "first_bytes": sorted({db.get(k)[:1].hex() for k in keys.values()}),
        "bytes_in_db": sum(len(db.get(k)) for k in keys.values()),
        "bytes_of_the_run": sum(len(db.get(keys[h])) for h in run),
        "light.store": span["attrs"], "light.store_ms": span["dur_ms"],
        "bytes_as_json_the_parent_wrote": sum(
            len(json.dumps(light_block_to_json(lb), separators=(",", ":"))) for lb in item.blocks.values()),
        "sqlite_other_process_equal": sum(other.get(h) == d for h, d in want.items()),
        "sqlite_other_process_heights": len(other),
        "flush": {k: v for k, v in trace.verify_stats()["last_flush"].items()
                  if k in ("path", "n", "chunks", "backend")},
    }
    ok = (not unequal and out["heights_as_served"] and other == want
          and out["first_bytes"] == ["01"]
          and span["attrs"] == {"headers": len(run), "bytes": out["bytes_of_the_run"]})
    out["ok"] = ok
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
