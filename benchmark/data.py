"""The inputs, from --seed alone: a validator set and a ring of signed
commits, as plain bytes and numbers. Signing is the validators' work and is
done here with OpenSSL over the reference's own sign bytes, so a program
that encodes a vote differently rejects what this signs. Every seed makes
the same sizes; only keys, block ids, timestamps and signatures differ.

An item is what one call verifies: one commit (a CommitData), or, where the
mix states `commits_per_call` over 1, a run of that many commits at
consecutive heights (a list of CommitData). `commits_of`, `n_rows`,
`blocks_of` and `rows_of` read either."""

from __future__ import annotations

from dataclasses import dataclass, field, replace

import numpy as np
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

from reference import FLAG_ABSENT, FLAG_COMMIT, SignBytes, address

BASE_TIME_NS = 1_700_000_000_000_000_000


@dataclass
class ValidatorData:
    pubkeys: list  # validator order: power descending, then address
    powers: list
    privs: list = field(repr=False, default_factory=list)

    @property
    def total_power(self) -> int:
        return sum(self.powers)


@dataclass
class CommitData:
    height: int
    round: int
    block_hash: bytes
    parts_total: int
    parts_hash: bytes
    flags: list       # per validator: FLAG_COMMIT or FLAG_ABSENT
    timestamps: list  # per validator, ns
    sigs: list        # per validator, b"" where absent
    tampered: tuple = ()  # validator indices whose signature was altered

    def present(self) -> list:
        return [i for i, f in enumerate(self.flags) if f != FLAG_ABSENT]


def make_validators(seed: int, config: dict, rows: int | None = None) -> ValidatorData:
    """Keys from the seed; the stake is the configuration's: `voting_powers`,
    one entry a validator as a genesis file holds them (a rehearsal of N
    takes the first N), or the one `voting_power` for all. Ordered as the
    program and upstream order a set: power descending, then address."""
    n = rows or int(config["validators"])
    rng = np.random.default_rng([seed, 1])
    key_seeds = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    privs = [Ed25519PrivateKey.from_private_bytes(s.tobytes()) for s in key_seeds]
    pubs = [p.public_key().public_bytes_raw() for p in privs]
    if "voting_powers" in config:
        powers = [int(p) for p in config["voting_powers"][:n]]
    else:
        powers = [int(config["voting_power"])] * n
    order = sorted(range(n), key=lambda i: (-powers[i], address(pubs[i])))
    return ValidatorData([pubs[i] for i in order], [powers[i] for i in order],
                         [privs[i] for i in order])


def sign_bytes_of(chain_id: str, c: CommitData) -> SignBytes:
    return SignBytes(chain_id, c.height, c.round, c.block_hash, c.parts_total,
                     c.parts_hash)


def flip_bit(sig: bytes, kind: str) -> bytes:
    """One bit of R (byte 7) or of s (byte 33, so that s stays canonical and
    the row reaches the device's sum instead of the host's range check)."""
    at = 7 if kind == "R" else 33
    return sig[:at] + bytes([sig[at] ^ 0x20]) + sig[at + 1:]


def commits_of(item) -> list:
    """The commits of an item, in block order."""
    return [item] if isinstance(item, CommitData) else list(item)


def with_commit(item, b: int, c: CommitData):
    """The item with its b-th commit replaced, of the item's own type."""
    if isinstance(item, CommitData):
        return c
    return [c if k == b else x for k, x in enumerate(item)]


def n_rows(item) -> int:
    """The signatures one call on this item has to verify."""
    return sum(len(c.present()) for c in commits_of(item))


def blocks_of(item) -> list:
    """An item's structure for a verdict rule: a block's height and how many
    of the item's rows, which stand in block order, are its."""
    return [{"height": c.height, "rows": len(c.present())} for c in commits_of(item)]


def _draw_commit(rng, item):
    """(b, commit): which commit of an item a probe alters. An item of one
    commit takes no draw, so its stream is what it was before items."""
    commits = commits_of(item)
    b = int(rng.integers(len(commits))) if len(commits) > 1 else 0
    return b, commits[b]


def make_ring(seed: int, config: dict, traffic: dict, vals: ValidatorData) -> list:
    """`ring_commits` distinct items: entry j is `commits_per_call` commits at
    consecutive heights from `first_height + j * commits_per_call` against
    the one validator set, each with its own block id, timestamps and draw
    of absent signers (with 1, the default, the commit itself and no list).
    `absent_share` of the validators (the same count in every commit, drawn
    afresh) do not sign; every `tampered_one_in`-th item carries one flipped
    bit."""
    n = len(vals.pubkeys)
    rng = np.random.default_rng([seed, 2])
    k = int(traffic["ring_commits"])
    per_call = int(traffic.get("commits_per_call", 1))
    n_absent = int(round(float(config.get("absent_share", 0.0)) * n))
    one_in = int(traffic.get("tampered_one_in", 0))
    ring = []
    for j in range(k):
        run = []
        for b in range(per_call):
            c = CommitData(
                height=int(traffic.get("first_height", 1)) + j * per_call + b,
                round=0,
                block_hash=rng.bytes(32),
                parts_total=int(rng.integers(1, 64)),
                parts_hash=rng.bytes(32),
                flags=[FLAG_COMMIT] * n,
                timestamps=(BASE_TIME_NS + rng.integers(1, 10**9, n)).tolist(),
                sigs=[b""] * n,
            )
            for i in rng.choice(n, n_absent, replace=False) if n_absent else ():
                c.flags[int(i)] = FLAG_ABSENT
            sb = sign_bytes_of(config["chain_id"], c)
            for i in c.present():
                c.sigs[i] = vals.privs[i].sign(sb.of(c.timestamps[i]))
            run.append(c)
        if one_in and j % one_in == one_in - 1:
            _, c = _draw_commit(rng, run)
            bad = int(rng.choice(c.present()))
            c.sigs[bad] = flip_bit(c.sigs[bad], "Rs"[j // one_in % 2])
            c.tampered = (bad,)
        ring.append(run[0] if per_call == 1 else run)
    return ring


def rows_of(config: dict, vals: ValidatorData, item):
    """(validator indices, pubkeys, sign bytes, signatures) of the rows a
    verifier has to check, by the reference's encoder: one list each, in
    block order (`blocks_of` says where a block's rows end)."""
    idx, pks, msgs, sigs = [], [], [], []
    for c in commits_of(item):
        sb = sign_bytes_of(config["chain_id"], c)
        here = c.present()
        idx += here
        pks += [vals.pubkeys[i] for i in here]
        msgs += [sb.of(c.timestamps[i]) for i in here]
        sigs += [c.sigs[i] for i in here]
    return idx, pks, msgs, sigs


def probes(seed: int, count: int, ring: list) -> list:
    """`count` tampered copies of ring items for the reject probe: probe j
    alters one signature in the j-th of `count` equal strata of the item's
    signing rows, all its blocks in one list, so a verifier that leaves out
    any half, the last third, or every block of a run but the first, meets
    one. Returns (ring index, position among the item's rows, kind)."""
    rng = np.random.default_rng([seed, 3])
    out = []
    clean = [j for j, item in enumerate(ring)
             if not any(c.tampered for c in commits_of(item))] or list(range(len(ring)))
    for j in range(count):
        r = clean[j % len(clean)]
        m = n_rows(ring[r])
        lo, hi = j * m // count, max((j + 1) * m // count, j * m // count + 1)
        out.append((r, int(rng.integers(lo, min(hi, m))), "sR"[j % 2]))
    return out


def _without(c: CommitData, gone: set) -> CommitData:
    return replace(c, flags=[FLAG_ABSENT if i in gone else f for i, f in enumerate(c.flags)],
                   sigs=[b"" if i in gone else s for i, s in enumerate(c.sigs)])


def entry_probes(seed: int, config: dict, traffic: dict, ring: list,
                 vals: ValidatorData) -> list:
    """Items that the entry itself has to refuse, called once each after the
    window: (label, item). Each alters ONE commit of a ring item, drawn from
    the seed, and reasons by power, not by head count.
    `short_power`: `short_power_absent_share` of the signatures left out, and
    where more than 2/3 of the power is then still signed, the largest
    signers left as well until it is not; all the others valid, so that the
    tally and not a signature refuses it; it rides the timed programs.
    `tampered`: one bit of one signature flipped, where the configuration
    says `reject_via_entry`: behind the combined check it walks the program's
    recovery ladder, which has programs of its own, down to the index that
    the verdict names. `invalid_power`, where the mix says
    `invalid_power_probe`, for a rule that tallies the valid signatures: one
    bit flipped in the signatures of the largest signers until the power
    that signed validly is 2/3 or under; nobody is absent who was not."""
    rng = np.random.default_rng([seed, 4])
    powers, total = vals.powers, vals.total_power
    out = []
    share = float(traffic.get("short_power_absent_share", 0.0))
    if share:
        item = ring[0]
        b, c = _draw_commit(rng, item)
        signed = c.present()
        gone = set(rng.choice(signed, int(round(share * len(c.flags))), replace=False).tolist())
        left = sorted((i for i in signed if i not in gone), key=lambda i: (-powers[i], i))
        have = sum(powers[i] for i in left)
        while have * 3 > total * 2:  # never with equal power: 60% is left
            have -= powers[left[0]]
            gone.add(left.pop(0))
        out.append(("short_power", with_commit(item, b, _without(c, gone))))
    if config.get("reject_via_entry"):
        item = ring[1 % len(ring)]
        b, c = _draw_commit(rng, item)
        bad = int(rng.choice(c.present()))
        sigs = list(c.sigs)
        sigs[bad] = flip_bit(sigs[bad], "sR"[seed % 2])
        out.append(("tampered", with_commit(item, b, replace(c, sigs=sigs, tampered=(bad,)))))
    if traffic.get("invalid_power_probe"):
        item = ring[2 % len(ring)]
        b, c = _draw_commit(rng, item)
        sigs, bad = list(c.sigs), []
        valid = sum(powers[i] for i in c.present())
        for i in sorted(c.present(), key=lambda i: (-powers[i], i)):
            if valid * 3 <= total * 2:
                break
            sigs[i] = flip_bit(sigs[i], "sR"[(seed + len(bad)) % 2])
            bad.append(i)
            valid -= powers[i]
        out.append(("invalid_power",
                    with_commit(item, b, replace(c, sigs=sigs, tampered=tuple(sorted(bad))))))
    return out
