"""The inputs, from --seed alone: a validator set and a ring of signed
commits, as plain bytes and numbers. Signing is the validators' work and is
done here with OpenSSL over the reference's own sign bytes, so a program
that encodes a vote differently rejects what this signs. Every seed makes
the same sizes; only keys, block ids, timestamps and signatures differ.

An item is what one call verifies: one commit (a CommitData), or, where the
mix states `commits_per_call` over 1, a run of that many commits at
consecutive heights (a list of CommitData). `commits_of`, `n_rows`,
`blocks_of` and `rows_of` read either.

Where the configuration states `headers`, the ring is a chain: every commit
carries a header of its own (`header`, the fourteen fields as plain values),
is signed over that header's real hash by the validator set of its own height
(`vals`) and knows the signed header before it (`prev`; the first commit's is
the trusted root). Height h + 1's set is height h's with its
`validator_changes_per_height` oldest keys replaced (upstream's ChangeKeys).
A configuration without `headers` takes no new draw: its bytes are what they
were."""

from __future__ import annotations

from dataclasses import dataclass, field, replace

import numpy as np
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

import reference
from reference import FLAG_ABSENT, FLAG_COMMIT, SignBytes, address

BASE_TIME_NS = 1_700_000_000_000_000_000


@dataclass
class ValidatorData:
    pubkeys: list  # validator order: power descending, then address
    powers: list
    privs: list = field(repr=False, default_factory=list)
    born: list = field(repr=False, default_factory=list)  # per seat: the key's number in the order keys were made

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
    # of a chain (a configuration that states `headers`) only:
    header: dict | None = None  # reference.HEADER_FIELDS as plain values; block_hash is its hash
    vals: ValidatorData | None = field(repr=False, default=None)  # the set of this height, which signed
    prev: CommitData | None = field(repr=False, default=None)  # the signed header one height below

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
    return _ordered(pubs, powers, privs, list(range(n)))


def _ordered(pubs, powers, privs, born) -> ValidatorData:
    order = sorted(range(len(pubs)), key=lambda i: (-powers[i], address(pubs[i])))
    return ValidatorData(*([xs[i] for i in order] for xs in (pubs, powers, privs, born)))


def _fresh_key(rng):
    priv = Ed25519PrivateKey.from_private_bytes(rng.integers(0, 256, 32, dtype=np.uint8).tobytes())
    return priv, priv.public_key().public_bytes_raw()


def _changed(vals: ValidatorData, k: int, rng, fresh_powers=()) -> ValidatorData:
    """The set one height on: `vals` with its k oldest keys dropped and k
    fresh keys from `rng` in their place (upstream's ChangeKeys), ordered as
    a set is. A fresh key takes the next of `fresh_powers` (by its number,
    cycled), or where none is stated the power of the key it replaces."""
    if not k:
        return vals
    by_age = sorted(range(len(vals.born)), key=vals.born.__getitem__)
    gone, stay = by_age[:k], sorted(by_age[k:])
    cols = [[xs[i] for i in stay] for xs in (vals.pubkeys, vals.powers, vals.privs, vals.born)]
    number = max(vals.born) + 1
    for j, i in enumerate(gone):
        priv, pub = _fresh_key(rng)
        power = fresh_powers[(number + j) % len(fresh_powers)] if fresh_powers else vals.powers[i]
        for col, x in zip(cols, (pub, int(power), priv, number + j)):
            col.append(x)
    return _ordered(*cols)


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
    of the item's rows, which stand in block order, are its. A chain's block
    also gives the `powers` and `total_power` of its own set and `link_ok`,
    the reference's finding whether its header is the one the header before
    it committed to."""
    out = []
    for c in commits_of(item):
        block = {"height": c.height, "rows": len(c.present())}
        if c.header is not None:
            block.update(powers=c.vals.powers, total_power=c.vals.total_power,
                         link_ok=reference.link_ok(c.prev.header, c.header, c.block_hash, c.height,
                                                   c.vals.pubkeys, c.vals.powers))
        out.append(block)
    return out


def _draw_commit(rng, item):
    """(b, commit): which commit of an item a probe alters. An item of one
    commit takes no draw, so its stream is what it was before items."""
    commits = commits_of(item)
    b = int(rng.integers(len(commits))) if len(commits) > 1 else 0
    return b, commits[b]


def _sign(chain_id: str, c: CommitData, vals: ValidatorData) -> CommitData:
    sb = sign_bytes_of(chain_id, c)
    for i in c.present():
        c.sigs[i] = vals.privs[i].sign(sb.of(c.timestamps[i]))
    return c


def _signed_commit(rng, config: dict, vals: ValidatorData, height: int,
                   header: dict | None = None, prev: CommitData | None = None) -> CommitData:
    """One commit at `height`, signed by `vals`: over the hash of `header`
    where it has one, else over a drawn block id. Part-set header, timestamps
    and absentees are drawn alike in both."""
    n = len(vals.pubkeys)
    n_absent = int(round(float(config.get("absent_share", 0.0)) * n))
    c = CommitData(
        height=height,
        round=0,
        block_hash=reference.header_hash(header) if header else rng.bytes(32),
        parts_total=int(rng.integers(1, 64)),
        parts_hash=rng.bytes(32),
        flags=[FLAG_COMMIT] * n,
        timestamps=(BASE_TIME_NS + rng.integers(1, 10**9, n)).tolist(),
        sigs=[b""] * n,
        header=header, vals=vals if header else None, prev=prev,
    )
    for i in rng.choice(n, n_absent, replace=False) if n_absent else ():
        c.flags[int(i)] = FLAG_ABSENT
    return _sign(config["chain_id"], c, vals)


class _Chain:
    """The signed headers of a chain configuration, one height after another
    from the trusted root (the height before the ring's first, signed by
    `vals`). Fresh keys and the drawn header fields come from a stream of
    their own; what a commit draws comes from the ring's, as without
    headers."""

    def __init__(self, seed: int, config: dict, vals: ValidatorData, root_height: int):
        if root_height < 1:
            raise ValueError("a chain's trusted root stands one height under first_height, "
                             "so first_height is 2 or more")
        self.rng = np.random.default_rng([seed, 5])
        self.config, self.root_height, self.height = config, root_height, root_height
        self.vals, self.prev = vals, None
        self.next_vals = self._next(vals)

    def _next(self, vals: ValidatorData) -> ValidatorData:
        return _changed(vals, int(self.config.get("validator_changes_per_height", 0)), self.rng,
                        self.config.get("fresh_voting_powers", ()))

    def commit(self, rng) -> CommitData:
        """The next height's signed header, linked to the one before."""
        r, prev, vals = self.rng, self.prev, self.vals
        header = {
            "version_block": 11, "version_app": 0, "chain_id": self.config["chain_id"],
            "height": self.height,
            "time_ns": BASE_TIME_NS + (self.height - self.root_height) * 10**9 + int(r.integers(10**9)),
            "validators_hash": reference.validators_hash(vals.pubkeys, vals.powers),
            "next_validators_hash": reference.validators_hash(self.next_vals.pubkeys,
                                                              self.next_vals.powers),
            "proposer_address": address(vals.pubkeys[self.height % len(vals.pubkeys)]),
        }
        if prev is not None:
            header.update(last_block_hash=prev.block_hash, last_parts_total=prev.parts_total,
                          last_parts_hash=prev.parts_hash)
        elif self.height > 1:  # the root's own predecessor is not part of the data
            header.update(last_block_hash=r.bytes(32), last_parts_total=int(r.integers(1, 64)),
                          last_parts_hash=r.bytes(32))
        else:  # height 1 has no block before it
            header.update(last_block_hash=b"", last_parts_total=0, last_parts_hash=b"")
        for k in ("last_commit_hash", "data_hash", "consensus_hash", "app_hash",
                  "last_results_hash", "evidence_hash"):
            header[k] = r.bytes(32)
        c = _signed_commit(rng, self.config, vals, self.height, header, prev)
        self.prev, self.height = c, self.height + 1
        self.vals, self.next_vals = self.next_vals, self._next(self.next_vals)
        return c


def make_ring(seed: int, config: dict, traffic: dict, vals: ValidatorData) -> list:
    """`ring_commits` distinct items: entry j is `commits_per_call` commits at
    consecutive heights from `first_height + j * commits_per_call`, each with
    its own block id, timestamps and draw of absent signers (with 1, the
    default, the commit itself and no list). `absent_share` of the validators
    (the same count in every commit, drawn afresh) do not sign; every
    `tampered_one_in`-th item carries one flipped bit, in a row drawn from the
    seed or, where the mix states `tampered_seats`, in the row its seat gives
    (`seated`; the k-th forged item takes the k-th share, cycled). All are
    signed by the one set `vals`, unless the configuration states `headers`: then the ring
    is one chain of signed headers from the trusted root at `first_height -
    1`, which `vals` signed and the first item's first commit has as `prev`;
    every height has a set of its own, and item j continues where j - 1
    ended."""
    rng = np.random.default_rng([seed, 2])
    k = int(traffic["ring_commits"])
    per_call = int(traffic.get("commits_per_call", 1))
    first = int(traffic.get("first_height", 1))
    one_in = int(traffic.get("tampered_one_in", 0))
    seats = traffic.get("tampered_seats")
    chain = None
    if config.get("headers"):
        chain = _Chain(seed, config, vals, first - 1)
        chain.commit(rng)  # the trusted root
    ring = []
    for j in range(k):
        run = []
        for b in range(per_call):
            run.append(chain.commit(rng) if chain
                       else _signed_commit(rng, config, vals, first + j * per_call + b))
        if one_in and j % one_in == one_in - 1:
            if seats:
                c, bad = seated(run, float(seats[j // one_in % len(seats)]))
            else:
                _, c = _draw_commit(rng, run)
                bad = int(rng.choice(c.present()))
            c.sigs[bad] = flip_bit(c.sigs[bad], "Rs"[j // one_in % 2])
            c.tampered = (bad,)
        ring.append(run[0] if per_call == 1 else run)
    return ring


def seated(item, share: float) -> tuple:
    """(commit, seat) of the row at position floor(share * rows) of an item's
    signing rows, all its commits in block order as one list (as `probes`
    counts them): where a forger who sits at that share of the rows signs.
    No draw: the same row under every seed."""
    at = int(share * n_rows(item))
    for c in commits_of(item):
        here = c.present()
        if at < len(here):
            return c, here[at]
        at -= len(here)
    raise ValueError(f"tampered seat {share!r} lies outside [0, 1)")


def rows_of(config: dict, vals: ValidatorData, item):
    """(validator indices, pubkeys, sign bytes, signatures) of the rows a
    verifier has to check, by the reference's encoder: one list each, in
    block order (`blocks_of` says where a block's rows end). An index is a
    seat in the set that signed the commit: its own where it has one, else
    `vals`."""
    idx, pks, msgs, sigs = [], [], [], []
    for c in commits_of(item):
        sb = sign_bytes_of(config["chain_id"], c)
        here = c.present()
        idx += here
        pks += [(c.vals or vals).pubkeys[i] for i in here]
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
    that signed validly is 2/3 or under; nobody is absent who was not.
    A chain's commit is weighed by its own set. `broken_link`, for every
    chain: the header of one height made anew for a set in which keys of
    the probe's own replace the oldest ones, so that its `validators_hash` is
    not what the header before it committed to, and the headers above it
    linked to it; every signature is valid under the set its header carries and
    every other link holds, so that only the hash chain refuses the run, at
    that height."""
    rng = np.random.default_rng([seed, 4])
    out = []
    share = float(traffic.get("short_power_absent_share", 0.0))
    if share:
        item = ring[0]
        b, c = _draw_commit(rng, item)
        own = c.vals or vals
        powers, total = own.powers, own.total_power
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
        own = c.vals or vals
        powers, total = own.powers, own.total_power
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
    if config.get("headers"):
        item = ring[3 % len(ring)]
        b, c = _draw_commit(rng, item)
        others = _changed(c.vals, max(1, int(config.get("validator_changes_per_height", 0))), rng)
        forged = [_reheaded(config, c, c.prev, others, validators_hash=reference.validators_hash(
            others.pubkeys, others.powers))]
        for above in commits_of(item)[b + 1:]:
            forged.append(_reheaded(config, above, forged[-1], above.vals,
                                    last_block_hash=forged[-1].block_hash))
        out.append(("broken_link", forged[0] if isinstance(item, CommitData)
                    else commits_of(item)[:b] + forged))
    return out


def _reheaded(config: dict, c: CommitData, prev: CommitData, vals: ValidatorData,
              **fields) -> CommitData:
    """A chain's commit with `fields` of its header changed: the block id is
    the new header's hash and every signer of `vals` signs it again; seats
    present, timestamps and part-set header stay."""
    header = dict(c.header, **fields)
    return _sign(config["chain_id"], replace(
        c, header=header, block_hash=reference.header_hash(header), vals=vals, prev=prev,
        sigs=[b""] * len(c.sigs)), vals)
