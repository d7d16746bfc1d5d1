"""The inputs, from --seed alone: a validator set and a ring of signed
commits, as plain bytes and numbers. Signing is the validators' work and is
done here with OpenSSL over the reference's own sign bytes, so a program
that encodes a vote differently rejects what this signs. Every seed makes
the same sizes; only keys, block ids, timestamps and signatures differ."""

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
    n = rows or int(config["validators"])
    rng = np.random.default_rng([seed, 1])
    key_seeds = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    privs = [Ed25519PrivateKey.from_private_bytes(s.tobytes()) for s in key_seeds]
    pubs = [p.public_key().public_bytes_raw() for p in privs]
    order = sorted(range(n), key=lambda i: address(pubs[i]))  # equal power
    power = int(config["voting_power"])
    return ValidatorData([pubs[i] for i in order], [power] * n,
                         [privs[i] for i in order])


def sign_bytes_of(chain_id: str, c: CommitData) -> SignBytes:
    return SignBytes(chain_id, c.height, c.round, c.block_hash, c.parts_total,
                     c.parts_hash)


def flip_bit(sig: bytes, kind: str) -> bytes:
    """One bit of R (byte 7) or of s (byte 33, so that s stays canonical and
    the row reaches the device's sum instead of the host's range check)."""
    at = 7 if kind == "R" else 33
    return sig[:at] + bytes([sig[at] ^ 0x20]) + sig[at + 1:]


def make_ring(seed: int, config: dict, traffic: dict, vals: ValidatorData) -> list:
    """`ring_commits` distinct commits at consecutive heights. `absent_share`
    of the validators (the same count in every commit, drawn afresh) do not
    sign; every `tampered_one_in`-th commit carries one flipped bit."""
    n = len(vals.pubkeys)
    rng = np.random.default_rng([seed, 2])
    k = int(traffic["ring_commits"])
    n_absent = int(round(float(config.get("absent_share", 0.0)) * n))
    one_in = int(traffic.get("tampered_one_in", 0))
    ring = []
    for j in range(k):
        c = CommitData(
            height=int(traffic.get("first_height", 1)) + j,
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
        if one_in and j % one_in == one_in - 1:
            bad = int(rng.choice(c.present()))
            c.sigs[bad] = flip_bit(c.sigs[bad], "Rs"[j // one_in % 2])
            c.tampered = (bad,)
        ring.append(c)
    return ring


def rows_of(config: dict, vals: ValidatorData, c: CommitData):
    """(validator indices, pubkeys, sign bytes, signatures) of the rows a
    verifier has to check, by the reference's encoder."""
    sb = sign_bytes_of(config["chain_id"], c)
    idx = c.present()
    return (idx, [vals.pubkeys[i] for i in idx],
            [sb.of(c.timestamps[i]) for i in idx], [c.sigs[i] for i in idx])


def probes(seed: int, count: int, ring: list) -> list:
    """`count` tampered copies of ring commits for the reject probe: probe j
    alters one signature in the j-th of `count` equal strata of the signing
    rows, so a verifier that leaves out any half, or the last third, meets
    one. Returns (ring index, position among the present rows, kind)."""
    rng = np.random.default_rng([seed, 3])
    out = []
    clean = [j for j, c in enumerate(ring) if not c.tampered] or list(range(len(ring)))
    for j in range(count):
        r = clean[j % len(clean)]
        m = len(ring[r].present())
        lo, hi = j * m // count, max((j + 1) * m // count, j * m // count + 1)
        out.append((r, int(rng.integers(lo, min(hi, m))), "sR"[j % 2]))
    return out


def entry_probes(seed: int, config: dict, traffic: dict, ring: list) -> list:
    """Commits that the entry itself has to refuse, called once each after
    the window: (label, commit). `short_power`: a ring commit with
    `short_power_absent_share` of its signatures left out, all the others
    valid, so that the tally and not a signature refuses it; it rides the
    timed programs. `tampered`: a ring commit with one bit of one signature
    flipped, where the configuration says `reject_via_entry`: behind the
    combined check it walks the program's recovery ladder, which has
    programs of its own, down to the index that the verdict names."""
    rng = np.random.default_rng([seed, 4])
    out = []
    share = float(traffic.get("short_power_absent_share", 0.0))
    if share:
        c = ring[0]
        signed = c.present()
        gone = set(rng.choice(signed, int(round(share * len(c.flags))), replace=False).tolist())
        out.append(("short_power", replace(
            c, flags=[FLAG_ABSENT if i in gone else f for i, f in enumerate(c.flags)],
            sigs=[b"" if i in gone else s for i, s in enumerate(c.sigs)])))
    if config.get("reject_via_entry"):
        c = ring[1 % len(ring)]
        bad = int(rng.choice(c.present()))
        sigs = list(c.sigs)
        sigs[bad] = flip_bit(sigs[bad], "sR"[seed % 2])
        out.append(("tampered", replace(c, sigs=sigs, tampered=(bad,))))
    return out
