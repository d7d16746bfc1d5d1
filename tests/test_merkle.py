"""Merkle: RFC6962 golden vectors + proof round-trips."""

import hashlib

import pytest

from tendermint_tpu.crypto import merkle


def test_empty_tree():
    assert merkle.hash_from_byte_slices([]) == hashlib.sha256(b"").digest()


def test_single_leaf():
    item = b"hello"
    assert merkle.hash_from_byte_slices([item]) == hashlib.sha256(b"\x00" + item).digest()


def test_two_leaves():
    a, b = b"a", b"b"
    la = hashlib.sha256(b"\x00" + a).digest()
    lb = hashlib.sha256(b"\x00" + b).digest()
    expect = hashlib.sha256(b"\x01" + la + lb).digest()
    assert merkle.hash_from_byte_slices([a, b]) == expect


def test_split_point():
    assert merkle.split_point(2) == 1
    assert merkle.split_point(3) == 2
    assert merkle.split_point(4) == 2
    assert merkle.split_point(5) == 4
    assert merkle.split_point(8) == 4
    assert merkle.split_point(9) == 8


def test_rfc6962_structure_five_leaves():
    items = [bytes([i]) for i in range(5)]
    left = merkle.hash_from_byte_slices(items[:4])
    right = merkle.hash_from_byte_slices(items[4:])
    expect = hashlib.sha256(b"\x01" + left + right).digest()
    assert merkle.hash_from_byte_slices(items) == expect


def test_proofs_verify():
    for n in [1, 2, 3, 5, 8, 13, 64]:
        items = [b"item-%d" % i for i in range(n)]
        root, proofs = merkle.proofs_from_byte_slices(items)
        assert root == merkle.hash_from_byte_slices(items)
        for i, proof in enumerate(proofs):
            assert proof.total == n and proof.index == i
            assert proof.verify(root, items[i])
            # wrong leaf / wrong root fail
            assert not proof.verify(root, b"bogus")
            assert not proof.verify(b"\x00" * 32, items[i])


def test_proof_wrong_index_fails():
    items = [b"a", b"b", b"c", b"d"]
    root, proofs = merkle.proofs_from_byte_slices(items)
    p = proofs[0]
    p.index = 1
    assert not p.verify(root, items[0])


# -- the root over leaf hashes (ISSUE 35): the one place a root is computed

def _items(n):
    return [b"leaf-%d" % i * (1 + i % 3) for i in range(n)]


def _recursive_root(items):
    """hash_from_byte_slices as it stood before the root over leaf hashes:
    the tree by RFC 6962's split point, written out."""
    if not items:
        return hashlib.sha256(b"").digest()
    if len(items) == 1:
        return hashlib.sha256(b"\x00" + items[0]).digest()
    k = merkle.split_point(len(items))
    return hashlib.sha256(b"\x01" + _recursive_root(items[:k]) + _recursive_root(items[k:])).digest()


# printed by the parent's hash_from_byte_slices (f4efa38) over _items(n)
PINNED = {
    7: "caca32ad814c836f4cffe1549b0304a23bcc7947c166d16da77478aa2b33f985",
    100: "64c593279d986e6c8932f0dc03fcd7d99e8919d604dae1a686d8c22f51630067",
    101: "0180f30577584e10c4a5aff5c095969126d7f0eef65bda9fc0a6d5e493e35ee9",
    175: "0cefbf2ec25ed0d3ef6a8b1e4ebcfbca54a4e7d8505788555c29906e2673b4c3",
}


@pytest.mark.parametrize("n", list(range(34)) + [100, 101, 175])
def test_the_root_over_leaf_hashes_is_the_root_over_the_items(n):
    items = _items(n)
    want = _recursive_root(items)
    assert merkle.hash_from_leaf_hashes([merkle.leaf_hash(i) for i in items]) == want
    assert merkle.hash_from_byte_slices(items) == want
    assert merkle.hash_from_byte_slices(tuple(items)) == want  # any sequence, as before
    if n in PINNED:
        assert want.hex() == PINNED[n]
    root, proofs = merkle.proofs_from_byte_slices(items)
    assert root == want and len(proofs) == n
    assert all(p.verify(want, item) for p, item in zip(proofs, items))
