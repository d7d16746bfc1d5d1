"""RFC-6962-style Merkle trees over SHA-256.

Mirrors the reference's crypto/merkle (hash.go, tree.go, proof.go): leaf nodes
are H(0x00 || leaf), inner nodes H(0x01 || left || right), empty tree hashes to
H(""), and the split point for n leaves is the largest power of two strictly
less than n. Proofs carry (total, index, leaf_hash, aunts).
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

LEAF_PREFIX = b"\x00"
INNER_PREFIX = b"\x01"


def _sha256(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


def empty_hash() -> bytes:
    return _sha256(b"")


def leaf_hash(leaf: bytes) -> bytes:
    return _sha256(LEAF_PREFIX + leaf)


def inner_hash(left: bytes, right: bytes) -> bytes:
    return _sha256(INNER_PREFIX + left + right)


def split_point(n: int) -> int:
    """Largest power of two strictly less than n (n >= 2)."""
    if n < 2:
        raise ValueError("split_point requires n >= 2")
    return 1 << (n - 1).bit_length() - 1


def hash_from_leaf_hashes(hashes: Sequence[bytes]) -> bytes:
    """The root over leaf hashes: the one place a root is computed.

    Level by level, neighbours paired and an odd last node carried up as it
    is. That is RFC 6962's tree: a node of level j covers the leaves
    [i * 2^j, (i + 1) * 2^j) cut off at n, so every node's left child is a
    full power of two, the largest below the leaves the node covers
    (split_point)."""
    if not hashes:
        return empty_hash()
    level = hashes
    while len(level) > 1:
        paired = [inner_hash(level[i], level[i + 1]) for i in range(0, len(level) - 1, 2)]
        if len(level) & 1:
            paired.append(level[-1])
        level = paired
    return level[0]


def hash_from_byte_slices(items: Sequence[bytes]) -> bytes:
    return hash_from_leaf_hashes([leaf_hash(item) for item in items])


@dataclass
class Proof:
    total: int
    index: int
    leaf_hash: bytes
    aunts: List[bytes] = field(default_factory=list)

    def compute_root_hash(self) -> Optional[bytes]:
        return _compute_hash_from_aunts(self.index, self.total, self.leaf_hash, self.aunts)

    def verify(self, root_hash: bytes, leaf: bytes) -> bool:
        if self.total < 0 or self.index < 0:
            return False
        if leaf_hash(leaf) != self.leaf_hash:
            return False
        return self.compute_root_hash() == root_hash


def _compute_hash_from_aunts(
    index: int, total: int, lh: bytes, aunts: List[bytes]
) -> Optional[bytes]:
    if index >= total or index < 0 or total <= 0:
        return None
    if total == 1:
        if aunts:
            return None
        return lh
    if not aunts:
        return None
    k = split_point(total)
    if index < k:
        left = _compute_hash_from_aunts(index, k, lh, aunts[:-1])
        if left is None:
            return None
        return inner_hash(left, aunts[-1])
    right = _compute_hash_from_aunts(index - k, total - k, lh, aunts[:-1])
    if right is None:
        return None
    return inner_hash(aunts[-1], right)


def proofs_from_byte_slices(items: Sequence[bytes]) -> tuple[bytes, List[Proof]]:
    """Root hash + a proof per item."""
    trails, root = _trails_from_byte_slices(list(items))
    root_hash = root.hash
    proofs = []
    for i, trail in enumerate(trails):
        proofs.append(
            Proof(total=len(items), index=i, leaf_hash=trail.hash, aunts=trail.flatten_aunts())
        )
    return root_hash, proofs


class _Node:
    __slots__ = ("hash", "parent", "left", "right")

    def __init__(self, h: bytes):
        self.hash = h
        self.parent: Optional[_Node] = None
        self.left: Optional[_Node] = None  # left sibling (aunt chain)
        self.right: Optional[_Node] = None

    def flatten_aunts(self) -> List[bytes]:
        aunts: List[bytes] = []
        node: Optional[_Node] = self
        while node is not None:
            if node.left is not None:
                aunts.append(node.left.hash)
            elif node.right is not None:
                aunts.append(node.right.hash)
            node = node.parent
        return aunts


def _trails_from_byte_slices(items: List[bytes]) -> tuple[List[_Node], _Node]:
    n = len(items)
    if n == 0:
        return [], _Node(empty_hash())
    if n == 1:
        node = _Node(leaf_hash(items[0]))
        return [node], node
    k = split_point(n)
    lefts, left_root = _trails_from_byte_slices(items[:k])
    rights, right_root = _trails_from_byte_slices(items[k:])
    root = _Node(inner_hash(left_root.hash, right_root.hash))
    left_root.parent = root
    left_root.right = right_root
    right_root.parent = root
    right_root.left = left_root
    return lefts + rights, root
