"""Trusted light-block store.

reference: light/store/store.go (Store iface) + light/store/db/db.go
(DB-backed impl with ordered heights, size-bounded pruning).

Thread-safe: the light SERVICE (light/service.py) uses a LightStore as its
verified-header cache and hits it from many concurrent request tasks, the
coalescer's worker thread, and the pruner at once — `_heights` is guarded
by an RLock so a reader never sees a half-applied insert/remove (the
reference wraps its db in a mutex for the same reason,
light/store/db/db.go:25)."""

from __future__ import annotations

import bisect
import struct
import threading
from typing import List, Optional

from tendermint_tpu.libs.kvdb import KVDB
from tendermint_tpu.types.light import (
    LightBlock,
    light_block_from_bytes,
    light_block_to_bytes,
)

_LB_PREFIX = b"lb/"


def _key(height: int) -> bytes:
    return _LB_PREFIX + struct.pack(">Q", height)


class LightStore:
    """Stores verified light blocks keyed by big-endian height so prefix
    iteration yields ascending order (reference: light/store/db/db.go:33)."""

    def __init__(self, db: KVDB):
        self.db = db
        self._lock = threading.RLock()
        self._heights: List[int] = [
            struct.unpack(">Q", k[len(_LB_PREFIX):])[0]
            for k, _ in db.iterate_prefix(_LB_PREFIX)
        ]
        self._heights.sort()

    def save_light_block(self, lb: LightBlock) -> int:
        """reference: light/store/db/db.go:52 SaveLightBlock. The block's
        record (types/light.py light_block_to_bytes) is in the db when this
        returns; the record's length is returned (the `light.store` span's
        `bytes`)."""
        if lb.height <= 0:
            raise ValueError("height <= 0")
        record = light_block_to_bytes(lb)
        with self._lock:
            i = bisect.bisect_left(self._heights, lb.height)
            if i == len(self._heights) or self._heights[i] != lb.height:
                self._heights.insert(i, lb.height)
            self.db.set(_key(lb.height), record)
        return len(record)

    def light_block(self, height: int) -> Optional[LightBlock]:
        """reference: light/store/db/db.go:96 LightBlock."""
        raw = self.db.get(_key(height))
        return light_block_from_bytes(raw) if raw is not None else None

    def latest_light_block(self) -> Optional[LightBlock]:
        """reference: light/store/db/db.go:126 LightBlockBefore/latest."""
        with self._lock:
            h = self._heights[-1] if self._heights else None
        return self.light_block(h) if h is not None else None

    def first_light_block(self) -> Optional[LightBlock]:
        with self._lock:
            h = self._heights[0] if self._heights else None
        return self.light_block(h) if h is not None else None

    def light_block_before(self, height: int) -> Optional[LightBlock]:
        """Latest stored block strictly below height
        (reference: light/store/db/db.go:126)."""
        with self._lock:
            i = bisect.bisect_left(self._heights, height)
            if i == 0:
                return None
            h = self._heights[i - 1]
        return self.light_block(h)

    def delete_light_block(self, height: int) -> None:
        with self._lock:
            self.db.delete(_key(height))
            try:
                self._heights.remove(height)
            except ValueError:
                pass

    def prune(self, size: int) -> None:
        """Keep only the newest `size` blocks (reference: light/store/db/db.go:152)."""
        with self._lock:
            while len(self._heights) > size:
                self.delete_light_block(self._heights[0])

    def size(self) -> int:
        with self._lock:
            return len(self._heights)

    def heights(self) -> List[int]:
        with self._lock:
            return list(self._heights)
