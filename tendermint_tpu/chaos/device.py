"""Device-level fault injection for the batch-verify pipeline.

Installs into crypto/batch.py's `_device_fault(site)` hook, which every
device entry point calls (RLC submit, RLC result sync, the per-signature
kernel, the circuit breaker's health probe). Armed faults fire on the next
device calls regardless of site — exactly what a sick accelerator looks like
from the host: every dispatch fails or stalls, whichever kernel it carries.

Shard-targeted faults (ISSUE 19) additionally install into
parallel/sharded.py's shard-fault hook, which every SHARDED submit site
calls with the participating device list — so a chaos schedule can kill
exactly one lane slice of one mesh dispatch:

    shard_error {shard}          the next sharded dispatch raises a
                                 ShardFaultError attributed to that shard
    shard_hang  {shard, seconds} the next sharded dispatch stalls first
                                 (feeds the health model's stall scoring)
    device_lost {device}         EVERY dispatch that includes that device
                                 raises, and its health probes fail, until
                                 heal()/revive_device() — a preempted chip

The injector also registers a probe intercept with the mesh health manager
(parallel/health.py), so a "lost" device keeps failing its rejoin probes —
the full death/probation/rejoin cycle is drivable from one schedule.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple


class DeviceFaultError(RuntimeError):
    """The injected stand-in for a device failure."""


class DeviceFaultInjector:
    """Count-armed fault source. Thread-safe: the consensus event loop, the
    prewarm thread, and the breaker's probe thread can all hit device entry
    points concurrently.

    arm_errors(k): the next k device calls raise DeviceFaultError.
    arm_hang(s):   the next device call sleeps s seconds first (a stall the
                   caller experiences as a slow flush — the breaker's
                   flush-deadline overrun path).
    persistent:    raise on EVERY call until heal() (a dead device).
    """

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._lock = threading.Lock()
        self._errors_left = 0
        self._hangs: List[float] = []
        self._persistent = False
        self._clock = clock
        self.calls = 0  # total device-entry calls observed
        self.fired: List[Tuple[str, str]] = []  # (site, "error"|"hang")
        # Shard-targeted state (sharded.set_shard_fault_hook); a shard index
        # is a LANE SLICE of the mesh dispatch, a lost device is a STRING key
        # matched against the participating device list.
        self._shard_errors: List[int] = []  # one-shot, by shard index
        self._shard_hangs: List[Tuple[int, float]] = []  # (shard, seconds)
        self._lost_indices: List[int] = []  # pending: resolve at next dispatch
        self._lost_devices: set = set()  # resolved device strings
        self._lost_by_index: Dict[int, str] = {}  # index -> resolved string
        self.shard_calls = 0  # total sharded-submit-site calls observed

    # -- arming -------------------------------------------------------------

    def arm_errors(self, count: int) -> None:
        with self._lock:
            self._errors_left += max(0, int(count))

    def arm_hang(self, seconds: float) -> None:
        with self._lock:
            self._hangs.append(float(seconds))

    def set_persistent(self, on: bool = True) -> None:
        with self._lock:
            self._persistent = bool(on)

    def arm_shard_error(self, shard: int) -> None:
        """The next sharded dispatch raises, attributed to `shard` (a lane
        slice index into the participating device list)."""
        with self._lock:
            self._shard_errors.append(int(shard))

    def arm_shard_hang(self, shard: int, seconds: float) -> None:
        """The next sharded dispatch stalls `seconds` first — the health
        model sees a slow flush and scores a stall strike on `shard`."""
        with self._lock:
            self._shard_hangs.append((int(shard), float(seconds)))

    def arm_device_lost(self, device) -> None:
        """EVERY sharded dispatch including `device` raises, and its health
        probes fail, until heal()/revive_device(). `device` may be a device
        string (matched exactly) or an int index (resolved against the
        participating device list at the next dispatch)."""
        with self._lock:
            if isinstance(device, int):
                self._lost_indices.append(device)
            else:
                self._lost_devices.add(str(device))

    def revive_device(self, device=None) -> None:
        """Un-lose a device (or all, if None): its probes pass again, so the
        health model's rejoin cycle can run. Accepts the same index/string
        forms as arm_device_lost (an index revives whatever string it
        resolved to at dispatch time)."""
        with self._lock:
            if device is None:
                self._lost_indices.clear()
                self._lost_devices.clear()
                self._lost_by_index.clear()
            elif isinstance(device, int):
                if device in self._lost_indices:
                    self._lost_indices.remove(device)
                key = self._lost_by_index.pop(device, None)
                if key is not None:
                    self._lost_devices.discard(key)
            else:
                self._lost_devices.discard(str(device))
                self._lost_by_index = {
                    i: k for i, k in self._lost_by_index.items() if k != str(device)
                }

    def lost_devices(self) -> List[str]:
        with self._lock:
            return sorted(self._lost_devices)

    def heal(self) -> None:
        with self._lock:
            self._errors_left = 0
            self._hangs.clear()
            self._persistent = False
            self._shard_errors.clear()
            self._shard_hangs.clear()
            self._lost_indices.clear()
            self._lost_devices.clear()
            self._lost_by_index.clear()

    # -- the hook (crypto/batch.set_device_fault_hook) ----------------------

    def __call__(self, site: str) -> None:
        with self._lock:
            self.calls += 1
            hang: Optional[float] = self._hangs.pop(0) if self._hangs else None
            fire_error = self._persistent or self._errors_left > 0
            if not self._persistent and self._errors_left > 0:
                self._errors_left -= 1
            if hang is not None:
                self.fired.append((site, "hang"))
            if fire_error:
                self.fired.append((site, "error"))
        if hang is not None:
            time.sleep(hang)  # the device call "stalls"
        if fire_error:
            raise DeviceFaultError(f"injected device fault at {site}")

    # -- the shard hook (parallel/sharded.set_shard_fault_hook) -------------

    def shard_fault(self, site: str, devices) -> None:
        """Called by every SHARDED submit site with the participating device
        list. Raises sharded.ShardFaultError carrying the shard index and
        device string, so the health model attributes the fault to exactly
        one chip instead of probing the whole mesh."""
        from tendermint_tpu.parallel.sharded import ShardFaultError

        keys = [str(d) for d in devices]
        with self._lock:
            self.shard_calls += 1
            # Resolve index-armed losses against this dispatch's device list
            # (first sharded dispatch after arming names the victim).
            while self._lost_indices:
                idx = self._lost_indices.pop(0)
                if 0 <= idx < len(keys):
                    self._lost_devices.add(keys[idx])
                    self._lost_by_index[idx] = keys[idx]
            lost_here = [i for i, k in enumerate(keys) if k in self._lost_devices]
            shard_err: Optional[int] = (
                self._shard_errors.pop(0) if self._shard_errors else None
            )
            shard_hang: Optional[Tuple[int, float]] = (
                self._shard_hangs.pop(0) if self._shard_hangs else None
            )
            if lost_here:
                self.fired.append((site, f"device_lost:{keys[lost_here[0]]}"))
            if shard_hang is not None:
                self.fired.append((site, f"shard_hang:{shard_hang[0]}"))
            if shard_err is not None:
                self.fired.append((site, f"shard_error:{shard_err}"))
        if shard_hang is not None:
            time.sleep(shard_hang[1])  # one shard "straggles"
        if lost_here:
            i = lost_here[0]
            raise ShardFaultError(site, i, keys[i])
        if shard_err is not None:
            i = max(0, min(int(shard_err), len(keys) - 1)) if keys else 0
            dev = keys[i] if keys else f"shard{shard_err}"
            raise ShardFaultError(site, i, dev)

    def probe_intercept(self, key: str) -> None:
        """Installed into MESH_HEALTH: a lost device keeps failing its rejoin
        probes until revive_device()/heal() — probation is chaos-drivable."""
        with self._lock:
            lost = key in self._lost_devices
        if lost:
            raise DeviceFaultError(f"injected probe failure on lost device {key}")

    # -- lifecycle ----------------------------------------------------------

    def install(self) -> "DeviceFaultInjector":
        from tendermint_tpu.crypto import batch
        from tendermint_tpu.parallel import health, sharded

        batch.set_device_fault_hook(self)
        sharded.set_shard_fault_hook(self.shard_fault)
        health.MESH_HEALTH.set_probe_intercept(self.probe_intercept)
        return self

    def uninstall(self) -> None:
        from tendermint_tpu.crypto import batch
        from tendermint_tpu.parallel import health, sharded

        batch.set_device_fault_hook(None)
        sharded.set_shard_fault_hook(None)
        health.MESH_HEALTH.set_probe_intercept(None)
