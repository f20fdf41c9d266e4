"""Tiered KV block manager: G1 device / G2 host / G3 disk + offload.

Role of the reference's `KvBlockManager` (`block_manager.rs:90`) +
`offload.rs` OffloadManager: cache levels G1 (device HBM — slots in the
engine's paged jax array), G2 (pinned host DRAM — one numpy array), G3
(local disk — numpy memmap), with

- automatic *offload* on G1 eviction: the evicted block's KV rides down to
  G2 (and G3 when G2 evicts) so the prefix stays warm;
- *onboard* on match: a prompt prefix found in G2/G3 is copied into fresh
  G1 slots before prefill, converting disk/DRAM residency into skipped
  prefill FLOPs.

Device↔host copies are slot-indexed gathers/scatters through jit
functions; host↔disk are numpy slice copies.

Offload is ASYNC (synchronous, every G1 eviction would block the engine
thread on a device→host copy): `_on_device_evict` runs only the device-side extract (an
async dispatch producing an independent staging array — device execution
order guarantees it reads the cache before the engine's next step), and
the host copy resolves on a background thread.  G2 readers
(onboard/export/spill-to-disk) consult the pending map and wait for the
specific block's future only when they actually need its bytes.
"""

from __future__ import annotations

import logging
import os
import tempfile
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from dynamo_tpu.llm.block_manager.pool import BlockPool
from dynamo_tpu.runtime import flight_recorder
from dynamo_tpu.runtime.contracts import (
    engine_thread_only,
    hot_path,
    never_engine_thread,
)

logger = logging.getLogger(__name__)


@dataclass(frozen=True)
class TieredConfig:
    """Capacities per tier, in blocks (reference `block_manager/config.rs`)."""

    device_blocks: int           # G1, includes the reserved null block 0
    host_blocks: int = 0         # G2; 0 disables the tier
    disk_blocks: int = 0         # G3; 0 disables
    block_size: int = 64
    disk_path: Optional[str] = None   # default: temp file


class KvBlockManager:
    """Owns the three pools + the transfer plumbing.

    The device tier's actual KV bytes live in the engine's cache pytree;
    the engine hands us `extract_fn(slot) -> np.ndarray` and
    `inject_fn(slot, data)` at construction so the manager stays agnostic
    of cache layout and sharding.
    """

    def __init__(
        self,
        config: TieredConfig,
        block_nbytes: int = 0,
        extract_fn=None,
        inject_fn=None,
        remote_fetch_fn=None,
    ) -> None:
        """`remote_fetch_fn(block_hash) -> Optional[np.ndarray]`: the G4
        tier (reference cache level G4 "remote",
        `block_manager.rs:68-82`) — consulted when a prefix block misses
        every local tier.  Must be synchronous and bounded (the caller is
        the engine thread); the disagg decode path wires this to a
        peer-worker kv_blocks pull."""
        self.config = config
        self.extract_fn = extract_fn
        self.inject_fn = inject_fn
        self.remote_fetch_fn = remote_fetch_fn

        self.device = BlockPool(config.device_blocks, name="G1-device",
                                on_evict=self._on_device_evict,
                                reserve_null=True)
        self.host: Optional[BlockPool] = None
        self.disk: Optional[BlockPool] = None
        self._host_data: Optional[np.ndarray] = None
        self._disk_data: Optional[np.ndarray] = None
        self._block_shape: Optional[tuple] = None

        if config.host_blocks:
            self.host = BlockPool(config.host_blocks, name="G2-host",
                                  on_evict=self._on_host_evict)
        if config.disk_blocks:
            self.disk = BlockPool(config.disk_blocks, name="G3-disk")
        self.offloaded_blocks = 0
        self.onboarded_blocks = 0
        self.remote_fetched_blocks = 0
        # Async offload: hash → Future resolving when the block's bytes
        # have landed in _host_data.
        from concurrent.futures import ThreadPoolExecutor

        self._offload_pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="kv-offload")
        self._pending_host: Dict[int, object] = {}

    # -- lazy tier storage (shape known at first offload) ------------------

    def _ensure_storage(self, sample: np.ndarray) -> None:
        if self._block_shape is not None:
            return
        self._block_shape = sample.shape
        if self.host is not None:
            self._host_data = np.empty(
                (self.config.host_blocks, *sample.shape), sample.dtype)
        if self.disk is not None:
            path = self.config.disk_path or os.path.join(
                tempfile.gettempdir(), f"dynamo_tpu_kv_{os.getpid()}.bin")
            self._disk_data = np.lib.format.open_memmap(
                path, mode="w+", dtype=sample.dtype,
                shape=(self.config.disk_blocks, *sample.shape))

    # -- offload path (down-tier) ------------------------------------------

    @hot_path
    def _on_device_evict(self, block_hash: int, slot: int) -> None:
        """G1 eviction → stash the block in G2 (if enabled).

        Synchronous part: ONLY the device-side extract dispatch (the
        extract must be enqueued before the evicted slot's next write;
        in-order device execution then guarantees it reads the old
        bytes).  The device→host transfer resolves off-thread."""
        if self.host is None or self.extract_fn is None:
            return
        if self.host.registry.lookup(block_hash) is not None:
            return  # already resident down-tier
        staged = self.extract_fn(slot)   # device array (async dispatch)
        if self._block_shape is None:
            # First offload: the storage allocation needs the concrete
            # shape — pay the one-time sync.
            # dynamo-lint: disable=DL001 one-time storage-shape settle
            staged = np.asarray(staged)
            self._ensure_storage(staged)
        if not self.host.can_allocate(1):
            return  # G2 fully pinned (shouldn't happen: G2 blocks unpin fast)
        [hslot] = self.host.allocate(1)
        self.host.register(hslot, block_hash)
        self.host.release([hslot])       # → inactive: resident, evictable

        def land(staged=staged, hslot=hslot):
            self._host_data[hslot] = np.asarray(staged)

        # Backpressure: each pending land pins a device staging buffer in
        # HBM; cap the backlog so an eviction burst can't OOM the device
        # (settling the oldest waits for exactly one transfer).
        if len(self._pending_host) >= 16:
            self._settle_host(next(iter(self._pending_host)))
        self._pending_host[block_hash] = self._offload_pool.submit(land)
        self.offloaded_blocks += 1
        # Tier-demotion breadcrumb (ISSUE 14): G1→G2 pressure in the
        # seconds before a stall/OOM is exactly what the postmortem
        # needs and what the cumulative gauges can't order.
        fl = flight_recorder.get_recorder()
        if fl.enabled:
            fl.record("tier_demote", src="G1", dst="G2", slot=hslot)

    def _settle_host(self, block_hash: int) -> bool:
        """Settle an in-flight offload for `block_hash` (if any) before
        reading its G2 bytes.  Returns False — and DISCARDS the G2
        registration — when the deferred device→host copy failed: the
        slot would otherwise serve uninitialized bytes as valid KV, and
        the captured exception would detonate inside whichever unrelated
        engine operation touched the hash next."""
        fut = self._pending_host.pop(block_hash, None)
        if fut is None:
            return True
        try:
            fut.result()
            return True
        except Exception:
            logger.exception("async offload of block %x failed; dropping "
                             "its G2 entry", block_hash)
            if self.host is not None:
                self.host.discard(block_hash)
            return False

    def _on_host_evict(self, block_hash: int, slot: int) -> None:
        """G2 eviction → spill to G3 (if enabled).

        The pending-offload entry is settled FIRST, on every path: an
        early return that left it behind would leak one Future per
        evicted hash forever."""
        ok = self._settle_host(block_hash)
        if self.disk is None or self._host_data is None or not ok:
            return
        if self.disk.registry.lookup(block_hash) is not None:
            return
        if not self.disk.can_allocate(1):
            return
        [dslot] = self.disk.allocate(1)
        self._disk_data[dslot] = self._host_data[slot]
        self.disk.register(dslot, block_hash)
        fl = flight_recorder.get_recorder()
        if fl.enabled:
            fl.record("tier_demote", src="G2", dst="G3", slot=dslot)
        self.disk.release([dslot])
        self.offloaded_blocks += 1

    # -- onboard path (up-tier) --------------------------------------------

    @engine_thread_only
    def match_and_onboard(self, hashes: Sequence[int]) -> Tuple[int, List[int]]:
        """Find the longest prefix resident in ANY tier; promote down-tier
        blocks into G1; pin and return (num_blocks, device_slot_ids).

        The returned slots are pinned for the caller (release via
        `release`)."""
        # 1) direct G1 prefix
        g1 = self.device.match_sequence_hashes(hashes)
        ids = self.device.acquire_matched(g1)
        n = len(ids)
        # 2) extend from lower tiers (G2 host → G3 disk → G4 remote).
        # Capacity/inject guards come FIRST: tiers below G2 materialize
        # data (disk read, remote network pull) and a block fetched with
        # nowhere to put it would be wasted work re-paid on every retry.
        while n < len(hashes):
            if self.inject_fn is None or not self.device.can_allocate(1):
                break
            h = hashes[n]
            data = None
            if self.host is not None:
                hslot = self.host.registry.lookup(h)
                if hslot is not None and self._settle_host(h):
                    data = self._host_data[hslot.index]
            if data is None and self.disk is not None:
                dslot = self.disk.registry.lookup(h)
                if dslot is not None:
                    data = np.array(self._disk_data[dslot.index])
            if data is None and self.remote_fetch_fn is not None:
                data = self.remote_fetch_fn(h)
                if data is not None:
                    self.remote_fetched_blocks += 1
            if data is None:
                break
            [gslot] = self.device.allocate(1)
            try:
                self.inject_fn(gslot, data)
            except Exception:
                # Un-injectable bytes (e.g. a kv-quant-mode mismatch from
                # a remote peer): release the fresh slot and stop the
                # prefix here — never leave a pinned slot with junk.
                self.device.release([gslot])
                raise
            self.device.register(gslot, h)
            ids.append(gslot)
            n += 1
            self.onboarded_blocks += 1
        return n, ids

    # -- cross-worker transfer (the NIXL-analog data plane) ----------------

    def export_block(self, block_hash: int) -> Optional[np.ndarray]:
        """Raw KV bytes of a resident block, searched G1→G2→G3 (the
        extract side of worker↔worker transfer; reference
        `block_manager/block/transfer.rs` + `storage/nixl.rs:403`)."""
        slot = self.device.registry.lookup(block_hash)
        if slot is not None and self.extract_fn is not None:
            return np.asarray(self.extract_fn(slot.index))
        if self.host is not None:
            hslot = self.host.registry.lookup(block_hash)
            if (hslot is not None and self._host_data is not None
                    and self._settle_host(block_hash)):
                return np.array(self._host_data[hslot.index])
        if self.disk is not None:
            dslot = self.disk.registry.lookup(block_hash)
            if dslot is not None and self._disk_data is not None:
                return np.array(self._disk_data[dslot.index])
        return None

    def export_block_device(self, block_hash: int):
        """G1-resident block as a DEVICE array (no host staging) — the
        extract side of the device-direct transfer plane
        (device_transfer.py).  None when the block lives only in G2/G3
        (those bytes are host-resident anyway; the host-staged path
        serves them)."""
        slot = self.device.registry.lookup(block_hash)
        if slot is not None and self.extract_fn is not None:
            return self.extract_fn(slot.index)
        return None

    @engine_thread_only
    def import_block(self, block_hash: int, data: np.ndarray) -> bool:
        """Inject a fetched block into G1 and register it (inactive,
        matchable) — the onboard side of a remote transfer.  Returns False
        when already resident or no capacity."""
        if self.device.registry.lookup(block_hash) is not None:
            return False  # already resident
        if self.inject_fn is None or not self.device.can_allocate(1):
            return False
        [slot] = self.device.allocate(1)
        try:
            self.inject_fn(slot, data)
        except Exception:
            self.device.release([slot])  # mode-mismatch etc: no junk slot
            raise
        if not self.device.register(slot, block_hash):
            self.device.release([slot])
            return False
        self.device.release([slot])  # -> inactive: resident, matchable
        self.onboarded_blocks += 1
        return True

    @engine_thread_only
    def demote_blocks(self, hashes: Sequence[int]) -> int:
        """QoS preemption demotion: push the given G1-resident INACTIVE
        blocks down to the host tier now, freeing their device slots.
        Without a host tier this is a deliberate no-op — the blocks stay
        inactive in G1 (still resumable until LRU pressure reclaims
        them) rather than being destroyed; "demoted, not lost" is the
        contract.  Returns how many blocks actually moved."""
        if self.host is None:
            return 0
        n = 0
        for h in hashes:
            # device.on_evict is the chained hook (ManagedBlockSource):
            # offload to G2 first, then the REMOVED KV event that keeps
            # router indexes truthful about G1 residency.
            if self.device.demote_hash(h):
                n += 1
        return n

    def set_eviction_bias(self, fn, scan: int = 8) -> None:
        """Install the eviction-bias hook on every demoting tier: G1
        eviction chooses what rides down to G2, G2 eviction what spills
        to G3 — biasing both keeps hot prefixes as high in the
        hierarchy as capacity allows (the SLO-aware hook,
        `pool.slo_eviction_bias`).  G3 has nowhere to demote to, so it
        stays pure LRU."""
        self.device.set_eviction_bias(fn, scan)
        if self.host is not None:
            self.host.set_eviction_bias(fn, scan)

    @never_engine_thread
    def close(self) -> None:
        """Settle outstanding offloads and stop the worker thread (a
        manager per discarded engine would otherwise leak its thread).
        Joining the offload pool FROM the engine thread would stall the
        step loop for the whole backlog, hence @never_engine_thread."""
        for h in list(self._pending_host):
            self._settle_host(h)
        self._offload_pool.shutdown(wait=True)

    # -- passthrough G1 ops ------------------------------------------------

    def allocate(self, n: int) -> List[int]:
        return self.device.allocate(n)

    def register(self, slot: int, block_hash: int) -> bool:
        return self.device.register(slot, block_hash)

    def release(self, slots: Sequence[int]) -> None:
        self.device.release(slots)

    @property
    def stats(self) -> Dict[str, float]:
        s = {
            "g1_active": self.device.active_slots,
            "g1_free": self.device.free_slots,
            "g1_hits": self.device.hits,
            "g1_misses": self.device.misses,
            "offloaded": self.offloaded_blocks,
            "onboarded": self.onboarded_blocks,
            "remote_fetched": self.remote_fetched_blocks,
        }
        if self.host:
            s["g2_resident"] = len(self.host.registry.by_hash)
        if self.disk:
            s["g3_resident"] = len(self.disk.registry.by_hash)
        return s

    def clear_cache(self) -> List[int]:
        """Admin flush (reference `http/service/clear_kv_blocks.rs`): drop
        every reusable cached block in every tier.  Returns the G1 hashes
        dropped (the ones routers index via KV events)."""
        for h in list(self._pending_host):
            self._settle_host(h)
        dropped = self.device.clear_inactive()
        if self.host is not None:
            self.host.clear_inactive()
        if self.disk is not None:
            self.disk.clear_inactive()
        return dropped
