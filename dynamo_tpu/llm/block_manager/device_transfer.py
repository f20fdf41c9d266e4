"""Device-direct KV data plane v2 (the NIXL analog, device edition).

The reference's data plane is RDMA-registered memory with descriptor
exchange (`lib/llm/src/block_manager/storage/nixl.rs:403`,
`docs/architecture/disagg_serving.md:70-99`): workers register buffers
with NIXL, publish metadata to etcd, and peers pull blocks NIC-to-NIC
without host staging.  The TPU-native equivalent built here moves JAX
device arrays over `jax.experimental.transfer`, PJRT's point-to-point
transfer service (DCN/ICI transport on real TPU fleets, TCP on CPU test
rigs).  One `TransferServer` per process; its listen address is the
transfer descriptor root.

The protocol is a descriptor exchange:

- the HOLDER stages G1-resident device blocks for pull under a fresh
  uuid and answers a `kv_offer` RPC with {uuid, address, transport,
  hashes, shape, dtype} — the per-transfer descriptor.  Offers carry
  the canonical wire block (`kv_cache.make_block_ops` extract): bf16
  `[2, L, bs, F]`, or the PACKED int8 `[2, L, bs, F + 4*Hkv]` with the
  page's f32 scales bitcast in-band — quantized fleets transfer
  device-direct with no second format, and the engine's
  `_validate_block` refuses a kv-quant mismatch at inject exactly as it
  does on the host-staged wire;
- the PULLER pulls the arrays device-to-device onto the sharding its
  OWN engine injects from (`EngineCore.block_inject_sharding`: the
  cache's device when meshless, replicated over the mesh otherwise —
  the cross-TP reshard is a `jax.device_put` on the puller, never a
  host hop), acks via `kv_pulled`, and injects them into its G1 as
  registered prefix-cache entries.  No numpy ever materialises.

The hot paths ride this plane in bounded double-buffered batches
(`pull_blocks_device` per batch: offer → pull → ack, batch N+1 in
flight while batch N injects): `EagerPuller` streams sealed blocks
device-to-device WHILE remote prefill runs, `PrefixFetcher` pulls
fleet prefix hints device-first with gap-only host-staged refetch, and
the disagg done-pull pipelines the whole prefix.  The host-staged
msgpack path (transfer.py) remains the fallback for blocks offloaded
out of G1 (G2/G3 bytes live on the host anyway) and for peers without
a compatible fabric — mirroring the reference's per-tier
transfer-strategy selection (`block_manager/transfer/strategy.rs`).
Every plane choice is counted (`note_plane` → the
`dynamo_kv_transfer_plane_total{plane,reason}` series), so a fleet
silently degraded to host staging is visible in `dynamo top`.
"""

from __future__ import annotations

import asyncio
import itertools
import logging
import os
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

from dynamo_tpu.runtime import flight_recorder
from dynamo_tpu.runtime.contracts import hot_path, never_engine_thread
from dynamo_tpu.runtime.logutil import warn_rate_limited
from dynamo_tpu.runtime.rpc import RpcError

logger = logging.getLogger(__name__)

KV_OFFER_ENDPOINT = "kv_offer"
KV_PULLED_ENDPOINT = "kv_pulled"

# Staged-offer cap: staging pins device arrays until the peer pulls (or
# the offer expires), so the cap bounds the strandable HBM.  Offers past
# the cap are refused — callers fall back to the host-staged plane.
MAX_OUTSTANDING_OFFERS = 32
# Per-offer deadline: a puller that dies between offer and pull must not
# wedge the cap forever.  Expired offers retire from the outstanding
# accounting (the arrays stay pinned — this jax has no un-stage API —
# but the cap stops lying).
OFFER_TTL_S = 120.0

DEVICE_PULL_BATCH_BLOCKS = 8     # blocks per offer/pull round
DEVICE_PULL_INFLIGHT = 2         # double-buffered: pull N+1 while N injects


# -- plane-choice accounting ------------------------------------------------
# Process-wide (one serving worker per process): every bulk-pull site in
# disagg.py / prefix_share.py / eager.py records which plane moved the
# blocks and, for host fallbacks, WHY.  Sampled into the
# dynamo_kv_transfer_plane_total{plane,reason} counter family by
# KvCacheMetrics.observe_transfer_plane at scrape time.

_plane_counts: Dict[Tuple[str, str], int] = {}
_plane_lock = threading.Lock()


def note_plane(plane: str, reason: str) -> None:
    """Record one bulk-transfer plane choice (host ints only)."""
    with _plane_lock:
        key = (plane, reason)
        _plane_counts[key] = _plane_counts.get(key, 0) + 1
    # Flight-recorder breadcrumb (ISSUE 14): the counter family shows
    # the cumulative split; the ring shows the ORDER of plane choices in
    # the seconds before a stall or death (e.g. device pulls degrading
    # to host right before a wedge).
    fl = flight_recorder.get_recorder()
    if fl.enabled:
        fl.record("kv_plane", plane=plane, reason=reason)


def plane_counts() -> Dict[Tuple[str, str], int]:
    """Snapshot of the cumulative plane-choice tallies."""
    with _plane_lock:
        return dict(_plane_counts)


def _routable_host() -> str:
    """Best-effort routable address for descriptor advertisement (the
    transfer server binds the wildcard; peers can't dial 0.0.0.0)."""
    import socket

    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect(("10.255.255.255", 1))  # no traffic; routing lookup only
        return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"
    finally:
        s.close()


def _jnp_dtype(name: str):
    if name == "bfloat16":
        import jax.numpy as jnp

        return jnp.bfloat16
    import numpy as np

    return np.dtype(name)


_process_server = None
# Process-wide uuid space: planes share the singleton transfer server,
# so staged transfers must not collide.
_uuid_counter = itertools.count(1)


def _get_transfer_server():
    """ONE TransferServer per process: PJRT's local bulk transport
    CHECK-fails when two servers share a process, and one listener serves
    any number of planes/engines anyway (connections are per peer).

    Explicit TCP transport addresses: the default (empty) advertises the
    same-PROCESS shared-memory bulk transport, which CHECK-fails for a
    same-host cross-process peer; socket transport serves both same-host
    and DCN peers."""
    global _process_server
    if _process_server is None:
        import jax
        from jax.experimental import transfer

        client = jax.devices()[0].client
        _process_server = transfer.start_transfer_server(
            client, "0.0.0.0:0", ["0.0.0.0:0"])
    return _process_server


class _PjrtTransport:
    """Cross-host device fabric over jax.experimental.transfer."""

    kind = "pjrt"

    def __init__(self) -> None:
        self._server = _get_transfer_server()
        self._conns: Dict[str, object] = {}

    @property
    def address(self) -> str:
        addr = self._server.address()
        host, _, port = addr.rpartition(":")
        if host in ("0.0.0.0", "[::]", "::"):
            return f"{_routable_host()}:{port}"
        return addr

    def can_serve(self, peer_fabric: Optional[str]) -> bool:
        # Any pjrt puller (or a legacy peer that sends no fabric id) can
        # dial our transfer server; a local-transport puller cannot.
        return peer_fabric is None or not peer_fabric.startswith("local:")

    def stage(self, uid: int, arrays: List[object]) -> None:
        self._server.await_pull(uid, arrays)

    def retire(self, uid: int) -> None:
        # No un-stage API in this jax: the arrays stay pinned until the
        # server drops them; only the accounting retires.
        pass

    async def pull(self, meta: dict, sds: List[object]) -> List[object]:
        address = meta["address"]
        conn = self._conns.get(address)
        if conn is None:
            conn = self._conns[address] = self._server.connect(address)
        try:
            # The pull blocks until bytes land; keep the event loop free.
            return await asyncio.to_thread(conn.pull, meta["uuid"], sds)
        except Exception:
            # A cached connection to a restarted peer stays dead forever;
            # evict so the next pull re-dials.
            self._conns.pop(address, None)
            raise

    def close(self) -> None:
        self._conns.clear()


class KvTransferPlane:
    """One per worker process: holder + puller halves of the device plane.

    `engine` is an InferenceEngine (async export/import of device blocks,
    and the source of the puller's target sharding); deviceless callers
    (tests) may pass None and use stage/pull directly.
    """

    def __init__(self, engine=None, *,
                 offer_ttl_s: float = OFFER_TTL_S) -> None:
        self.engine = engine
        self.offer_ttl_s = offer_ttl_s
        self._transport = None
        # uuid → (staged blocks, monotonic deadline)
        self._outstanding: Dict[int, Tuple[int, float]] = {}
        # Observability (tests + metrics).
        self.offers = 0
        self.refused_offers = 0
        self.expired_offers = 0
        self.pulled_blocks = 0
        # Device bytes landed by pulls (array nbytes, post-reshard
        # layout): the ledger's kv_transfer stamps and `dynamo top`'s
        # plane split read deltas of this to report how much actually
        # crossed the device fabric.
        self.pulled_bytes = 0
        # Cross-mesh landings: pulls whose target sharding spanned >1
        # device, i.e. the block was resharded source→dest layout on
        # the wire (tests/test_reshard_grid.py::
        # test_heterogeneous_disagg_serves_oracle_output pins this
        # alongside the device plane counter).
        self.reshard_pulls = 0
        self.last_refusal: Optional[str] = None

    def start(self) -> str:
        self._transport = _PjrtTransport()
        return self.address

    @property
    def address(self) -> str:
        return self._transport.address

    @property
    def transport_kind(self) -> str:
        return self._transport.kind

    @property
    def fabric(self) -> str:
        """What a PULLER advertises in its kv_offer probe so the holder
        can refuse incompatible transports before staging anything."""
        return self._transport.kind

    def stop(self) -> None:
        if self._transport is not None:
            for uid in list(self._outstanding):
                self._transport.retire(uid)
            self._transport.close()
        self._outstanding.clear()
        self._transport = None

    # -- holder side -------------------------------------------------------

    def _expire_offers(self) -> None:
        now = time.monotonic()
        expired = [uid for uid, (_, deadline) in self._outstanding.items()
                   if deadline <= now]
        for uid in expired:
            self._outstanding.pop(uid, None)
            self._transport.retire(uid)
            self.expired_offers += 1
        if expired:
            logger.warning(
                "device transfer: %d offer(s) expired unpulled (puller "
                "died between offer and pull); cap accounting reclaimed",
                len(expired))

    @hot_path
    def stage(self, blocks: Dict[int, object], order: Iterable[int],
              peer_fabric: Optional[str] = None,
              ttl_s: Optional[float] = None) -> Optional[dict]:
        """Stage device arrays for one pull; returns the descriptor, or
        None when nothing can be offered — `last_refusal` then names why
        (the caller falls back to the host-staged plane rather than
        stranding memory): 'not_resident' (no requested block in G1),
        'transport' (the peer can't reach this fabric), 'offer_cap'
        (MAX_OUTSTANDING_OFFERS live offers even after TTL expiry).

        `ttl_s` overrides the plane's offer TTL for THIS offer —
        ack-less protocols (the multimodal encode descriptor, which has
        no kv_pulled analog) stage with a short TTL so their offers
        reclaim out of the cap accounting quickly instead of parking
        there for the full default."""
        self.last_refusal = None
        present = [h for h in order if h in blocks]
        if not present:
            self.last_refusal = "not_resident"
            return None
        if not self._transport.can_serve(peer_fabric):
            self.refused_offers += 1
            self.last_refusal = "transport"
            return None
        if len(self._outstanding) >= MAX_OUTSTANDING_OFFERS:
            self._expire_offers()
        if len(self._outstanding) >= MAX_OUTSTANDING_OFFERS:
            self.refused_offers += 1
            self.last_refusal = "offer_cap"
            logger.warning("device transfer: %d offers outstanding "
                           "(unpulled, none expired); refusing until "
                           "peers ack", len(self._outstanding))
            return None
        arrays = [blocks[h] for h in present]
        uid = next(_uuid_counter)
        self._transport.stage(uid, arrays)
        ttl = self.offer_ttl_s if ttl_s is None else ttl_s
        self._outstanding[uid] = (len(present), time.monotonic() + ttl)
        self.offers += 1
        a0 = arrays[0]
        return {
            "uuid": uid,
            "address": self.address,
            "transport": self._transport.kind,
            "hashes": present,
            "shape": list(a0.shape),
            "dtype": str(a0.dtype),
        }

    def mark_pulled(self, uid: int) -> None:
        if self._outstanding.pop(uid, None) is not None:
            self._transport.retire(uid)

    async def offer(self, hashes: List[int],
                    peer_fabric: Optional[str] = None) -> Optional[dict]:
        """Export G1-resident blocks as device arrays and stage them.
        The transport check runs FIRST — an unreachable peer must not
        cost an engine-thread device gather it then throws away."""
        if not self._transport.can_serve(peer_fabric):
            self.refused_offers += 1
            self.last_refusal = "transport"
            return None
        # The transfer service moves single-device buffers: export the
        # canonical device-0 gather.
        blocks = await self.engine.export_blocks_device(hashes)
        return self.stage(blocks, hashes, peer_fabric=peer_fabric)

    def make_offer_handler(self):
        """RPC handler for KV_OFFER_ENDPOINT: {"hashes": [...],
        "fabric": <puller fabric id>} → one descriptor delta, or
        {"reason": ...} when nothing can be offered (nothing G1-resident,
        incompatible transport, or the offer cap — the caller falls back
        to the host-staged kv_blocks plane)."""

        async def handler(payload: dict):
            # A probe with no fabric id is a legacy peer: those can only
            # pull over pjrt.
            meta = await self.offer(payload.get("hashes", []),
                                    peer_fabric=payload.get("fabric")
                                    or "pjrt")
            if meta is not None:
                yield meta
            else:
                yield {"reason": self.last_refusal or "no_offer"}

        return handler

    def make_pulled_handler(self):
        """RPC handler for KV_PULLED_ENDPOINT: the puller's ack retiring
        the offer from the outstanding accounting."""

        async def handler(payload: dict):
            self.mark_pulled(payload.get("uuid"))
            yield {"ok": True}

        return handler

    # -- puller side -------------------------------------------------------

    def _target_sharding(self):
        """The sharding pulled blocks should LAND on: whatever the
        engine's inject consumes (`EngineCore.block_inject_sharding`),
        so the inject's own device_put is a no-op instead of a second
        copy.  Deviceless planes (tests) land on the default device —
        the pre-fix behavior, correct when there is one device."""
        import jax

        core = getattr(self.engine, "core", None)
        sharding = getattr(core, "block_inject_sharding", None)
        if sharding is not None:
            return sharding
        return jax.sharding.SingleDeviceSharding(jax.devices()[0])

    @never_engine_thread
    async def pull(self, meta: dict) -> Dict[int, object]:
        """Pull the staged arrays device-to-device; returns hash → array
        committed to the engine's inject sharding
        (`block_inject_sharding`: the wire block laid out the way THIS
        cache shards — the generalized cross-mesh reshard target).  The
        transfer service delivers single-device buffers, so
        multi-device targets land on one device first and reshard with
        a second device_put.  The host never touches the bytes."""
        import jax

        if not meta or meta.get("uuid") is None:
            return {}
        kind = meta.get("transport", "pjrt")
        if kind != self._transport.kind:
            raise RuntimeError(
                f"descriptor names the {kind!r} fabric but this plane "
                f"runs {self._transport.kind!r} (mixed jax builds "
                "between peers); use the host-staged plane")
        target = self._target_sharding()
        reshard = None
        land = target
        if len(target.device_set) > 1:
            # The transfer service delivers to one device; the mesh
            # layout is a puller-side device_put after landing.
            land = jax.sharding.SingleDeviceSharding(
                min(target.device_set, key=lambda d: d.id))
            reshard = target
        sds = [
            jax.ShapeDtypeStruct(
                tuple(meta["shape"]), _jnp_dtype(meta["dtype"]),
                sharding=land)
            for _ in meta["hashes"]
        ]
        arrays = await self._transport.pull(meta, sds)
        if reshard is not None:
            arrays = await asyncio.to_thread(
                lambda: list(jax.device_put(list(arrays), reshard)))
        if len(target.device_set) > 1:
            self.reshard_pulls += len(arrays)
        self.pulled_blocks += len(arrays)
        for a in arrays:
            self.pulled_bytes += int(getattr(a, "nbytes", 0))
        return dict(zip(meta["hashes"], arrays))


async def _ack_pulled(rpc_client, uid: int) -> None:
    """Retire the holder's offer accounting.  Fire-and-forget semantics
    (a lost ack only consumes cap slack until the offer's TTL), but a
    donor that persistently drops acks is worth ONE line a minute."""
    try:
        async for _ in rpc_client.call(KV_PULLED_ENDPOINT, {"uuid": uid}):
            pass
    except Exception as e:
        warn_rate_limited(
            logger, "kv_pulled_ack", 60.0,
            "kv_pulled ack to donor failed (offer retires via TTL): %s", e)


# Strong refs keep spawned ack tasks alive until done (asyncio only
# weak-refs running tasks); the done-callback discards them.
_ack_tasks: set = set()


def _ack_pulled_async(rpc_client, uid: int) -> None:
    """Spawn the ack off the pull's critical path: the ack is pure
    holder bookkeeping and already tolerated lost (TTL), so the puller
    must not serialize an extra RPC round-trip per batch behind it."""
    task = asyncio.ensure_future(_ack_pulled(rpc_client, uid))
    _ack_tasks.add(task)
    task.add_done_callback(_ack_tasks.discard)


@never_engine_thread
async def pull_blocks_device(plane: KvTransferPlane, rpc_client,
                             hashes: List[int], *,
                             context: str = "pull"
                             ) -> Tuple[Dict[int, object], Optional[str]]:
    """One offer → pull → ack round over the device plane: the unit the
    double-buffered pull pipelines are built from.  Returns
    (blocks, refusal_reason): reason None means a descriptor was granted
    (`blocks` may still be a SUBSET — only G1-resident hashes stage; the
    caller's gap machinery host-fetches the rest); a reason string means
    the holder declined and the caller should use the host-staged plane.
    Transport errors raise — the caller counts the fallback."""
    meta = None
    async for msg in rpc_client.call(KV_OFFER_ENDPOINT,
                                     {"hashes": list(hashes),
                                      "fabric": plane.fabric}):
        meta = msg
    if not meta or meta.get("uuid") is None:
        return {}, (meta or {}).get("reason") or "no_offer"
    blocks = await plane.pull(meta)
    _ack_pulled_async(rpc_client, meta["uuid"])
    note_plane("device", context)
    return blocks, None


@never_engine_thread
async def try_pull_device(plane: KvTransferPlane, rpc_client,
                          hashes: List[int], *, context: str,
                          site: str) -> Tuple[Optional[Dict[int, object]],
                                              Optional[str]]:
    """One device-first batch attempt with the shared fallback
    discipline every pull site (eager stream, prefix share) uses:
    returns (blocks, None) when the device plane served the batch, or
    (None, reason) when the caller should flip sticky to the
    host-staged wire — transport errors are logged here and converted
    to 'pull_failed' so call sites never duplicate the except ladder."""
    try:
        blocks, refusal = await pull_blocks_device(
            plane, rpc_client, hashes, context=context)
    except (ConnectionError, OSError, RpcError, RuntimeError) as e:
        logger.warning("%s: device pull of %d block(s) failed (%s); "
                       "host-staged from here", site, len(hashes), e)
        return None, "pull_failed"
    if refusal is not None:
        return None, refusal
    return blocks, None


@never_engine_thread
async def pull_prefix_device(engine, plane: KvTransferPlane, rpc_client,
                             prompt_tokens: List[int],
                             block_size: int,
                             covered_tokens: int = 0, *,
                             batch_blocks: int = DEVICE_PULL_BATCH_BLOCKS,
                             max_inflight: int = DEVICE_PULL_INFLIGHT,
                             context: str = "disagg") -> int:
    """Device-direct onboard of a peer's sealed prompt blocks: batched
    descriptor probes over the RPC plane, double-buffered device pulls
    (batch N+1 in flight while batch N injects), contiguous-frontier
    inject.  Returns tokens covered; `covered_tokens` unchanged when the
    peer offered nothing (caller falls back to the host-staged pull or
    local prefill).  Transport errors on one batch leave a gap the
    host-staged residual covers; a kv-quant mismatch (inject ValueError)
    propagates — every block would fail identically and the caller must
    fall back to local prefill, not the host wire.

    `covered_tokens`: block-aligned prefix already resident locally (e.g.
    landed by an eager stream) — those hashes are neither offered nor
    pulled, mirroring pull_prefix's resume semantics."""
    from dynamo_tpu.llm.block_manager.transfer import (
        inject_run, sealed_hashes)

    hashes = sealed_hashes(prompt_tokens, block_size)
    hashes = hashes[covered_tokens // block_size:]
    if not hashes:
        return covered_tokens
    sem = asyncio.Semaphore(max(1, max_inflight))
    ready: Dict[int, object] = {}
    inject_lock = asyncio.Lock()
    state = {"frontier": 0, "refusal": None}

    async def inject_ready() -> None:
        async with inject_lock:
            run: Dict[int, object] = {}
            i = state["frontier"]
            while i in ready:
                run[hashes[i]] = ready.pop(i)
                i += 1
            state["frontier"], stalled = await inject_run(
                engine, hashes, run, state["frontier"], i)
            if stalled:
                state["refusal"] = state["refusal"] or "inject_stall"

    async def one(lo: int, hi: int) -> None:
        async with sem:
            if state["refusal"]:
                return
            try:
                blocks, refusal = await pull_blocks_device(
                    plane, rpc_client, hashes[lo:hi], context=context)
            except (ConnectionError, OSError, RpcError, RuntimeError) as e:
                state["refusal"] = "pull_failed"
                logger.warning("device pull of blocks [%d, %d) failed: "
                               "%s", lo, hi, e)
                return
            if refusal is not None:
                state["refusal"] = refusal
                return
            for j, h in enumerate(hashes[lo:hi]):
                if h in blocks:
                    ready[lo + j] = blocks[h]
            await inject_ready()

    tasks = [asyncio.ensure_future(
                one(lo, min(lo + batch_blocks, len(hashes))))
             for lo in range(0, len(hashes), batch_blocks)]
    results = await asyncio.gather(*tasks, return_exceptions=True)
    await inject_ready()
    ready.clear()   # non-contiguous islands: the host residual refetches
    for r in results:
        if isinstance(r, BaseException):
            # In practice a kv-quant ValueError from inject — loud, and
            # the caller must NOT retry over the host wire.
            raise r
    if state["refusal"] and state["frontier"] < len(hashes):
        note_plane("host", state["refusal"])
    return covered_tokens + state["frontier"] * block_size
