"""Paged KV cache as preallocated JAX arrays.

TPU re-imagining of vLLM's paged KV cache (which the reference orchestrates
around but does not implement; its block bookkeeping lives in
`lib/llm/src/block_manager/layout.rs` — LayoutConfig{num_blocks, num_layers,
page_size, inner_dim, dtype}).  On TPU the cache must be a *static-shape*
array XLA can reason about, so:

- storage is PER-LAYER arrays `[num_blocks * block_size, num_kv_heads *
  head_dim]` for K and V — a flat "slot" axis by a flat "feature" axis,
  so both the scatter (write new tokens) and gather (read context) are
  single `take`/`scatter` ops with precomputed flat indices.  Layers are
  separate buffers, NOT one stacked [L, S, F] array: each layer's
  update is then an independent in-place scatter XLA can alias under
  donation and inside `fori_loop` carries, and the Pallas decode kernel
  reads the layer buffer directly in HBM.  (r2 stacked the layers; every
  layer update sliced + wrote back the whole array and every kernel call
  materialised its layer slice — the decode step ran ~15x over its HBM
  floor.);
- the feature axis is FLAT (Hkv * head_dim, head-major) rather than a
  [Hkv, D] pair: with head_dim 64, a 3D [S, 8, 64] buffer tiles as
  T(8,128) on its two minor dims, and XLA's layout assignment stores it
  transposed ({0,2,1}) to dodge the 64→128 lane padding — then inserts
  TWO full-buffer relayout copies per layer per decode step to feed the
  row-major scatter and the Pallas kernel (r3 measured ~4.3 GB/token of
  pure relayout traffic, 3/4 of the whole step).  A 2D [S, F=512] buffer
  has one natural layout; scatter, kernel, and carry all agree, and the
  relayouts vanish;
- block 0 is reserved as the *null block*: padded block-table entries point
  at it, and its contents are never read unmasked;
- sharding: `num_kv_heads` over the `tp` mesh axis (head-sharded cache means
  KV writes and attention reads stay device-local under tensor parallelism).

The index math (block table → flat slots) runs inside jit on int32 arrays —
no host round-trip per step.

Quantized mode (`kv_quant="int8"`, ISSUE 6): K/V buffers store int8 with
per-token-per-head f32 scales in sibling `[S, Hkv]` arrays (`k_scale` /
`v_scale` in the cache pytree).  Scales are per-TOKEN so the incremental
scatter write stays a scatter (a per-block scale would have to requantize
every previously written token in the block when a new token raises the
block max — impossible in-place under jit); grouped per BLOCK for
export/import, where a page's `[block_size, Hkv]` scale slice travels
atomically with its int8 rows inside one packed array (see
`make_block_ops`).  Decode attention dequantizes INSIDE the kernel's VMEM
tile after the DMA (ops/pallas/paged_attention.py), so HBM reads ~halve:
per context token the wire cost drops from `2*F*2` bf16 bytes to
`2*(F + 4*Hkv)` bytes — a 0.53x ratio at serving geometry (head_dim 64).

Latent mode (`latent_row` > 0: a latent-attention model, MLA): ONE buffer a
layer, `{'kv': [L x [S, latent_row]]}`, whose row is `[c_kv (normed) |
k_rope (rotated) | zeros to a 128-lane multiple]` — the compressed row all
heads share, which every read takes in the weight-absorbed form
(models/llama._latent_read).  The wire block is `[1, L, bs,
latent_row]`; the byte counts below count the row at its stored width,
padding included.  int8 and meshes are refused for it at construction.

Recurrent state (`state_slots` > 0: a model with state-space layers, the
third kind of state beside pages of K/V and pages of latent rows): two more
leaves a layer, `ssm [state_slots + 1, heads, head_dim, d_state]` in float32
and `conv [state_slots + 1, taps - 1, channels]` at the cache's dtype, a
fixed-size slot a sequence indexed by the scheduler's `req.slot`; the last
index is scratch, where padding rows read and write.  A slot is not paged and
holds the state after the sequence's last computed token only, so nothing of
it can be shared, exported by block or resumed from a cached prefix: the
engine gives such a model the no-reuse block source and refuses int8,
meshes, block transfer and tier offload at construction.  A model without
such layers gets neither leaf: its cache pytree, and so its step programs,
are what they were.

Two page groups (a model with window layers, `ModelConfig.layer_windows`):
the `k` and `v` buffers of a WINDOW layer are `window_blocks * block_size`
slots long and those of a FULL layer `num_blocks * block_size`: two pools
under the same two leaves, each with a null block 0, each layer kind indexed
through a table of its own (the step programs' `window_tables` beside
`block_tables`, both by position).  A window layer reads at most its window
behind a query, so the scheduler gives a window-group block back once every
position in it is a window or more behind the sequence's next position, and
the table's entry becomes the null block, which the window kernels never
fetch.  Bounded on purpose: bf16 pages, meshless, no prefix reuse (a cached
prefix's window pages are gone), no block transfer or tier offload.

Leaves by layer kind (a model whose layers differ by a pattern,
`ModelConfig.layer_pattern`): the `k` and `v` lists hold one buffer an
ATTENTION layer and the `ssm` and `conv` lists one leaf a STATE layer, each
in layer order; a layer of routed experts holds nothing.  `num_layers` here
counts the layers that page K and V and `state_layers` those that keep
state, so every byte count follows the kinds (`ModelConfig.attention_layers`
and `state_layers` map a model's layer to its place in the lists).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.models.config import ModelConfig

# Block-table entries for never-allocated pages point at the null block.
NULL_BLOCK = 0


@dataclass(frozen=True)
class KvCacheConfig:
    """Geometry of the paged cache (reference LayoutConfig analog,
    `block_manager/layout.rs`)."""

    num_blocks: int          # includes the reserved null block 0
    block_size: int          # tokens per block (reference default 64)
    num_layers: int
    num_kv_heads: int
    head_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    # "none" = store K/V at `dtype`; "int8" = int8 pages + per-token
    # per-head f32 scales (see module docstring).
    kv_quant: str = "none"
    # > 0: the latent form (module docstring): one buffer a layer of rows
    # this wide; num_kv_heads and head_dim then describe nothing stored.
    latent_row: int = 0
    # > 0: recurrent state beside the pages (module docstring): this many
    # sequence slots and one scratch slot a layer, each an `ssm_shape`
    # float32 state (heads, head_dim, d_state) and a `conv_shape` tail of
    # the convolution's inputs (taps - 1, channels) at `dtype`.
    state_slots: int = 0
    ssm_shape: Tuple[int, ...] = ()
    conv_shape: Tuple[int, ...] = ()
    # Layers that keep such state; None: all `num_layers` of them.
    state_layers: Optional[int] = None
    # The window group (a model with window layers; module docstring): the
    # places in `k` and `v` of the layers whose pages are that group's, and
    # the blocks of its own pool (the null block 0 among them).  Every byte
    # count below stays the FULL group's: `num_blocks` blocks of the layers
    # that are not named here.
    window_places: Tuple[int, ...] = ()
    window_blocks: int = 0

    def __post_init__(self):
        if self.kv_quant not in ("none", "int8"):
            raise ValueError(f"kv_quant must be 'none' or 'int8', "
                             f"got {self.kv_quant!r}")
        if self.latent and self.quantized:
            raise ValueError(
                "a latent (MLA) cache has no int8 form: kv_quant='int8' is "
                "refused for a latent-attention model (its one row a token "
                "has no per-head scale to carry)")
        if self.has_state and self.quantized:
            raise ValueError(
                "a model with state-space layers has no int8 KV form: "
                "kv_quant='int8' is refused beside recurrent state slots")
        if self.window_places and self.quantized:
            from dynamo_tpu.models.config import WINDOW_NO_INT8

            raise ValueError(WINDOW_NO_INT8)
        if self.window_places and self.window_blocks < 2:
            raise ValueError("a window group needs at least 2 blocks (block "
                             "0 is the null block)")

    @property
    def full_layers(self) -> int:
        """Layers whose pages are the full group's (all of them without a
        window group)."""
        return self.num_layers - len(self.window_places)

    @property
    def window_bytes_per_block(self) -> int:
        """K+V bytes of one block of the window group across its layers."""
        return (self.buffers * len(self.window_places) * self.block_size
                * self.feature_dim * jnp.dtype(self.dtype).itemsize)

    @property
    def has_state(self) -> bool:
        return self.state_slots > 0

    @property
    def num_state_layers(self) -> int:
        if not self.has_state:
            return 0
        return (self.num_layers if self.state_layers is None
                else self.state_layers)

    @property
    def state_bytes_per_slot(self) -> int:
        """Bytes of recurrent state one sequence holds across all layers:
        the float32 scan state and the convolution's tail.  Fixed: it does
        not grow with the context."""
        if not self.has_state:
            return 0
        return self.num_state_layers * (
            4 * math.prod(self.ssm_shape)
            + math.prod(self.conv_shape) * jnp.dtype(self.dtype).itemsize)

    @property
    def latent(self) -> bool:
        return self.latent_row > 0

    @property
    def buffers(self) -> int:
        """Row buffers a layer: K and V, or the one latent row."""
        return 1 if self.latent else 2

    @property
    def quantized(self) -> bool:
        return self.kv_quant == "int8"

    @property
    def num_slots(self) -> int:
        return self.num_blocks * self.block_size

    @property
    def feature_dim(self) -> int:
        """Flat per-token K (or V) width: num_kv_heads * head_dim; the
        stored row's width for a latent cache."""
        return self.latent_row or self.num_kv_heads * self.head_dim

    @property
    def store_dtype(self):
        """Dtype of the K/V page buffers as stored in HBM."""
        return jnp.int8 if self.quantized else self.dtype

    @property
    def bytes_per_context_token(self) -> int:
        """K+V bytes one decode step reads from HBM per context token,
        across all layers — INCLUDING quantization scales.  This is the
        numerator of every bytes/token roofline claim."""
        if self.quantized:
            per = self.feature_dim + 4 * self.num_kv_heads  # int8 + f32 scale
        else:
            per = self.feature_dim * jnp.dtype(self.dtype).itemsize
        return self.buffers * self.full_layers * per

    @property
    def bytes_per_block(self) -> int:
        """K+V bytes for one block across all layers (the unit the block
        manager, router and dynamo_kv_pool_* / HBM accounting count in).
        Quantized mode includes the per-token-per-head f32 scales — the
        tiers store pages+scales together, so reporting bare int8 bytes
        would understate real residency by 4*Hkv/F (~6% at head_dim 64,
        25% at head_dim 16)."""
        return self.block_size * self.bytes_per_context_token

    @property
    def ring_payload_bytes_per_token(self) -> int:
        """Bytes ONE token's K+V contribute to each ring-SP hop, summed
        over layers (every layer's attention rotates its own chunk).
        Unquantized chunks rotate at the compute dtype; quantized chunks
        rotate int8 rows + their f32 scales (ISSUE 12 leg 1) — the ICI
        exchange halves with the cache mode, and the modeled
        `ring_exchange_bytes` series must say so."""
        if self.quantized:
            per = self.feature_dim + 4 * self.num_kv_heads
        else:
            per = self.feature_dim * jnp.dtype(self.dtype).itemsize
        return 2 * self.num_layers * per

    @property
    def block_wire_shape(self) -> tuple:
        """Canonical shape of one exported block (the transfer-plane and
        tier-storage unit).  bf16 mode: [2, L, bs, F] at `dtype`; int8
        mode: [2, L, bs, F + 4*Hkv] int8, the trailing 4*Hkv bytes being
        the page's [bs, Hkv] f32 scales bitcast to bytes so pages and
        scales ship atomically in ONE array."""
        feat = self.feature_dim
        if self.quantized:
            feat += 4 * self.num_kv_heads
        return (self.buffers, self.num_layers, self.block_size, feat)

    @property
    def block_wire_dtype(self):
        return jnp.int8 if self.quantized else self.dtype

    @staticmethod
    def for_model(
        config: ModelConfig,
        num_blocks: int,
        block_size: int = 64,
        dtype: jnp.dtype | None = None,
        kv_quant: str = "none",
        state_slots: int = 0,
        window_blocks: int = 0,
    ) -> "KvCacheConfig":
        """`state_slots`: the sequences that can be live at once (the
        scheduler's `max_seqs`); read only for a model with state-space
        layers.  `window_blocks`: the window group's pool; read only for a
        model with window layers."""
        state = {}
        if config.has_window:
            at = {layer: j for j, layer in enumerate(config.attention_layers)}
            state = dict(
                window_places=tuple(at[i] for i in config.window_layers),
                window_blocks=window_blocks)
        if config.has_ssm:
            state = dict(
                state_slots=state_slots,
                state_layers=len(config.state_layers),
                ssm_shape=(config.mamba_n_heads, config.mamba_d_head,
                           config.mamba_d_state),
                conv_shape=(config.mamba_d_conv - 1, config.mamba_conv_dim))
        return KvCacheConfig(
            **state,
            num_blocks=num_blocks,
            block_size=block_size,
            num_layers=len(config.attention_layers),
            num_kv_heads=config.num_kv_heads,
            head_dim=config.head_dim,
            dtype=dtype if dtype is not None else config.dtype,
            kv_quant=kv_quant,
            latent_row=config.latent_row if config.is_latent else 0,
        )


def init_cache(cfg: KvCacheConfig) -> dict:
    """Allocate the cache pytree: {'k': [L x [S, F]], 'v': [L x [S, F]]}
    — per-layer 2D buffers, F = num_kv_heads * head_dim head-major (see
    module docstring for why flat, and why not one stacked array).

    Quantized mode adds {'k_scale': [L x [S, Hkv]], 'v_scale': ...} f32
    sibling buffers; forward steps branch on the presence of these keys
    (static at trace time), so one factory serves both modes."""
    shape = (cfg.num_slots, cfg.feature_dim)
    if cfg.latent:
        return {"kv": [jnp.zeros(shape, cfg.store_dtype)
                       for _ in range(cfg.num_layers)]}
    if cfg.window_places:
        # Two pools under the same two leaves: a window layer's buffers are
        # the window group's blocks long, a full layer's the full group's.
        wshape = (cfg.window_blocks * cfg.block_size, cfg.feature_dim)
        return {leaf: [jnp.zeros(wshape if j in cfg.window_places else shape,
                                 cfg.store_dtype)
                       for j in range(cfg.num_layers)] for leaf in "kv"}
    cache = {}
    if cfg.has_state:
        n = cfg.state_slots + 1           # the last one is scratch
        cache = {
            "ssm": [jnp.zeros((n,) + tuple(cfg.ssm_shape), jnp.float32)
                    for _ in range(cfg.num_state_layers)],
            "conv": [jnp.zeros((n,) + tuple(cfg.conv_shape), cfg.dtype)
                     for _ in range(cfg.num_state_layers)]}
    cache.update({
        "k": [jnp.zeros(shape, cfg.store_dtype)
              for _ in range(cfg.num_layers)],
        "v": [jnp.zeros(shape, cfg.store_dtype)
              for _ in range(cfg.num_layers)],
    })
    if cfg.quantized:
        sshape = (cfg.num_slots, cfg.num_kv_heads)
        cache["k_scale"] = [jnp.zeros(sshape, jnp.float32)
                            for _ in range(cfg.num_layers)]
        cache["v_scale"] = [jnp.zeros(sshape, jnp.float32)
                            for _ in range(cfg.num_layers)]
    return cache


def cache_is_quantized(cache: dict) -> bool:
    """Static (trace-time) quantization test: the pytree structure IS the
    mode bit."""
    return "k_scale" in cache


def cache_is_latent(cache: dict) -> bool:
    """Static (trace-time) test for the latent form, as `k_scale` is for
    int8: the pytree's structure is the mode bit."""
    return "kv" in cache


def slots_for_positions(
    block_tables: jax.Array,  # [B, P] int32 block ids
    positions: jax.Array,     # [B, T] int32 absolute token positions
    block_size: int,
) -> jax.Array:
    """Flat slot index for each (sequence, position): `bt[pos//bs]*bs + pos%bs`.

    Positions whose page index falls past the table width resolve to the
    null block explicitly (not clip-to-last-column, which would alias a
    *real* page and corrupt cached context); within-table entries that were
    never allocated are NULL_BLOCK by construction, so their slots are junk
    by design and must stay masked by the caller.
    """
    block_idx = positions // block_size            # [B, T]
    offset = positions % block_size                # [B, T]
    P = block_tables.shape[1]
    in_range = block_idx < P
    block_ids = jnp.take_along_axis(
        block_tables, jnp.minimum(block_idx, P - 1), axis=1)  # [B, T]
    block_ids = jnp.where(in_range, block_ids, NULL_BLOCK)
    return block_ids * block_size + offset


def write_kv(
    cache_layer_k: jax.Array,  # [S, F]
    cache_layer_v: jax.Array,
    slots: jax.Array,          # [N] flat slot ids (may repeat NULL for pad)
    k: jax.Array,              # [N, F] flat rows
    v: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Scatter new K/V rows into one layer's slot axis.

    Padding tokens should carry slot 0 (null block) so their writes land in
    the reserved junk block.  `mode="drop"` guards out-of-range indices.
    """
    k_new = cache_layer_k.at[slots].set(k.astype(cache_layer_k.dtype),
                                        mode="drop")
    v_new = cache_layer_v.at[slots].set(v.astype(cache_layer_v.dtype),
                                        mode="drop")
    return k_new, v_new


def write_latent(cache_layer: jax.Array, slots: jax.Array,
                 rows: jax.Array) -> jax.Array:
    """Scatter new latent rows [N, row] into one layer's buffer [S, row]:
    `write_kv`'s discipline on the one buffer of a latent cache (pad tokens
    carry the null block's slot; `mode="drop"` guards out-of-range)."""
    return cache_layer.at[slots].set(rows.astype(cache_layer.dtype),
                                     mode="drop")


def gather_kv(
    cache_layer_k: jax.Array,  # [S, F]
    cache_layer_v: jax.Array,
    slots: jax.Array,          # [B, C] flat slot ids for each context position
    num_kv_heads: int,
) -> Tuple[jax.Array, jax.Array]:
    """Gather per-sequence context K/V: returns [B, C, H, D] pairs."""
    B, C = slots.shape
    F = cache_layer_k.shape[-1]
    D = F // num_kv_heads
    k = jnp.take(cache_layer_k, slots, axis=0, mode="clip")
    v = jnp.take(cache_layer_v, slots, axis=0, mode="clip")
    return (k.reshape(B, C, num_kv_heads, D),
            v.reshape(B, C, num_kv_heads, D))


# ---------------------------------------------------------------------------
# int8 quantization (kv_quant="int8")

# Smallest per-head scale: heads whose K/V rows are all-zero (padding, the
# null block) quantize to 0 with a nonzero scale instead of dividing by 0.
_QUANT_EPS = 1e-8


def quantize_kv_rows(
    x: jax.Array,              # [N, F] rows in compute dtype
    num_kv_heads: int,
) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-token-per-head int8 quantization: returns
    (int8 [N, F], f32 scales [N, Hkv]) with x ≈ q * scale[..., head]."""
    N, F = x.shape
    D = F // num_kv_heads
    xf = x.astype(jnp.float32).reshape(N, num_kv_heads, D)
    amax = jnp.max(jnp.abs(xf), axis=-1)                    # [N, Hkv]
    scale = jnp.maximum(amax, _QUANT_EPS) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8).reshape(N, F), scale


def dequantize_rows(
    q: jax.Array,              # [..., Hkv, D] int8
    scale: jax.Array,          # [..., Hkv] f32
    out_dtype=jnp.bfloat16,
) -> jax.Array:
    """Inverse of quantize_kv_rows on head-split rows: f32 multiply then
    cast to `out_dtype` — the same dequant numerics as the Pallas
    kernel's in-VMEM path, so the XLA gather path and the kernel agree
    bit-for-bit on the dequantized operands."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(out_dtype)


def scatter_kv_quant(
    cache_layer_k: jax.Array,   # [S, F] int8
    cache_layer_v: jax.Array,
    scale_layer_k: jax.Array,   # [S, Hkv] f32
    scale_layer_v: jax.Array,
    slots: jax.Array,           # [N] flat slot ids (NULL for pad)
    kq: jax.Array,              # [N, F] int8 rows (already quantized)
    vq: jax.Array,
    ks: jax.Array,              # [N, Hkv] f32 scales
    vs: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Scatter ALREADY-quantized rows + scales into one layer —
    write_kv_quant minus the quantization.  Callers that need the int8
    rows for their own attention (the ring-SP chunk exchange, ISSUE 12
    leg 1) quantize ONCE via quantize_kv_rows and share the result, so
    the cache and the ring can never hold different quantizations of the
    same token."""
    return (
        cache_layer_k.at[slots].set(kq, mode="drop"),
        cache_layer_v.at[slots].set(vq, mode="drop"),
        scale_layer_k.at[slots].set(ks, mode="drop"),
        scale_layer_v.at[slots].set(vs, mode="drop"),
    )


def write_kv_quant(
    cache_layer_k: jax.Array,   # [S, F] int8
    cache_layer_v: jax.Array,
    scale_layer_k: jax.Array,   # [S, Hkv] f32
    scale_layer_v: jax.Array,
    slots: jax.Array,           # [N] flat slot ids (NULL for pad)
    k: jax.Array,               # [N, F] unquantized rows
    v: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Quantize and scatter new K/V rows + their scales into one layer.
    Same padding discipline as write_kv (pad rows target the null block;
    `mode="drop"` guards out-of-range)."""
    H = scale_layer_k.shape[-1]
    kq, ks = quantize_kv_rows(k, H)
    vq, vs = quantize_kv_rows(v, H)
    return scatter_kv_quant(cache_layer_k, cache_layer_v, scale_layer_k,
                            scale_layer_v, slots, kq, vq, ks, vs)


def gather_kv_quant(
    cache_layer_k: jax.Array,   # [S, F] int8
    cache_layer_v: jax.Array,
    scale_layer_k: jax.Array,   # [S, Hkv] f32
    scale_layer_v: jax.Array,
    slots: jax.Array,           # [B, C]
    num_kv_heads: int,
    out_dtype=jnp.bfloat16,
) -> Tuple[jax.Array, jax.Array]:
    """Gather + dequantize context K/V: returns [B, C, H, D] in
    `out_dtype` (the XLA fallback path's read side; prefill attention
    and non-Pallas decode both come through here in int8 mode)."""
    B, C = slots.shape
    F = cache_layer_k.shape[-1]
    D = F // num_kv_heads
    kq = jnp.take(cache_layer_k, slots, axis=0, mode="clip")
    vq = jnp.take(cache_layer_v, slots, axis=0, mode="clip")
    ks = jnp.take(scale_layer_k, slots, axis=0, mode="clip")
    vs = jnp.take(scale_layer_v, slots, axis=0, mode="clip")
    k = dequantize_rows(kq.reshape(B, C, num_kv_heads, D), ks, out_dtype)
    v = dequantize_rows(vq.reshape(B, C, num_kv_heads, D), vs, out_dtype)
    return k, v


def wire_block_pspec(mesh, cache_specs, wire_shape):
    """PartitionSpec for the canonical wire block [2, L, bs, F*] that
    mirrors how THIS cache shards its pages: the cache K-leaf spec
    [slots, features] maps axis-for-axis onto the wire block's
    (block_size, features) trailing dims.

    This is the generalized cross-mesh reshard's landing layout (ISSUE
    16): a pulled block device_put directly onto this sharding scatters
    straight into the cache's own layout — head-sharded tp lands
    head-sharded, dp_local slot-sharded lands slot-sharded — so an
    sp-prefill worker's KV arrives on a tp+int8 decode worker with ONE
    puller-side device_put and zero device-0 pileup, for ARBITRARY
    source→dest PartitionSpec pairs (the source's layout never appears
    here; device_put reshards whatever arrives).

    Falls back to fully replicated P() when a sharded axis would not
    divide the wire shape (jax refuses non-divisible NamedShardings) —
    replicated is always a correct landing, just not a balanced one.
    """
    from jax.sharding import PartitionSpec as P

    try:
        spec = cache_specs["k"][0]
    except (KeyError, IndexError, TypeError):
        return P()
    slot_ax = spec[0] if len(spec) > 0 else None
    feat_ax = spec[1] if len(spec) > 1 else None

    def shards(ax) -> int:
        names = ax if isinstance(ax, tuple) else (ax,) if ax else ()
        n = 1
        for nm in names:
            n *= dict(mesh.shape).get(nm, 1)
        return n

    bs, fw = int(wire_shape[2]), int(wire_shape[3])
    # Packed int8 note: F* = Hkv*(D+4) and tp | Hkv, so the feature
    # split stays divisible even with scales in-band; the guard is for
    # tiny test geometries where it is not.
    if bs % shards(slot_ax) or fw % shards(feat_ax):
        return P()
    return P(None, None, slot_ax, feat_ax)


def make_block_ops(block_size: int, mesh=None, cache_specs=None,
                   constrain_mesh=None):
    """Jitted whole-block extract/inject against the cache pytree.

    These are the device ends of every tier/wire movement — G1→G2 offload,
    G2/G3→G1 onboard, and the cross-worker transfer data plane (the role of
    the reference's `block_copy.cu` scatter/gather kernel,
    `lib/llm/src/kernels/block_copy.cu:41`).  The page id is traced so one
    compiled program serves every page.

    `mesh` + `cache_specs` (PartitionSpec pytree for the cache): build the
    multihost variant — extract gathers the block REPLICATED so every
    process can host-read it, inject takes host bytes on every process.
    Required when the cache spans processes (the default jits would try
    to host-read remote shards).

    Returns (extract, inject):
      extract(cache, page) -> [2, L, block_size, F] (K stacked on V;
                              [1, L, block_size, row] for a latent cache)
      inject(cache, page, data) -> cache' (donated, in-place on device)

    Quantized caches (kv_quant="int8") extract the PACKED wire block
    [2, L, block_size, F + 4*Hkv] int8: int8 K/V rows with the page's
    [block_size, Hkv] f32 scales bitcast to trailing bytes — pages and
    scales move through every tier (G2 host, G3 disk, the kv_blocks wire,
    eager streaming) as ONE array, so no path can ship one without the
    other.  Inject unpacks and bitcasts back.  The branch is static: the
    cache pytree's structure selects it at trace time.

    `constrain_mesh` (single-process mesh engines): the quantized pack's
    concatenate — int8 rows sharded on the feature axis joined with
    bitcast scale bytes — is mis-partitioned by GSPMD on meshes that
    carry a replicated axis alongside the sharded one (sp×tp: every
    byte comes back doubled, a partial-sum over the sp replicas).  An
    explicit replicated constraint on the packed result forces a real
    all-gather instead, so the wire block is byte-correct on every
    mesh.  bf16 extracts are unaffected and stay unconstrained.
    """

    def _slice_layers(layers, start):
        return jnp.stack([
            jax.lax.dynamic_slice_in_dim(layer, start, block_size, axis=0)
            for layer in layers])

    def extract(cache: dict, page: jax.Array) -> jax.Array:
        start = page * block_size
        if cache_is_latent(cache):
            return _slice_layers(cache["kv"], start)[None]
        k = _slice_layers(cache["k"], start)
        v = _slice_layers(cache["v"], start)
        if not cache_is_quantized(cache):
            return jnp.stack([k, v])

        ks = _slice_layers(cache["k_scale"], start)  # [L, bs, Hkv] f32
        vs = _slice_layers(cache["v_scale"], start)
        if constrain_mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(constrain_mesh, PartitionSpec())
            k, v, ks, vs = (jax.lax.with_sharding_constraint(x, rep)
                            for x in (k, v, ks, vs))

        def pack(q, s):
            # f32 [L, bs, Hkv] -> int8 [L, bs, Hkv, 4] -> [L, bs, 4*Hkv]
            sb = jax.lax.bitcast_convert_type(s, jnp.int8)
            sb = sb.reshape(s.shape[0], s.shape[1], -1)
            return jnp.concatenate([q, sb], axis=-1)

        return jnp.stack([pack(k, ks), pack(v, vs)])

    def inject(cache: dict, page: jax.Array, data: jax.Array) -> dict:
        start = page * block_size
        upd = jax.lax.dynamic_update_slice_in_dim
        if cache_is_latent(cache):
            data = data.astype(cache["kv"][0].dtype)
            return {"kv": [upd(layer, data[0, i], start, axis=0)
                           for i, layer in enumerate(cache["kv"])]}
        if not cache_is_quantized(cache):
            data = data.astype(cache["k"][0].dtype)
            return {
                "k": [upd(layer, data[0, i], start, axis=0)
                      for i, layer in enumerate(cache["k"])],
                "v": [upd(layer, data[1, i], start, axis=0)
                      for i, layer in enumerate(cache["v"])],
            }
        F = cache["k"][0].shape[-1]
        H = cache["k_scale"][0].shape[-1]
        data = data.astype(jnp.int8)  # packed wire block (validated host-side)

        def unpack(d):  # [L, bs, F + 4H] -> (int8 [L, bs, F], f32 [L, bs, H])
            q = d[..., :F]
            sb = d[..., F:].reshape(d.shape[0], d.shape[1], H, 4)
            return q, jax.lax.bitcast_convert_type(sb, jnp.float32)

        kq, ks = unpack(data[0])
        vq, vs = unpack(data[1])
        return {
            "k": [upd(layer, kq[i], start, axis=0)
                  for i, layer in enumerate(cache["k"])],
            "v": [upd(layer, vq[i], start, axis=0)
                  for i, layer in enumerate(cache["v"])],
            "k_scale": [upd(layer, ks[i], start, axis=0)
                        for i, layer in enumerate(cache["k_scale"])],
            "v_scale": [upd(layer, vs[i], start, axis=0)
                        for i, layer in enumerate(cache["v_scale"])],
        }

    if mesh is None:
        return jax.jit(extract), jax.jit(inject, donate_argnums=(0,))

    from jax.sharding import NamedSharding, PartitionSpec as P

    from dynamo_tpu.parallel.multihost import wrap_global_inputs

    cache_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), cache_specs)
    rep = NamedSharding(mesh, P())
    ex = jax.jit(extract, in_shardings=(cache_sh, rep), out_shardings=rep)
    inj = jax.jit(inject, in_shardings=(cache_sh, rep, rep),
                  out_shardings=cache_sh, donate_argnums=(0,))
    return (wrap_global_inputs(ex, (cache_sh, rep)),
            wrap_global_inputs(inj, (cache_sh, rep, rep)))
