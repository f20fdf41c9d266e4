"""Pipeline parallelism: stage-partitioned forward with microbatch rotation.

SURVEY §2.5 row "PP" (the reference configures PP in its delegated
engines for multinode runs, `trtllm/multinode/multinode-examples.md`;
here the engine is ours).  TPU-idiomatic design — a GPipe-style schedule
expressed entirely inside one `shard_map` over the `pp` mesh axis:

- layer stacks shard over pp: stage s owns layers [s·L/S, (s+1)·L/S) as
  STACKED arrays, applied with `lax.scan` (one compiled layer body per
  stage, not L/S unrolled copies);
- the KV cache for the pp path is the stacked [L, slots, F] layout
  sharded over pp on the layer axis — each stage holds exactly its
  layers' cache (int8 caches carry stacked [L, slots, Hkv] scale
  buffers sharded the same way — ISSUE 12 leg 2);
- activations + per-microbatch metadata rotate stage→stage+1 via
  `lax.ppermute` each tick; stage 0 injects fresh microbatch embeddings,
  the last stage runs the LM head and banks logits.  S + M − 1 ticks
  drain M microbatches through S stages; every stage executes identical
  code every tick (junk lanes masked at the end) so the schedule is
  branch-free and XLA-friendly.

The tick schedule is ONE shared body (`_pp_schedule`) that three
programs compile (ISSUE 12 leg 3 — the pp half of the r5 single-step
cliff):

- `make_pp_step` — the plain unified step ([B, V] logits out);
- `make_pp_greedy_step` — the ALL-IN-ONE stage program: schedule +
  on-device argmax fused into one donated-cache dispatch returning [B]
  tokens, so steady pp single-step decode costs 1 dispatch + 1 tiny
  host sync instead of 3 dispatches + a [B, V] f32 transfer;
- `make_pp_decode_window` — K schedule passes in one dispatch with
  on-device token feedback (llama.make_decode_window's contract), so pp
  decode rides the same pipelined window path as every other mesh.

v1 restrictions (validated): dense models (no MoE), pp exclusive of
tp/sp in this step (dp rides outside via engine replicas).  The unified
step contract matches `make_forward_step`, so tests compare logits AND
cache against the single-device oracle.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.engine import kv_cache as kvc
from dynamo_tpu.models.config import ModelConfig


def stack_layer_params(params: Dict) -> Dict:
    """Convert the per-layer list-of-dicts into stacked arrays [L, ...]
    (scan-ready; the pp in_spec shards axis 0)."""
    layers = params["layers"]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    out = dict(params)
    out["layers"] = stacked
    return out


def init_pp_cache(cfg: kvc.KvCacheConfig) -> Dict:
    """Stacked cache for the pp step: {'k': [L, slots, F], 'v': ...} —
    per-layer 2D geometry matching kv_cache.init_cache, stacked on L.
    Quantized configs add stacked [L, slots, Hkv] f32 scale buffers
    (the sibling-buffer discipline of kv_cache.init_cache, stacked)."""
    shape = (cfg.num_layers, cfg.num_slots, cfg.feature_dim)
    cache = {"k": jnp.zeros(shape, cfg.store_dtype),
             "v": jnp.zeros(shape, cfg.store_dtype)}
    if cfg.quantized:
        sshape = (cfg.num_layers, cfg.num_slots, cfg.num_kv_heads)
        cache["k_scale"] = jnp.zeros(sshape, jnp.float32)
        cache["v_scale"] = jnp.zeros(sshape, jnp.float32)
    return cache


def pp_param_pspecs(cfg: ModelConfig) -> Dict:
    """Stacked-params pspecs: layer leaves shard axis 0 over pp; embed /
    norms / head replicated."""
    layer_leaf = P("pp")
    layers = {
        "attn": {"wq": layer_leaf, "wk": layer_leaf, "wv": layer_leaf,
                 "wo": layer_leaf},
        "attn_norm": layer_leaf,
        "mlp_norm": layer_leaf,
        "mlp": {"w_gate": layer_leaf, "w_up": layer_leaf,
                "w_down": layer_leaf},
    }
    specs = {"embed": P(None, None), "final_norm": P(None),
             "layers": layers}
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, None)
    return specs


def pp_cache_pspecs(kv_quant: bool = False) -> Dict:
    """Stacked-cache pspecs: each stage owns its layers' slice of pages
    AND (int8) of their scale buffers — scales never leave their stage."""
    spec = P("pp", None, None)
    out = {"k": spec, "v": spec}
    if kv_quant:
        out["k_scale"] = spec
        out["v_scale"] = spec
    return out


def make_pp_block_ops(block_size: int, mesh: Mesh, kv_quant: bool = False):
    """Whole-block extract/inject for the STACKED pp cache layout — the
    piece that lets pp serving run the tiered prefix cache (VERDICT r4
    next-10; the reference's block manager is universal,
    `block_manager.rs:90`).

    Same canonical block format as kv_cache.make_block_ops
    ([2, L, block_size, F]), so offload/onboard and the transfer planes
    are layout-agnostic: extract gathers the layer-sharded block off the
    pp axis (replicated out — host reads stay collective-free), inject
    scatters it back.

    Quantized caches (ISSUE 12 leg 2) move the SAME packed wire block as
    kv_cache.make_block_ops: [2, L, bs, F + 4·Hkv] int8 with the page's
    [bs, Hkv] f32 scales bitcast into the trailing bytes — so pp peers
    transfer to/from meshless, tp and dp peers byte-identically, and no
    path can ship pages without their scales.
    """
    cache_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                            pp_cache_pspecs(kv_quant))
    rep = NamedSharding(mesh, P())

    def _slice(buf, start):
        return jax.lax.dynamic_slice_in_dim(buf, start, block_size, axis=1)

    def extract(cache: Dict, page) -> jnp.ndarray:
        start = page * block_size
        k = _slice(cache["k"], start)          # [L, bs, F]
        v = _slice(cache["v"], start)
        if not kvc.cache_is_quantized(cache):
            return jnp.stack([k, v])           # [2, L, bs, F]
        ks = _slice(cache["k_scale"], start)   # [L, bs, Hkv] f32
        vs = _slice(cache["v_scale"], start)

        def pack(q, s):
            # f32 [L, bs, Hkv] -> int8 [L, bs, Hkv, 4] -> [L, bs, 4*Hkv]
            sb = jax.lax.bitcast_convert_type(s, jnp.int8)
            sb = sb.reshape(s.shape[0], s.shape[1], -1)
            return jnp.concatenate([q, sb], axis=-1)

        return jnp.stack([pack(k, ks), pack(v, vs)])

    def inject(cache: Dict, page, data) -> Dict:
        start = page * block_size
        upd = jax.lax.dynamic_update_slice_in_dim
        if not kvc.cache_is_quantized(cache):
            data = data.astype(cache["k"].dtype)
            return {
                "k": upd(cache["k"], data[0], start, axis=1),
                "v": upd(cache["v"], data[1], start, axis=1),
            }
        F = cache["k"].shape[-1]
        H = cache["k_scale"].shape[-1]
        data = data.astype(jnp.int8)  # packed wire block (validated host-side)

        def unpack(d):  # [L, bs, F+4H] -> (int8 [L, bs, F], f32 [L, bs, H])
            q = d[..., :F]
            sb = d[..., F:].reshape(d.shape[0], d.shape[1], H, 4)
            return q, jax.lax.bitcast_convert_type(sb, jnp.float32)

        kq, ks = unpack(data[0])
        vq, vs = unpack(data[1])
        return {
            "k": upd(cache["k"], kq, start, axis=1),
            "v": upd(cache["v"], vq, start, axis=1),
            "k_scale": upd(cache["k_scale"], ks, start, axis=1),
            "v_scale": upd(cache["v_scale"], vs, start, axis=1),
        }

    ex = jax.jit(extract, in_shardings=(cache_sh, rep), out_shardings=rep)
    inj = jax.jit(inject, in_shardings=(cache_sh, rep, rep),
                  out_shardings=cache_sh, donate_argnums=(0,))
    return ex, inj


def _validate_pp(cfg: ModelConfig, mesh: Mesh) -> int:
    """Shared pp-plane validation; returns the stage count S."""
    cfg.validate()
    if cfg.is_moe:
        raise ValueError("pp v1 supports dense models only")
    if cfg.post_norms:
        raise ValueError("pp v1 does not wire Gemma-style post-norms")
    S = mesh.shape["pp"]
    if cfg.num_layers % S != 0:
        raise ValueError(f"pp={S} must divide num_layers={cfg.num_layers}")
    for axis in ("dp", "sp", "ep", "tp"):
        if mesh.shape[axis] != 1:
            # The shard_map specs mention only pp: any other populated
            # axis would silently replicate the whole stage compute —
            # wasted chips, which make_mesh treats as a provisioning bug.
            raise ValueError(
                f"pp v1 composes with no other axis in-mesh (got "
                f"{axis}={mesh.shape[axis]}); run dp via engine replicas")
    return S


def _pp_schedule(cfg: ModelConfig, block_size: int, S: int, M: int,
                 quant: bool):
    """ONE tick schedule body, shared by the plain step, the fused
    greedy step and the decode window (the refactor that makes fused pp
    decode a 10-line wrapper instead of a fork).

    Returns `step(params, cache, tokens, positions, seq_lens,
    block_tables, sample_positions) -> (logits, cache)`, traced INSIDE a
    shard_map over the pp axis.  `cache` is the stacked dict (with scale
    buffers when `quant`); one compiled tick body runs inside fori_loop —
    the schedule's length (S + M − 1 ticks) must not scale program
    size/compile time, so all per-tick variation (inject? bank?) is
    traced masking.
    """
    from dynamo_tpu.models import llama

    def step(params, cache, tokens, positions, seq_lens, block_tables,
             sample_positions):
        B, T = tokens.shape
        if B % M:
            raise ValueError(f"batch {B} not divisible by {M} microbatches")
        mb = B // M
        Pw = block_tables.shape[1]
        C = Pw * block_size
        stage = jax.lax.axis_index("pp")
        last_stage = S - 1
        layers = params["layers"]  # stacked, local shard [L/S, ...]
        leaves = ("k", "v") + (("k_scale", "v_scale") if quant else ())
        caches = tuple(cache[n] for n in leaves)  # each [L/S, slots, ...]

        def stage_compute(x, meta, caches, valid):
            """Run this stage's layers on one microbatch activation.
            `valid` (traced bool): whether this (stage, tick) holds a real
            microbatch — bubble ticks compute uniformly but their cache
            writes are redirected to the null block (slot 0), because the
            rotated-in metadata can point at REAL pages of a previous
            microbatch (the M=2 drain tick corrupted mb1's cache before
            this mask existed)."""
            positions_mb, seq_lens_mb, bt_mb = meta
            write_slots = kvc.slots_for_positions(
                bt_mb, positions_mb, block_size).reshape(mb * T)
            write_slots = jnp.where(valid, write_slots, 0)
            ctx_positions = jnp.broadcast_to(
                jnp.arange(C, dtype=jnp.int32), (mb, C))
            ctx_slots = kvc.slots_for_positions(bt_mb, ctx_positions,
                                                block_size)

            mixers = llama.chunk_mixers(
                cfg, block_size, positions_mb, seq_lens_mb, bt_mb,
                write_slots, ctx_slots, ctx_positions)

            def layer_fn(x, scanned):
                # One layer of the stack is a model of one layer: the
                # model's own walk, over this layer's cache buffers.
                layer, *bufs = scanned
                opened = llama.OpenCache(
                    cfg, {n: [buf] for n, buf in zip(leaves, bufs)})
                x, _ = next(llama.walk_layers(cfg, [layer], x, opened,
                                              mixers))
                new = opened.close()
                return x, tuple(new[n][0] for n in leaves)

            x, new_caches = jax.lax.scan(layer_fn, x, (layers,) + caches)
            return x, new_caches

        def microbatch(i, arr):
            return jax.lax.dynamic_slice_in_dim(arr, i * mb, mb, axis=0)

        perm = [(i, (i + 1) % S) for i in range(S)]
        H = cfg.hidden_size
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T

        def tick(t, carry):
            x, meta, sample_mb, out, caches = carry

            # Stage 0 swaps in microbatch t's fresh embedding while any
            # remain; every stage computes the candidate uniformly and
            # `where`-selects — branch-free across stages and ticks.
            t_inj = jnp.minimum(t, M - 1)
            fresh_x = jnp.take(params["embed"], microbatch(t_inj, tokens),
                               axis=0)
            fresh_meta = (microbatch(t_inj, positions),
                          microbatch(t_inj, seq_lens),
                          microbatch(t_inj, block_tables))
            fresh_sample = microbatch(t_inj, sample_positions)
            inject = jnp.logical_and(stage == 0, t < M)
            x = jnp.where(inject, fresh_x, x)
            meta = tuple(jnp.where(inject, f, m)
                         for f, m in zip(fresh_meta, meta))
            sample_mb = jnp.where(inject, fresh_sample, sample_mb)

            valid = jnp.logical_and(t - stage >= 0, t - stage < M)
            x, caches = stage_compute(x, meta, caches, valid)

            # Last stage banks its finished microbatch's logits.
            idx = t - (S - 1)
            bank = jnp.logical_and(stage == last_stage, idx >= 0)
            idx_c = jnp.clip(idx, 0, M - 1)
            hfin = llama.rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
            hsel = jnp.take_along_axis(
                hfin, sample_mb[:, None, None].astype(jnp.int32),
                axis=1)[:, 0]
            logits_mb = (hsel @ head).astype(jnp.float32)
            out = out.at[idx_c].set(
                jnp.where(bank, logits_mb, out[idx_c]))

            x = jax.lax.ppermute(x, "pp", perm)
            meta = tuple(jax.lax.ppermute(m, "pp", perm) for m in meta)
            sample_mb = jax.lax.ppermute(sample_mb, "pp", perm)
            return x, meta, sample_mb, out, caches

        carry = (
            jnp.zeros((mb, T, H), params["embed"].dtype),
            (jnp.zeros((mb, T), jnp.int32), jnp.zeros((mb,), jnp.int32),
             jnp.zeros((mb, Pw), jnp.int32)),
            jnp.zeros((mb,), jnp.int32),
            jnp.zeros((M, mb, cfg.vocab_size), jnp.float32),
            caches,
        )
        _, _, _, out, caches = jax.lax.fori_loop(
            0, S + M - 1, tick, carry)

        # Only the last stage wrote non-zero logits: psum replicates them.
        logits = jax.lax.psum(out, "pp").reshape(M * mb, cfg.vocab_size)
        return logits, dict(zip(leaves, caches))

    return step


def _pp_in_specs(cfg: ModelConfig, kv_quant: bool) -> Tuple:
    """in_specs shared by every pp step variant: stacked params + cache,
    replicated batch inputs."""
    return (pp_param_pspecs(cfg), pp_cache_pspecs(kv_quant),
            P(None, None), P(None, None), P(None), P(None, None),
            P(None))


def make_pp_step(cfg: ModelConfig, block_size: int, mesh: Mesh,
                 n_microbatches: int, kv_quant: bool = False):
    """Jit the pipeline-parallel unified step.

    Returns `step(params_stacked, cache, tokens, positions, seq_lens,
    block_tables, sample_positions) -> (logits, cache)` — the regular
    step contract; tokens [B, T] with B divisible by n_microbatches.
    Build inputs with `stack_layer_params` / `init_pp_cache`.
    """
    S = _validate_pp(cfg, mesh)
    body = _pp_schedule(cfg, block_size, S, n_microbatches, kv_quant)
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=_pp_in_specs(cfg, kv_quant),
        out_specs=(P(None, None), pp_cache_pspecs(kv_quant)),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(1,))


def make_pp_greedy_step(cfg: ModelConfig, block_size: int, mesh: Mesh,
                        n_microbatches: int, kv_quant: bool = False):
    """Jit the FUSED greedy pp single step — the all-in-one stage
    program (ISSUE 12 leg 3): schedule + on-device argmax compile into
    ONE donated-cache dispatch returning [B] int32 tokens.  The unfused
    pp decode loop was a schedule dispatch returning [B, V] f32 logits
    plus host-side argmax per token — the pp half of the r5 single-step
    cliff; here steady pp decode costs 1 dispatch + 1 tiny host sync
    (counters pinned in tests/test_compose_matrix.py).

    Same signature as the meshless `EngineCore._greedy_step_fn`:
    `fused(params, cache, tokens[B,1], positions[B,1], seq_lens[B],
    block_tables[B,P], sample_positions[B]) -> (tokens[B], cache)`.
    """
    S = _validate_pp(cfg, mesh)
    body = _pp_schedule(cfg, block_size, S, n_microbatches, kv_quant)

    def fused(params, cache, tokens, positions, seq_lens, block_tables,
              sample_positions):
        logits, cache = body(params, cache, tokens, positions, seq_lens,
                             block_tables, sample_positions)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    sharded = jax.shard_map(
        fused,
        mesh=mesh,
        in_specs=_pp_in_specs(cfg, kv_quant),
        out_specs=(P(None), pp_cache_pspecs(kv_quant)),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(1,))


def make_pp_decode_window(cfg: ModelConfig, block_size: int, mesh: Mesh,
                          n_microbatches: int, window: int,
                          greedy_only: bool = False,
                          kv_quant: bool = False):
    """Jit the fused K-token decode window OVER the pipeline schedule:
    K schedule passes run inside one `lax.fori_loop` with sampled tokens
    fed back on device — llama.make_decode_window's exact run()
    contract, so the engine's pipelined window path (device-resident row
    state, async token fetch) serves pp meshes unchanged.

    Sampling runs replicated inside the shard_map (logits are psum'd
    across stages), so every stage derives identical tokens — the same
    argument that makes the schedule SPMD-safe makes the window so.
    """
    from dynamo_tpu.engine.sampling import sample

    S = _validate_pp(cfg, mesh)
    body = _pp_schedule(cfg, block_size, S, n_microbatches, kv_quant)

    def run(params, cache, last_tokens, positions0, seq_lens0,
            block_tables, temp, top_k, top_p, base_key_data, key_offsets):
        B = last_tokens.shape[0]
        zero_pos = jnp.zeros((B,), jnp.int32)
        base_keys = (None if greedy_only
                     else jax.random.wrap_key_data(base_key_data))
        # Padding rows (seq_lens0 == 0) stay dead across device-side
        # advances — same discipline as make_decode_window.
        live = seq_lens0 > 0

        def wbody(i, carry):
            cache, toks, out = carry
            adv = jnp.where(live, i, 0)
            logits, cache = body(
                params, cache, toks[:, None],
                (positions0 + adv)[:, None], seq_lens0 + adv,
                block_tables, zero_pos)
            if greedy_only:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                keys = jax.vmap(jax.random.fold_in)(base_keys,
                                                    key_offsets + i)
                nxt = sample(logits, temp, top_k, top_p, keys)
            return cache, nxt, out.at[i].set(nxt)

        out0 = jnp.zeros((window, B), jnp.int32)
        cache, _, out = jax.lax.fori_loop(
            0, window, wbody, (cache, last_tokens, out0))
        adv = jnp.where(live, window, 0)
        return (cache, out, positions0 + adv, seq_lens0 + adv,
                key_offsets + window)

    rep = P(None)
    sharded = jax.shard_map(
        run,
        mesh=mesh,
        in_specs=(pp_param_pspecs(cfg), pp_cache_pspecs(kv_quant),
                  rep, rep, rep, P(None, None), rep, rep, rep,
                  P(None, None), rep),
        out_specs=(pp_cache_pspecs(kv_quant), P(None, None), rep, rep,
                   rep),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=(1,))
