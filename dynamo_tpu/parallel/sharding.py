"""GSPMD sharding rules for the Llama-family engine.

Megatron-style tensor parallelism expressed as PartitionSpecs; XLA inserts
the collectives (reference counterpart: NCCL inside vLLM — SURVEY.md §2.6
"Collectives (in-engine)"):

- attention: wq/wk/wv column-parallel (heads over tp), wo row-parallel
  (psum on exit); the KV cache shards its head axis over tp so cache
  reads/writes stay device-local.
- MLP: w_gate/w_up column-parallel, w_down row-parallel.
- MoE: expert dimension over ep, each expert's MLP additionally tp-sharded.
- embedding / lm_head: vocab-sharded over tp (logit psum/all-gather at the
  end of the step).
- activations/batch: sharded over dp.

GQA note: `num_kv_heads` (8 for Llama-3) bounds head-sharded tp for the
cache; tp degrees beyond that would need head replication — rejected here
rather than silently replicated.
"""

from __future__ import annotations

from dataclasses import dataclass, replace as _dc_replace
from typing import Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.models.config import ModelConfig

Params = Dict


# ---------------------------------------------------------------------------
# Declarative plane spec + capability table (ISSUE 12 tentpole)


@dataclass(frozen=True)
class PlaneSpec:
    """Declarative spec of one compiled serving plane.

    The per-combo `make_sharded_{step,window,greedy,embed,mm}_step`
    family collapsed into ONE `make_sharded_step(cfg, block, mesh,
    plane)` builder parameterized by this spec — the feature-composition
    matrix is now a value, not a code grid:

    - `quant`: int8 KV cache — the cache pytree carries sibling
      `[S, Hkv]` f32 scale buffers that shard with their kv heads
      (or slots, under dp_attention) and every attention body
      dequantizes shard-locally (ring hops included).
    - `spec`: speculative verify chunks (T = K+1 decode with
      all-positions logits) will ride this engine's step.
    - `fused`: on-device argmax fused into the program — [B] int32
      tokens out instead of [B, V] f32 logits (the single-step-cliff
      killer).
    - `window`: fused K-token decode window (0/1 = single-step program).
    - `greedy_only`: argmax-only window variant (no sort, no keys).
    - `use_pallas`: route decode attention through the Pallas paged
      kernel inside shard_map.
    - `dp_attention` / `dp_local`: batch-sharded attention with
      slot-sharded KV, optionally with page locality.
    - `moe`: the model has expert layers.  MoE composes with the decode
      window, the fused greedy step, int8 KV and packed prefill (ISSUE
      17 killed those exclusions); the genuinely-impossible combos
      (moe × pp stacked layout, moe × ring-SP) are declared in
      `plane_capability`, not hand-gated in the engine.
    - `role`: "decode" (the unified step family), "embed"
      (return_hidden), "mm" (input-embeds prefill), "sp_prefill"
      (ring-SP whole-prompt prefill).
    """

    quant: bool = False
    spec: bool = False
    fused: bool = False
    window: int = 0
    greedy_only: bool = False
    use_pallas: bool = False
    dp_attention: bool = False
    dp_local: bool = False
    moe: bool = False
    role: str = "decode"


@dataclass(frozen=True)
class Capability:
    ok: bool
    reason: Optional[str] = None


def plane_capability(mesh: Optional[Mesh], plane: PlaneSpec,
                     multihost: Optional[bool] = None) -> Capability:
    """THE capability table: every genuinely-impossible (feature x mesh)
    combination is declared HERE, with the pointed error serving code
    raises — the engine's gating, the README matrix Notes, and the
    composition grid test all read this one function instead of
    hand-maintained combo lists.  `mesh=None` is the meshless engine;
    `multihost` overrides process-span detection so tests can query
    lockstep combos without building a multi-process mesh."""
    pp = mesh is not None and mesh.shape.get("pp", 1) > 1
    if multihost is None:
        from dynamo_tpu.parallel.multihost import mesh_spans_processes

        multihost = mesh is not None and mesh_spans_processes(mesh)

    def no(reason: str) -> Capability:
        return Capability(False, reason)

    if plane.dp_local and not plane.dp_attention:
        return no("dp_local implies dp_attention")
    if (plane.dp_attention or plane.dp_local) and mesh is None:
        return no("dp_attention needs a mesh")
    if plane.use_pallas and plane.dp_attention and not plane.dp_local:
        return no(
            "pallas decode under dp_attention needs page locality "
            "(dp_attention_local=True): without it a row's pages may "
            "live on any shard and the kernel's slot indexing cannot "
            "cross chips — set dp_attention_local (plain allocator) or "
            "drop use_pallas_decode for the gather path")
    if plane.use_pallas and pp:
        return no(
            "pallas paged decode is not wired into the pp stage scan "
            "(the schedule attends gathered context inside each stage); "
            "drop use_pallas_decode (auto keeps pp on the gather path) "
            "or --pp")
    if plane.use_pallas and multihost:
        return no(
            "pallas paged decode under a multi-process mesh is not "
            "audited for the lockstep stream (shard_map custom calls "
            "across processes are unvalidated); drop use_pallas_decode "
            "— auto keeps multihost on the gather path")
    if pp and multihost:
        return no("pipeline parallelism under a multi-process mesh is "
                  "not wired yet (multihost v2 covers tp/dp/dp-attention "
                  "with int8 and fused steps)")
    if plane.moe:
        if pp:
            return no(
                "MoE on the pp engine is declared impossible: the stage "
                "scan stacks per-stage layer weights into one batched "
                "pytree and its body has no expert branch (router / "
                "grouped / dispatch all need per-layer expert weights); "
                "serve MoE models on a tp/ep/dp mesh or drop --pp")
        if plane.role == "sp_prefill":
            return no(
                "ring-SP prefill is declared impossible for MoE: the sp "
                "step shards the TOKEN axis around the ICI ring while "
                "expert dispatch shards tokens over dp×ep — the two "
                "chunkings conflict; MoE prefill rides the padded or "
                "packed plane")
    if plane.spec:
        if pp:
            return no(
                "speculative decode on the pp engine is declared "
                "impossible: the stage program banks ONE sampled row "
                "per microbatch, and the T=K+1 verify chunk needs "
                "all-positions logits; drop --spec-decode or --pp")
        if multihost:
            return no(
                "speculative decode under a multi-process mesh is "
                "loudly versioned out of the audited lockstep stream "
                "(the host-side verify jit carries no multihost "
                "shardings); drop --spec-decode or run single-process")
    if plane.role == "embed":
        if pp:
            return no("embeddings are not wired for the pp engine "
                      "(pipeline stages have no return_hidden path)")
        if multihost:
            return no("embeddings are not wired for multihost (the "
                      "embed route isn't in the lockstep command "
                      "stream)")
    if plane.role == "mm":
        if pp:
            return no("prompt_embeds (multimodal) on the pp engine is "
                      "not wired (stage step has no input-embeds "
                      "variant)")
        if multihost:
            return no("prompt_embeds (multimodal) under a multi-process "
                      "mesh is not in the lockstep command stream yet")
    if plane.role == "sp_prefill" and plane.dp_attention:
        return no("ring-SP prefill is not wired for dp_attention (the "
                  "sp step's cache specs conflict with slot sharding)")
    return Capability(True)


def check_plane(mesh: Optional[Mesh], plane: PlaneSpec,
                multihost: Optional[bool] = None) -> None:
    """Raise the capability table's pointed error for impossible combos."""
    cap = plane_capability(mesh, plane, multihost)
    if not cap.ok:
        raise ValueError(cap.reason)


def param_pspecs(cfg: ModelConfig, moe_mode: str = "dense",
                 dp_attention: bool = False) -> Params:
    """PartitionSpec pytree matching `llama.init_params` structure.

    MoE weights: dense mode shards each expert's MLP over tp too (the
    dense einsums partition fine under GSPMD); dispatch mode shards the
    expert dim over ep AND each expert's intermediate dim over tp (the
    shard_map body computes a partial down projection per tp member and
    psums — ops/moe.py `_dispatch_one_shard` tp_axis) and replicates the
    router (every shard routes its own tokens).

    `dp_attention` (reference: sglang --enable-dp-attention,
    `disagg_dp_attn.sh:33-37`): attention runs data-parallel over the
    batch with REPLICATED attention weights while MLPs stay
    tensor-parallel — the mode for models whose kv-head count is below
    the tp degree (head-sharded KV would cap tp or duplicate KV)."""
    if dp_attention:
        attn = {
            "wq": P(None, None),
            "wk": P(None, None),
            "wv": P(None, None),
            "wo": P(None, None),
        }
    else:
        attn = {
            "wq": P(None, "tp"),
            "wk": P(None, "tp"),
            "wv": P(None, "tp"),
            "wo": P("tp", None),
        }
    layer = {
        "attn": attn,
        "attn_norm": P(None),
        "mlp_norm": P(None),
    }
    if cfg.post_norms:
        layer["post_attn_norm"] = P(None)
        layer["post_mlp_norm"] = P(None)
    if cfg.is_moe:
        if moe_mode == "dispatch":
            layer["moe"] = {
                "router": P(None, None),
                "w_gate": P("ep", None, "tp"),
                "w_up": P("ep", None, "tp"),
                "w_down": P("ep", "tp", None),
            }
        else:
            layer["moe"] = {
                "router": P(None, "ep"),
                "w_gate": P("ep", None, "tp"),
                "w_up": P("ep", None, "tp"),
                "w_down": P("ep", "tp", None),
            }
    else:
        layer["mlp"] = {
            "w_gate": P(None, "tp"),
            "w_up": P(None, "tp"),
            "w_down": P("tp", None),
        }
    specs: Params = {
        "embed": P("tp", None),
        "final_norm": P(None),
        "layers": [layer] * cfg.num_layers,
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "tp")
    return specs


def cache_pspecs(num_layers: int, dp_attention: bool = False,
                 dp_local: bool = False, kv_quant: bool = False) -> Dict:
    """KV cache: per-layer [slots, F = kv_heads * head_dim] buffers; the
    flat feature axis shards over tp, which IS head sharding (F is
    head-major and validate() enforces tp | num_kv_heads).

    The slot axis is deliberately *not* dp-sharded: each dp replica runs its
    own engine process with its own cache (serving-style DP, reference
    PushRouter replicas), so within one process the cache only shards over
    tp.

    `dp_attention`: the SLOT axis shards over tp instead of heads — total
    KV memory still splits tp-ways, but head count no longer caps tp.
    GSPMD resolves page→device movement with collectives.

    `dp_local` (implies dp_attention): slots shard over the FLAT (dp, tp)
    device grid and the engine's locality-aware allocator guarantees a
    row's pages live on that row's device — decode attention then runs
    fully device-local under shard_map (llama._dp_local_attention), no cross-chip gathers per step (VERDICT r3 weak #4).

    `kv_quant` (ISSUE 9): the int8 cache's sibling per-layer [S, Hkv] f32
    scale buffers SHARD WITH THEIR KV HEADS — head-sharded tp splits the
    Hkv axis exactly as the F axis splits (F is head-major and tp | Hkv),
    so every shard dequantizes its own heads with locally-resident
    scales; slot-sharded modes (dp_attention / dp_local) shard the scale
    slot axis like the page slot axis.  Scales are never replicated:
    a replicated [S, Hkv] f32 buffer would cost more HBM per chip than
    the int8 quantization saves at small head_dim."""
    if dp_local:
        spec = P(("dp", "tp"), None)
        sspec = P(("dp", "tp"), None)
    elif dp_attention:
        spec = P("tp", None)
        sspec = P("tp", None)
    else:
        spec = P(None, "tp")
        sspec = P(None, "tp")   # Hkv axis: scales ride their heads
    out = {"k": [spec] * num_layers, "v": [spec] * num_layers}
    if kv_quant:
        out["k_scale"] = [sspec] * num_layers
        out["v_scale"] = [sspec] * num_layers
    return out


def data_pspecs() -> Dict:
    """Per-step input batch: batch dim over dp."""
    return {
        "tokens": P("dp", None),
        "positions": P("dp", None),
        "seq_lens": P("dp"),
        "block_tables": P("dp", None),
    }


def validate(cfg: ModelConfig, mesh: Mesh,
             dp_attention: bool = False) -> None:
    tp = mesh.shape["tp"]
    ep = mesh.shape["ep"]
    if not dp_attention and cfg.num_kv_heads % tp:
        raise ValueError(
            f"tp={tp} must divide num_kv_heads={cfg.num_kv_heads} "
            "(head-sharded KV cache; use dp_attention for tp beyond the "
            "kv-head count)"
        )
    if cfg.intermediate_size % tp:
        raise ValueError(f"tp={tp} must divide intermediate={cfg.intermediate_size}")
    if cfg.vocab_size % tp:
        raise ValueError(f"tp={tp} must divide vocab={cfg.vocab_size}")
    if cfg.is_moe and cfg.num_experts % ep:
        raise ValueError(f"ep={ep} must divide num_experts={cfg.num_experts}")
    if not cfg.is_moe and ep > 1:
        raise ValueError("ep > 1 on a dense model wastes chips; use tp/dp")


def shard_pytree(tree, pspecs, mesh: Mesh):
    """Place a pytree on the mesh according to a matching pspec pytree.

    Under a multi-process mesh, host leaves become GLOBAL arrays via
    make_array_from_callback (each process serves its addressable shards
    from identical host bytes — plain device_put would commit to one
    process's devices)."""
    from dynamo_tpu.parallel.multihost import mesh_spans_processes, to_global

    if mesh_spans_processes(mesh):
        import numpy as _np

        return jax.tree.map(
            lambda x, s: to_global(_np.asarray(x), NamedSharding(mesh, s)),
            tree, pspecs)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, pspecs
    )


def init_on_mesh(make, pspecs, mesh: Mesh):
    """Build `make()`'s pytree ALREADY sharded per `pspecs`: the
    initialiser is jitted with `out_shardings`, so each device only ever
    materialises its own shard.  (Eager init + `shard_pytree` commits
    the whole tree to device 0 first — llama-3-8b's 16 GB of bf16
    weights do not fit one 16 GB chip, though tp4 shards do.)

    Multi-process meshes keep the host-bytes path of `shard_pytree`."""
    from dynamo_tpu.parallel.multihost import mesh_spans_processes

    if mesh_spans_processes(mesh):
        return shard_pytree(make(), pspecs, mesh)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    return jax.jit(make, out_shardings=shardings)()


def _finalize(fn, in_shardings, mesh: Mesh):
    """Multihost-aware jit wrapper: when the mesh spans processes, host
    (numpy / process-local) inputs are converted to global arrays per the
    in_shardings tree before the call; single-process meshes return the
    jit untouched (zero overhead on the tuned serving path)."""
    from dynamo_tpu.parallel.multihost import (
        mesh_spans_processes, wrap_global_inputs)

    if mesh_spans_processes(mesh):
        return wrap_global_inputs(fn, in_shardings)
    return fn


def resolve_moe_mode(cfg: ModelConfig, mesh: Optional[Mesh],
                     moe_mode: str = "auto") -> str:
    """The MoE mode ladder: dense | grouped | dispatch.

    - "dense": exact dense compute, every expert over every token with
      zero gates — the oracle, and the GSPMD fallback (tp shards the
      expert einsums fine).  E/k× the minimal FLOPs and weight bytes.
    - "grouped": the MESHLESS fast path — tokens sorted by expert on
      device, one ragged grouped GEMM streams each active expert's
      weights HBM→VMEM once (ops/pallas/moe_grouped.py).
    - "dispatch": all-to-all token dispatch over the mesh's ep axis;
      ep × tp meshes additionally tp-shard each expert's MLP on the
      intermediate dim (psum on exit — ops/moe.py tp_axis), so tp > 1
      no longer blocks dispatch.

    'auto': meshless → "grouped" when the backend is TPU and the expert
    geometry passes `moe_grouped_geometry_ok`, else "dense"; sharded →
    "dispatch" when an ep axis > 1 exists, else "dense"."""
    if not cfg.is_moe:
        return "dense"
    valid = ("auto", "dense", "grouped", "dispatch")
    if moe_mode not in valid:
        raise ValueError(f"moe_mode={moe_mode!r} not in {valid}")
    if mesh is None:
        if moe_mode == "dispatch":
            raise ValueError(
                "moe_mode='dispatch' needs a mesh with an ep axis (the "
                "all-to-all is an ep collective); meshless engines use "
                "'grouped' (TPU fast path) or 'dense'")
        if moe_mode == "auto":
            from dynamo_tpu.ops.pallas import moe_grouped_geometry_ok

            ok = (jax.default_backend() == "tpu"
                  and moe_grouped_geometry_ok(
                      cfg.moe_latent_size or cfg.hidden_size,
                      cfg.expert_size,
                      jax.numpy.dtype(cfg.dtype).itemsize))
            return "grouped" if ok else "dense"
        return moe_mode
    if moe_mode == "grouped":
        raise ValueError(
            "moe_mode='grouped' is the meshless fast path (the Pallas "
            "grouped GEMM runs whole experts per chip); sharded meshes "
            "use 'dispatch' (ep all-to-all, tp-sharded expert MLPs) or "
            "'dense' (GSPMD einsums)")
    if moe_mode == "auto":
        return "dispatch" if mesh.shape["ep"] > 1 else "dense"
    return moe_mode


def make_sharded_step(cfg: ModelConfig, block_size: int, mesh: Mesh,
                      plane: Optional[PlaneSpec] = None,
                      with_expert_load: bool = False, *,
                      moe_mode: str = "auto",
                      dp_attention: bool = False,
                      use_pallas_decode: bool = False,
                      dp_local: bool = False,
                      kv_quant: bool = False,
                      window: int = 0,
                      greedy_only: bool = False):
    """THE sharded-step builder (ISSUE 12 tentpole): one entry point,
    parameterized by a declarative `PlaneSpec`, for every compiled
    program a sharded engine dispatches — the plain unified step, the
    fused greedy single step, the K-token decode window, the embeddings
    (return_hidden) step, the multimodal (input-embeds) prefill, and the
    ring-SP whole-prompt prefill.  The per-combo
    `make_sharded_{window,greedy,embed,mm,sp_prefill}_step` spellings
    survive as thin wrappers that construct the PlaneSpec.

    Impossible combinations raise the capability table's pointed error
    (`plane_capability`) — ONE place declares them, the engine's gating
    reads the same table, and the composition grid test asserts it.

    Common contract pieces: cache donated (in-place paged update);
    host-read outputs (logits / fused tokens) come back replicated under
    a multi-process mesh so every lockstep process reads locally, and
    host (numpy) inputs are converted to global arrays per in_shardings
    (`_finalize`).  `dp_attention` shards batch over (dp, tp) and the
    cache's slot axis over tp; `quant` carries the int8 cache's sharded
    scale buffers through every plane (ring hops included).

    Pipeline (pp) meshes build their stage programs through
    `parallel.pipeline` (stacked layer/cache layout); this builder
    serves every non-pp mesh.

    Legacy keyword spelling (moe_mode / dp_attention / use_pallas_decode
    / dp_local / kv_quant, and a positional moe_mode string) is still
    accepted and folded into a PlaneSpec.
    """
    import jax.numpy as jnp

    from dynamo_tpu.models.llama import make_decode_window, make_forward_step
    from dynamo_tpu.parallel.multihost import mesh_spans_processes

    if isinstance(plane, str):       # legacy positional moe_mode
        moe_mode, plane = plane, None
    if plane is None:
        plane = PlaneSpec(quant=kv_quant, dp_attention=dp_attention,
                          use_pallas=use_pallas_decode, dp_local=dp_local,
                          window=window, greedy_only=greedy_only)
    # The model decides the moe plane dimension — fold it in here so
    # every caller (engine gates, wrappers, the grid test) queries the
    # capability table with the true spec.
    if plane.moe != cfg.is_moe:
        plane = _dc_replace(plane, moe=cfg.is_moe)
    validate(cfg, mesh, plane.dp_attention)
    check_plane(mesh, plane)
    mh = mesh_spans_processes(mesh)
    # moe × sp_prefill already raised in check_plane, so no dense-forcing
    # special case survives here.
    moe_mode = resolve_moe_mode(cfg, mesh, moe_mode)
    batch_axes = ("dp", "tp") if plane.dp_attention else "dp"

    def nsh(spec):
        return NamedSharding(mesh, spec)

    param_sh = jax.tree.map(
        nsh, param_pspecs(cfg, moe_mode, plane.dp_attention))
    cache_sh = jax.tree.map(
        nsh, cache_pspecs(cfg.num_layers, plane.dp_attention,
                          plane.dp_local, plane.quant))
    b = nsh(P(batch_axes))
    b2 = nsh(P(batch_axes, None))

    def jit_plane(fn, in_shardings, out_shardings):
        return _finalize(jax.jit(fn, in_shardings=in_shardings,
                                 out_shardings=tuple(out_shardings),
                                 donate_argnums=(1,)), in_shardings, mesh)

    if plane.role == "sp_prefill":
        # SEQUENCE-PARALLEL full-prompt prefill: the token axis shards
        # over sp and attention runs on the ICI ring
        # (ops/ring_attention.py).  Contract: the chunk is the WHOLE
        # prompt (positions 0..T-1, no prior cached context); T must
        # divide by sp.  MoE never reaches here (moe × sp_prefill is a
        # capability-table pointed error: token-axis ring sharding
        # conflicts with dp×ep token dispatch).  Quantized caches ride
        # the ring as int8 chunks + scales (llama._attention_write sp
        # branch — ISSUE 12 leg 1).
        # plane.use_pallas routes eligible geometry through the Pallas
        # flash ring kernel (RDMA exchange hidden under the fold); the
        # XLA ppermute ring stays the fallback and the oracle
        # (llama._sp_ring_attention picks per trace).
        step = make_forward_step(cfg, block_size, moe_mode="dense",
                                 mesh=mesh, sp_ring=True,
                                 sp_ring_pallas=plane.use_pallas)
        seq = nsh(P("dp", "sp"))
        in_shardings = (param_sh, cache_sh, seq, seq, nsh(P("dp")),
                        nsh(P("dp", None)), nsh(P("dp")))
        out_shardings = (
            # Logits are host-read (sampling); multihost replicates them
            # so every process can read locally.
            nsh(P(None, None) if mh else P("dp", None)),
            cache_sh,
        )
        return jit_plane(step, in_shardings, out_shardings)

    if plane.role == "embed":
        # return_hidden step (the /v1/embeddings path on a sharded
        # engine — r3 raised NotImplementedError here).
        step = make_forward_step(cfg, block_size, moe_mode=moe_mode,
                                 mesh=mesh, return_hidden=True,
                                 dp_local=plane.dp_local)
        in_shardings = (param_sh, cache_sh, b2, b2, b, b2, b)
        return jit_plane(step, in_shardings, (b2, cache_sh))

    if plane.role == "mm":
        # Multimodal prefill: masked chunk positions take provided
        # [B, T, H] embeddings instead of the token lookup
        # (llm/multimodal.py).  Embeddings shard like activations:
        # batch over the batch axes, H replicated.
        step = make_forward_step(cfg, block_size, moe_mode=moe_mode,
                                 mesh=mesh, with_input_embeds=True,
                                 dp_local=plane.dp_local)
        b3 = nsh(P(batch_axes, None, None))
        in_shardings = (param_sh, cache_sh, b2, b2, b, b2, b, b3, b2)
        out_shardings = (
            nsh(P(None, None) if mh else P(batch_axes, None)), cache_sh)
        return jit_plane(step, in_shardings, out_shardings)

    if plane.window > 0:
        # Fused K-token decode window — the fast decode path for SERVED
        # sharded models (VERDICT r3 weak #3).  Same contract as
        # llama.make_decode_window; MoE models return a sixth output
        # (accumulated expert-load counts through the fori_loop carry).
        # window == 1 still builds the WINDOW program (degenerate
        # single-iteration loop): callers chose the 11-arg run()
        # contract, and silently handing back the 7-arg plain step
        # would TypeError at their first dispatch.
        run = make_decode_window(cfg, block_size, plane.window,
                                 use_pallas_decode=plane.use_pallas,
                                 greedy_only=plane.greedy_only, mesh=mesh,
                                 dp_local=plane.dp_local,
                                 moe_mode=moe_mode,
                                 with_expert_load=cfg.is_moe)
        in_shardings = (param_sh, cache_sh,
                        b,    # last_tokens [B]
                        b,    # positions0 [B]
                        b,    # seq_lens0 [B]
                        b2,   # block_tables [B, P]
                        b,    # temp [B]
                        b,    # top_k [B]
                        b,    # top_p [B]
                        b2,   # base_key_data [B, 2]
                        b)    # key_offsets [B]
        out_shardings = [
            cache_sh,
            # Tokens are the one host-read output: multihost replicates
            # them so the fetch thread can read locally (collectives are
            # illegal off the lockstep thread).
            nsh(P(None, None) if mh else P(None, batch_axes)),
            b,    # positions0 + K
            b,    # seq_lens0 + K
            b,    # key_offsets + K
        ]
        if cfg.is_moe:
            out_shardings.append(nsh(P(None)))  # expert load
        return jit_plane(run, in_shardings, out_shardings)

    # Single-step planes (plain unified step / fused greedy).
    inner = make_forward_step(cfg, block_size, moe_mode=moe_mode, mesh=mesh,
                              with_expert_load=with_expert_load,
                              use_pallas_decode=plane.use_pallas,
                              dp_local=plane.dp_local)
    div = ((mesh.shape["dp"] * mesh.shape["tp"])
           if plane.dp_attention else 1)

    def checked(params, cache, tokens, *rest):
        if tokens.shape[0] % div:
            # Shape check at trace time (batch is static under jit):
            # surfaces a clear error instead of opaque GSPMD padding.
            raise ValueError(
                f"dp_attention: batch {tokens.shape[0]} must be a "
                f"multiple of dp*tp = {div}")
        return inner(params, cache, tokens, *rest)

    step = checked if plane.dp_attention else inner
    in_shardings = (param_sh, cache_sh,
                    b2,   # tokens [B, T]
                    b2,   # positions [B, T]
                    b,    # seq_lens [B]
                    b2,   # block_tables [B, P]
                    b)    # sample_positions [B]

    if plane.fused:
        # FUSED greedy single step: forward + on-device argmax in ONE
        # program with a donated cache, [B] int32 tokens out instead of
        # [B, V] f32 logits (ISSUE 9 leg 3 — the sharded half of the r5
        # single-step cliff; the unfused path was 3 eager dispatches plus
        # a full-vocab output per token).  Multi-process meshes replicate
        # the token output so every lockstep process reads it locally —
        # the fused step IS in the audited command stream (ISSUE 12
        # leg 4).
        def fused(params, cache, tokens, positions, seq_lens,
                  block_tables, sample_positions):
            out = step(params, cache, tokens, positions, seq_lens,
                       block_tables, sample_positions)
            if with_expert_load:
                logits, cache, load = out
                return (jnp.argmax(logits, -1).astype(jnp.int32), cache,
                        load)
            logits, cache = out
            return jnp.argmax(logits, -1).astype(jnp.int32), cache

        out_shardings = [nsh(P(None) if mh else P(batch_axes)), cache_sh]
        if with_expert_load:
            out_shardings.append(nsh(P(None)))
        return jit_plane(fused, in_shardings, out_shardings)

    out_shardings = [
        # Logits are host-read (sampling); multihost replicates them so
        # every process reads locally.
        nsh(P(None, None) if mh else P(batch_axes, None)),
        cache_sh,
    ]
    if with_expert_load:
        out_shardings.append(nsh(P(None)))
    return jit_plane(step, in_shardings, out_shardings)


# -- legacy spellings: thin PlaneSpec wrappers over make_sharded_step ------


def make_sp_prefill_step(cfg: ModelConfig, block_size: int, mesh: Mesh,
                         kv_quant: bool = False,
                         use_pallas: bool = False):
    """Ring-SP whole-prompt prefill (`role="sp_prefill"`): tokens and
    positions shard P(dp, sp); same step signature otherwise.
    `use_pallas` selects the flash ring kernel at eligible geometry
    (ops/pallas/ring_attention.py)."""
    return make_sharded_step(cfg, block_size, mesh,
                             PlaneSpec(role="sp_prefill", quant=kv_quant,
                                       use_pallas=use_pallas))


def make_sharded_window(cfg: ModelConfig, block_size: int, mesh: Mesh,
                        window: int,
                        greedy_only: bool = False,
                        use_pallas_decode: bool = False,
                        dp_attention: bool = False,
                        dp_local: bool = False,
                        kv_quant: bool = False,
                        moe_mode: str = "auto"):
    """Fused K-token decode window (`plane.window=K`); see
    llama.make_decode_window for the run() contract."""
    return make_sharded_step(
        cfg, block_size, mesh,
        PlaneSpec(window=window, greedy_only=greedy_only,
                  use_pallas=use_pallas_decode, dp_attention=dp_attention,
                  dp_local=dp_local, quant=kv_quant),
        moe_mode=moe_mode)


def make_sharded_greedy_step(cfg: ModelConfig, block_size: int, mesh: Mesh,
                             moe_mode: str = "auto",
                             with_expert_load: bool = False,
                             dp_attention: bool = False,
                             use_pallas_decode: bool = False,
                             dp_local: bool = False,
                             kv_quant: bool = False):
    """Fused greedy single step (`plane.fused=True`): forward + argmax in
    one donated-cache program, [B] tokens out."""
    return make_sharded_step(
        cfg, block_size, mesh,
        PlaneSpec(fused=True, use_pallas=use_pallas_decode,
                  dp_attention=dp_attention, dp_local=dp_local,
                  quant=kv_quant),
        with_expert_load, moe_mode=moe_mode)


def make_sharded_embed_step(cfg: ModelConfig, block_size: int, mesh: Mesh,
                            dp_attention: bool = False,
                            dp_local: bool = False,
                            kv_quant: bool = False):
    """return_hidden step (`role="embed"`) — the /v1/embeddings path."""
    return make_sharded_step(
        cfg, block_size, mesh,
        PlaneSpec(role="embed", dp_attention=dp_attention,
                  dp_local=dp_local, quant=kv_quant))


def make_sharded_mm_step(cfg: ModelConfig, block_size: int, mesh: Mesh,
                         dp_attention: bool = False,
                         dp_local: bool = False,
                         kv_quant: bool = False):
    """Multimodal input-embeds prefill (`role="mm"`)."""
    return make_sharded_step(
        cfg, block_size, mesh,
        PlaneSpec(role="mm", dp_attention=dp_attention,
                  dp_local=dp_local, quant=kv_quant))
