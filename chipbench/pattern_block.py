"""Bytes and operations of the pattern block (layers of three kinds by a
pattern: a Mamba-2 mixer, attention without a position term, or routed
experts in a latent space beside a shared expert, of which this chip holds a
range), as functions of the configuration's published keys and its pattern,
and the readers of its layer metrics.  Kept with the benchmark: a share of a
roofline is only as good as the count it divides by, and no PR that claims a
gain may move it.  They read the same work whatever implements it.

The block (references/ holds its forward), a layer by its character of
`hybrid_override_pattern`:
- "M": in_proj H x (2 d + 2 G N + heads) with d = mamba_num_heads x
  mamba_head_dim, out_proj d x H, the convolution's taps x (d + 2 G N) and
  bias, the gated norm d, three float32 vectors a head, the layer's norm.  A
  sequence holds a float32 scan state [heads, head_dim, N] and the
  convolution's last taps - 1 inputs in bf16, read and written by every
  decode step for every live row;
- "*": q and o H x heads x head_dim, k and v H x kv_heads x head_dim;
- "E": the router H x `of` (and its float32 bias), the two latent maps H x
  lat, the shared expert 2 x H x Fs, and the routed experts HELD HERE
  (`routed_experts_held.count`), each 2 x lat x F.  A step streams the held
  experts that got a row, and no other.

Every reader here returns None, and never raises, where a series, a kernel
label, a scrape or a configuration key is absent: on a program without the
`dynamo_worker_moe_capture_*` series (the parent of the PR that added them),
on a run without a capture, on another configuration's file."""

from __future__ import annotations

from chipbench import latent_block, readers, state_block
from chipbench.state_block import quiet

BYTES_PER_PARAM = 2     # bf16 weights, activations, pages, convolution tail
STATE_BYTES = 4         # the scan's state and the router's bias are float32


def kinds(hf: dict) -> dict:
    """Layers of each kind: {"M": n, "*": n, "E": n}."""
    pattern = hf["hybrid_override_pattern"]
    return {k: pattern.count(k) for k in "M*E"}


def d_ssm(hf: dict) -> int:
    return hf["mamba_num_heads"] * hf["mamba_head_dim"]


def conv_dim(hf: dict) -> int:
    return d_ssm(hf) + 2 * hf["n_groups"] * hf["ssm_state_size"]


def mixer_matmul_params(hf: dict) -> int:
    h = hf["hidden_size"]
    return h * (d_ssm(hf) + conv_dim(hf) + hf["mamba_num_heads"]) \
        + d_ssm(hf) * h


def mixer_bytes(hf: dict) -> int:
    small = conv_dim(hf) * (hf["conv_kernel"] + 1) + d_ssm(hf) \
        + hf["hidden_size"]
    return (mixer_matmul_params(hf) + small) * BYTES_PER_PARAM \
        + 3 * hf["mamba_num_heads"] * STATE_BYTES


def attn_matmul_params(hf: dict) -> int:
    h, d = hf["hidden_size"], hf["head_dim"]
    return 2 * h * hf["num_attention_heads"] * d \
        + 2 * h * hf["num_key_value_heads"] * d


def held(hf: dict) -> dict:
    """{first, count, of}: the routed experts this chip holds."""
    return hf.get("routed_experts_held") or {
        "first": 0, "count": hf["n_routed_experts"],
        "of": hf["n_routed_experts"]}


def expert_layer_matmul_params(hf: dict) -> int:
    """An expert layer's matrices every token multiplies by: the router, the
    two latent maps, the shared expert."""
    h = hf["hidden_size"]
    return h * held(hf)["of"] + 2 * h * hf["moe_latent_size"] \
        + 2 * h * hf["moe_shared_expert_intermediate_size"]


def expert_params(hf: dict) -> int:
    """One routed expert: up and down in the latent space, no gate."""
    return 2 * hf["moe_latent_size"] * hf["moe_intermediate_size"]


def expert_bytes(hf: dict) -> int:
    return expert_params(hf) * BYTES_PER_PARAM


def weight_bytes_every_row(hf: dict) -> int:
    """Weights a decode step streams whatever the routing: every mixer,
    attention, and expert layer outside its routed experts, the final norm,
    the head.  1.98 GB at the published widths, 11 layers, 32,768 words."""
    h, n = hf["hidden_size"], kinds(hf)
    return (n["M"] * mixer_bytes(hf)
            + n["*"] * (attn_matmul_params(hf) + h) * BYTES_PER_PARAM
            + n["E"] * ((expert_layer_matmul_params(hf) + h) * BYTES_PER_PARAM
                        + held(hf)["of"] * STATE_BYTES)
            + (h + h * hf["vocab_size"]) * BYTES_PER_PARAM)


def scan_state_bytes(hf: dict) -> int:
    """One sequence's scan state in one state layer."""
    return d_ssm(hf) * hf["ssm_state_size"] * STATE_BYTES


def state_bytes_per_seq(hf: dict) -> int:
    """One sequence's recurrent state over the state layers: 21,278,720 at
    the published widths and 5 state layers."""
    tail = (hf["conv_kernel"] - 1) * conv_dim(hf) * BYTES_PER_PARAM
    return kinds(hf)["M"] * (scan_state_bytes(hf) + tail)


def update_operations(hf: dict) -> int:
    """One row's state update in one layer: the decay, the outer product's
    two multiplies and its add, the read-out's multiply and add, an element
    of the state."""
    return 6 * d_ssm(hf) * hf["ssm_state_size"]


def scan_operations_per_token(hf: dict) -> int:
    """The chunked scan a token a state layer at `chunk_size` Q: the scan
    chunk's C.B products (2 Q N a group), the masked products over the
    heads' values (2 Q d), the state's update and read-out (2 d N each)."""
    q, n = hf["chunk_size"], hf["ssm_state_size"]
    return 2 * q * n * hf["n_groups"] + 2 * q * d_ssm(hf) + 4 * d_ssm(hf) * n


def pair_operations(hf: dict) -> int:
    """One causal (query, context) pair in one attention layer, all heads."""
    return 4 * hf["num_attention_heads"] * hf["head_dim"]


def token_matmul_operations(hf: dict) -> int:
    """One token through every layer's matrices but the routed experts'."""
    n = kinds(hf)
    return 2 * (n["M"] * mixer_matmul_params(hf)
                + n["*"] * attn_matmul_params(hf)
                + n["E"] * expert_layer_matmul_params(hf))


def _capture(ctx, at: str, what: str):
    """A `dynamo_worker_moe_capture_*` tally's rise over the capture (they
    move only for calls dispatched while a device capture runs)."""
    return ctx.delta(
        "worker", f"dynamo_worker_moe_capture_{at}_{what}_total", "capture")


def _per_layer_forward(ctx, at: str):
    """(held experts touched, held experts' assignments) an expert layer
    forward, over the `at` (decode | prefill) calls dispatched inside the
    capture; None where the program has no such series or none ran."""
    n = _capture(ctx, at, "layer_forwards")
    touched = _capture(ctx, at, "experts_touched")
    local = _capture(ctx, at, "local_assignments")
    if not n or touched is None or local is None:
        return None
    return touched / n, local / n


def _expert_need_s(ctx, hf, layer_forwards: float, touched: float,
                   local: float) -> float:
    """The least seconds the held experts' kernel could take for
    `layer_forwards` layer forwards that each touch `touched` experts with
    `local` assignments: the touched experts' weights once and the rows in
    and out over the HBM peak, or the assignments' operations over the bf16
    peak, whichever is larger."""
    lat = hf["moe_latent_size"]
    nbytes = touched * expert_bytes(hf) + 2 * local * lat * BYTES_PER_PARAM
    ops = local * 2 * expert_params(hf)
    return layer_forwards * max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                                ops / ctx.peaks["bf16_flops_per_s"])


@quiet
def decode_step_mfu_share(ctx):
    """The whole decode step's share of the peak that binds it, HBM bytes:
    the weights every row uses once a step, the held experts the step's rows
    touched, each live row's recurrent state read and written, the pages the
    engine's own model says attention swept, over what the HBM could deliver
    in the device time the steps took.  Rows and touched experts are those
    of the calls dispatched inside the capture; the pages alone (a
    thousandth of the bytes) are read off the capture's scrapes and scaled
    to the trace's steps."""
    got = latent_block.decode_in_trace(ctx)
    kv = readers._engine(ctx, "kv_read_bytes_modeled", "capture")
    per = _per_layer_forward(ctx, "decode")
    rows = state_block._per_call_in_capture(ctx, "decode_row_steps", "decode_steps")
    if got is None or kv is None or per is None or rows is None \
            or not ctx.peaks:
        return None
    role, scale = got
    hf = ctx.config
    need = role["steps"] * (
        weight_bytes_every_row(hf)
        + kinds(hf)["E"] * per[0] * expert_bytes(hf)
        + rows * 2 * state_bytes_per_seq(hf)) + scale * kv
    return 100.0 * need / (role["seconds"] * ctx.peaks["hbm_bytes_per_s"])


@quiet
def prefill_mfu_share(ctx):
    """The whole prefill chunk's share of the peak that binds it: its bytes
    (the weights every row uses and the held experts it touched, a call)
    over the HBM peak, or its operations (every prompt token through the
    matrices and the chunked scan, its held assignments through their
    experts, every causal pair through attention) over the bf16 peak,
    whichever is larger, over the device time the chunks took."""
    got = latent_block.prefill_in_trace(ctx)
    pairs = ctx.delta("worker", "dynamo_worker_prefill_attn_pairs_total",
                      "capture")
    per = _per_layer_forward(ctx, "prefill")
    tokens = state_block._per_call_in_capture(ctx, "prefill_tokens", "prefill_calls")
    if got is None or pairs is None or per is None or tokens is None \
            or not ctx.peaks:
        return None
    role, scale = got
    hf, n = ctx.config, kinds(ctx.config)
    nbytes = role["calls"] * (weight_bytes_every_row(hf)
                              + n["E"] * per[0] * expert_bytes(hf))
    ops = role["calls"] * (
        tokens * (token_matmul_operations(hf)
                  + n["M"] * scan_operations_per_token(hf))
        + n["E"] * per[1] * 2 * expert_params(hf)) \
        + scale * pairs * n["*"] * pair_operations(hf)
    least = max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                ops / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least / role["seconds"]


@quiet
def local_expert_roofline_share(ctx):
    """The held experts' grouped kernel's share of its roofline over the
    capture: for the decode steps and the prefill calls the trace holds, the
    touched experts' weights and the rows (or the operations, where they
    bind), over the kernel's device time."""
    seconds = (ctx.trace or {}).get("kernels_s", {}).get("moe_local")
    if not seconds or not ctx.peaks:
        return None
    hf, n_e = ctx.config, kinds(ctx.config)["E"]
    least = 0.0
    for at, role, runs in (
            ("decode", readers._role(ctx, "decode"), "steps"),
            ("prefill", readers._role(ctx, "prefill"), "calls")):
        per = _per_layer_forward(ctx, at)
        if role is None or per is None:
            continue
        least += _expert_need_s(ctx, hf, role[runs] * n_e, *per)
    return 100.0 * least / seconds if least else None


@quiet
def local_expert_kernel_share(ctx):
    return readers.kernel_share(ctx, "moe_local")


@quiet
def local_experts_touched_share(ctx):
    """Held experts that got a row, of the held experts, an expert layer
    forward of the window (decode steps and prefill chunks alike)."""
    touched = ctx.delta("worker", "dynamo_worker_moe_experts_touched_total")
    forwards = ctx.delta("worker", "dynamo_worker_moe_layer_forwards_total")
    if ctx.delta("worker",
                 "dynamo_worker_moe_local_assignments_total") is None:
        return None
    return 100.0 * touched / (forwards * held(ctx.config)["count"])


@quiet
def local_rows_per_touched_expert(ctx):
    local = ctx.delta("worker", "dynamo_worker_moe_local_assignments_total")
    touched = ctx.delta("worker", "dynamo_worker_moe_experts_touched_total")
    return local / touched


@quiet
def local_assignments_share(ctx):
    """Of the (token, expert) pairs the router chose over all the model's
    experts, those whose expert is held here: about count / of where the
    router is as wide as the model (it guards routing over all of them)."""
    local = ctx.delta("worker", "dynamo_worker_moe_local_assignments_total")
    routed = ctx.delta("worker", "dynamo_worker_moe_routed_assignments_total")
    return 100.0 * local / routed


@quiet
def state_update_roofline_share(ctx):
    """The decode step's state update's share of its roofline, counted over
    the state layers: each live row's scan state once in and once out a
    state layer over the HBM peak (or its operations over the bf16 peak,
    whichever is larger), over the kernel's device time."""
    role = readers._role(ctx, "decode")
    seconds = (ctx.trace or {}).get("kernels_s", {}).get("ssm_update")
    rows = state_block._per_call_in_capture(ctx, "decode_row_steps", "decode_steps")
    if role is None or not seconds or rows is None or not ctx.peaks:
        return None
    hf = ctx.config
    n = role["steps"] * rows * kinds(hf)["M"]
    least = max(n * 2 * scan_state_bytes(hf) / ctx.peaks["hbm_bytes_per_s"],
                n * update_operations(hf) / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least / seconds
