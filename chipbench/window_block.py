"""Bytes and operations of the parallel window block (layers of window or full
attention by `layer_types`, attention and routed experts side by side on one
norm, averaged shared experts, of the routed experts a range held here), as
functions of the configuration's published keys, and the readers of its
layer metrics.  Kept with the benchmark: a share of a roofline is only as
good as the count it divides by, and no PR that claims a gain may move it.
They read the same work whatever implements it.

The block (references/ holds its forward), every layer alike but for its
attention's kind:
- the norm's weight H;
- attention: q and o H x heads x head_dim, k and v H x kv_heads x head_dim;
  a decode step reads, a layer, one K and one V row (kv_heads x head_dim
  each) of every position its query sees: all of the context in a
  `full_attention` layer, the last `sliding_window` of it in a
  `sliding_attention` layer;
- the router H x `of`; `num_shared_experts` always-on SwiGLUs 3 x H x F;
  the routed experts HELD HERE (`routed_experts_held.count`), each 3 x H x
  F with F = `intermediate_size`.  A step streams the held experts that got
  a row, and no other;
- the final norm and the tied head, vocab x H.

Every reader here returns None, and never raises, where a series, a kernel
label, a scrape or a configuration key is absent: on a program without the
`dynamo_worker_attn_*` series (the parent of the PR that added them), on a
run without a capture, on another configuration's file."""

from __future__ import annotations

from chipbench import readers, stats
# (held experts touched, held assignments) an expert layer forward inside the
# capture: the `dynamo_worker_moe_capture_*` tallies' one reader.
from chipbench.pattern_block import _per_layer_forward
from chipbench.state_block import quiet

BYTES_PER_PARAM = 2     # bf16 weights, activations and pages


def kinds(hf: dict) -> dict:
    """Layers of each attention kind: {"window": n, "full": n}."""
    types = hf["layer_types"]
    return {"window": types.count("sliding_attention"),
            "full": types.count("full_attention")}


def layers(hf: dict) -> int:
    return len(hf["layer_types"])


def held(hf: dict) -> dict:
    """{first, count, of}: the routed experts this chip holds."""
    return hf.get("routed_experts_held") or {
        "first": 0, "count": hf["num_experts"], "of": hf["num_experts"]}


def attn_matmul_params(hf: dict) -> int:
    h, d = hf["hidden_size"], hf["head_dim"]
    return 2 * h * hf["num_attention_heads"] * d \
        + 2 * h * hf["num_key_value_heads"] * d


def expert_params(hf: dict) -> int:
    """One SwiGLU expert, routed or shared: gate, up and down."""
    return 3 * hf["hidden_size"] * hf["intermediate_size"]


def expert_bytes(hf: dict) -> int:
    """100,663,296 at the published widths."""
    return expert_params(hf) * BYTES_PER_PARAM


def layer_matmul_params(hf: dict) -> int:
    """A layer's matrices every token multiplies by: attention's four, the
    router, the shared experts."""
    return attn_matmul_params(hf) + hf["hidden_size"] * held(hf)["of"] \
        + hf["num_shared_experts"] * expert_params(hf)


def weight_bytes_every_row(hf: dict) -> int:
    """Weights a decode step streams whatever the routing: every layer
    outside its routed experts, the final norm, the tied head.  3.02 GB at
    the published widths, 4 layers, 32,768 words."""
    h = hf["hidden_size"]
    return (layers(hf) * (layer_matmul_params(hf) + h)
            + h + h * hf["vocab_size"]) * BYTES_PER_PARAM


def kv_row_bytes(hf: dict) -> int:
    """One position's K and V rows in one layer: 4,096 B as published."""
    return 2 * hf["num_key_value_heads"] * hf["head_dim"] * BYTES_PER_PARAM


def pair_operations(hf: dict) -> int:
    """One (query, key) pair in one attention layer, all heads: the score's
    and the value's multiply-adds."""
    return 4 * hf["num_attention_heads"] * hf["head_dim"]


def token_matmul_operations(hf: dict) -> int:
    """One token through every layer's matrices but the routed experts'."""
    return 2 * layers(hf) * layer_matmul_params(hf)


def _pairs(ctx, name: str, at: str, kind: str, scope: str):
    return ctx.delta(
        "worker", f'dynamo_worker_attn_{name}_total{{at="{at}",'
        f'kind="{kind}"}}', scope)


def _capture_per_call(ctx, at: str, kind: str):
    """(query, key) pairs x layers of `kind` (or "queries") a decode step or
    a prefill call, over the calls dispatched inside the capture (tallies
    that move only while one runs); None where there is none."""
    calls = ctx.delta(
        "worker", f'dynamo_worker_attn_capture_calls_total{{at="{at}"}}',
        "capture")
    total = _pairs(ctx, "capture_pairs", at, kind, "capture")
    return total / calls if calls and total is not None else None


def _attended(ctx, at: str):
    """Pairs x layers both kinds of attention layer visited a call."""
    window = _capture_per_call(ctx, at, "window")
    full = _capture_per_call(ctx, at, "full")
    return None if window is None or full is None else window + full


@quiet
def decode_step_mfu_share(ctx):
    """The whole decode step's share of the peak that binds it, HBM bytes:
    the weights every row uses once a step, the held experts the step's rows
    touched, and the K and V rows its attention read (a window layer's at
    min(context, window)), over what the HBM could deliver in the device
    time the steps took.  Touched experts and pairs are those of the calls
    dispatched inside the capture."""
    role = readers._role(ctx, "decode")
    per = _per_layer_forward(ctx, "decode")
    pairs = _attended(ctx, "decode")
    if role is None or per is None or pairs is None or not ctx.peaks:
        return None
    hf = ctx.config
    need = role["steps"] * (
        weight_bytes_every_row(hf) + layers(hf) * per[0] * expert_bytes(hf)
        + pairs * kv_row_bytes(hf))
    return 100.0 * need / (role["seconds"] * ctx.peaks["hbm_bytes_per_s"])


@quiet
def prefill_mfu_share(ctx):
    """The whole prefill chunk's share of the peak that binds it: its bytes
    (the weights every row uses and the held experts it touched, a call)
    over the HBM peak, or its operations (every prompt token through the
    matrices, its held assignments through their experts, every (query, key)
    pair through attention, a window layer's inside the window) over the
    bf16 peak, whichever is larger, over the device time the chunks took."""
    role = readers._role(ctx, "prefill")
    per = _per_layer_forward(ctx, "prefill")
    pairs = _attended(ctx, "prefill")
    tokens = _capture_per_call(ctx, "prefill", "queries")
    if role is None or per is None or pairs is None or tokens is None \
            or not ctx.peaks:
        return None
    hf = ctx.config
    nbytes = role["calls"] * (weight_bytes_every_row(hf)
                              + layers(hf) * per[0] * expert_bytes(hf))
    ops = role["calls"] * (
        tokens * token_matmul_operations(hf)
        + layers(hf) * per[1] * 2 * expert_params(hf)
        + pairs * pair_operations(hf))
    least = max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                ops / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least / role["seconds"]


def _attn_kernels_s(ctx, labels):
    found = [(ctx.trace or {}).get("kernels_s", {}).get(k) for k in labels]
    return sum(s for s in found if s) or None


@quiet
def attn_decode_roofline_share(ctx):
    """Both decode attention kernels (the full layers' and the window
    layers') against their roofline over the capture: the K and V rows the
    steps' queries see (or the pairs' operations, where they bind) over the
    two kernels' device time."""
    role = readers._role(ctx, "decode")
    pairs = _attended(ctx, "decode")
    seconds = _attn_kernels_s(ctx, ("attn_decode", "window_attn_decode"))
    if role is None or pairs is None or not seconds or not ctx.peaks:
        return None
    hf = ctx.config
    n = role["steps"] * pairs
    least = max(n * kv_row_bytes(hf) / ctx.peaks["hbm_bytes_per_s"],
                n * pair_operations(hf) / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least / seconds


@quiet
def attn_prefill_roofline_share(ctx):
    """Both prefill attention kernels against their roofline over the
    capture: the chunks' (query, key) pairs' operations over the bf16 peak
    (a key tile serves a hundred queries of sixteen heads: the bytes do not
    bind), over the two kernels' device time."""
    role = readers._role(ctx, "prefill")
    pairs = _attended(ctx, "prefill")
    seconds = _attn_kernels_s(ctx, ("attn_prefill", "window_attn_prefill"))
    if role is None or pairs is None or not seconds or not ctx.peaks:
        return None
    ops = role["calls"] * pairs * pair_operations(ctx.config)
    return 100.0 * ops / ctx.peaks["bf16_flops_per_s"] / seconds


@quiet
def attn_rows_read_share(ctx):
    """(query, key) pairs the attention visited over the window, decode
    steps and prefill chunks, a window layer's counted inside its window,
    over what a model of this depth without a window would have visited:
    100 says the window is a mask over a full read."""
    read = total = 0
    for at in ("decode", "prefill"):
        parts = [_pairs(ctx, "pairs", at, kind, "window")
                 for kind in ("window", "full", "unwindowed")]
        if None in parts:
            return None
        read += parts[0] + parts[1]
        total += parts[2]
    return 100.0 * read / total


@quiet
def window_pages_held_share(ctx):
    """Blocks of the window group that live sequences hold over the blocks
    of the full group they hold (a block of either covers `block_size`
    positions of its layers), averaged over the scrapes inside the window:
    100 says nothing was released behind a window."""
    shares = []
    for page in ctx.scrapes.values():
        wk = page.get("worker") or {}
        used = wk.get('dynamo_kv_window_pool_blocks{state="used"}')
        full = wk.get('dynamo_kv_window_pool_blocks{state="full_used"}')
        if used is not None and full:
            shares.append(100.0 * used / full)
    return stats.mean(shares)


@quiet
def gated_expert_roofline_share(ctx):
    """The held experts' gated three-matrix grouped kernel (label
    `moe_local`) against its roofline over the capture: for the decode
    steps and the prefill calls the trace holds, the touched held experts'
    weights once and the rows in and out over the HBM peak, or the held
    assignments x 6 x H x F operations over the bf16 peak, whichever is
    larger, over the kernel's device time."""
    seconds = (ctx.trace or {}).get("kernels_s", {}).get("moe_local")
    if not seconds or not ctx.peaks:
        return None
    hf = ctx.config
    least = 0.0
    for at, runs in (("decode", "steps"), ("prefill", "calls")):
        role, per = readers._role(ctx, at), _per_layer_forward(ctx, at)
        if role is None or per is None:
            continue
        touched, local = per
        nbytes = touched * expert_bytes(hf) \
            + 2 * local * hf["hidden_size"] * BYTES_PER_PARAM
        ops = local * 2 * expert_params(hf)
        least += role[runs] * layers(hf) * max(
            nbytes / ctx.peaks["hbm_bytes_per_s"],
            ops / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least / seconds if least else None
