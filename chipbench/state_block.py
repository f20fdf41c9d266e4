"""Bytes and operations of the parallel hybrid block (a Mamba-2 state-space
mixer beside grouped-query attention in every layer, then a SwiGLU MLP), as
functions of the configuration's published keys, and the readers of its layer
metrics.  Kept with the benchmark: a share of a roofline is only as good as
the count it divides by, and no PR that claims a gain may move it.

The block (references/ holds its forward): per layer the attention's four
matrices (q H x heads x head_dim, k and v H x kv_heads x head_dim, o), the
mixer (in_proj H x (d_ssm + conv_dim + heads), out_proj d_ssm x H, the
convolution's taps x conv_dim and bias, the gated norm, three vectors of one
float32 a head), the MLP's three H x intermediate_size, two norms.  A
sequence holds, a layer, a float32 scan state [heads, head_dim, d_state] and
the convolution's last taps - 1 inputs in bf16; a decode step reads and
writes both for every live row.

Every reader here returns None, and never raises, where a series, a kernel
label, a scrape or a configuration key is absent: on a program without the
`dynamo_worker_ssm_*` series (the parent of the PR that added them), on a run
without a capture, on another configuration's file."""

from __future__ import annotations

import functools

from chipbench import latent_block, readers

BYTES_PER_PARAM = 2     # bf16 weights, activations, pages, convolution tail
STATE_BYTES = 4         # the scan's state is float32


def quiet(read):
    """`read(ctx)`, or None where what it reads is not there."""
    @functools.wraps(read)
    def safe(ctx):
        try:
            return read(ctx)
        except (KeyError, TypeError, AttributeError, IndexError,
                ZeroDivisionError, ValueError):
            return None
    return safe


def d_ssm(hf: dict) -> int:
    return hf.get("mamba_d_ssm") or int(hf["mamba_expand"]
                                        * hf["hidden_size"])


def conv_dim(hf: dict) -> int:
    return d_ssm(hf) + 2 * hf["mamba_n_groups"] * hf["mamba_d_state"]


def head_dim(hf: dict) -> int:
    return hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]


def mixer_matmul_params(hf: dict) -> int:
    h = hf["hidden_size"]
    return h * (d_ssm(hf) + conv_dim(hf) + hf["mamba_n_heads"]) \
        + d_ssm(hf) * h


def attn_params(hf: dict) -> int:
    h, d = hf["hidden_size"], head_dim(hf)
    return 2 * h * hf["num_attention_heads"] * d \
        + 2 * h * hf["num_key_value_heads"] * d


def layer_matmul_params(hf: dict) -> int:
    """Parameters of one layer's matrices: what a token multiplies by."""
    return attn_params(hf) + mixer_matmul_params(hf) \
        + 3 * hf["hidden_size"] * hf["intermediate_size"]


def weight_bytes_per_step(hf: dict) -> int:
    """Weights a decode step streams: every layer's matrices, convolution,
    norms and head vectors (those three in float32), the final norm, the
    head.  (The embedding's rows a step gathers are a few KB.)"""
    h = hf["hidden_size"]
    small = conv_dim(hf) * (hf["mamba_d_conv"] + 1) + d_ssm(hf) + 2 * h
    per_layer = (layer_matmul_params(hf) + small) * BYTES_PER_PARAM \
        + 3 * hf["mamba_n_heads"] * STATE_BYTES
    return hf["num_hidden_layers"] * per_layer \
        + (h + h * hf["vocab_size"]) * BYTES_PER_PARAM


def scan_state_bytes(hf: dict) -> int:
    """One sequence's scan state in one layer."""
    return hf["mamba_n_heads"] * hf["mamba_d_head"] * hf["mamba_d_state"] \
        * STATE_BYTES


def state_bytes_per_seq(hf: dict) -> int:
    """One sequence's recurrent state over all layers: the scan state and
    the convolution's tail.  25,165,824 + 184,320 at the published widths
    and 6 layers."""
    tail = (hf["mamba_d_conv"] - 1) * conv_dim(hf) * BYTES_PER_PARAM
    return hf["num_hidden_layers"] * (scan_state_bytes(hf) + tail)


def update_operations(hf: dict) -> int:
    """Operations of one row's state update in one layer: the decay, the
    outer product's two multiplies and its add, and the read-out's multiply
    and add, an element of the state."""
    return 6 * hf["mamba_n_heads"] * hf["mamba_d_head"] * hf["mamba_d_state"]


def scan_operations_per_token(hf: dict) -> int:
    """Operations of the chunked scan a token a layer at `mamba_chunk_size`
    Q: the scan chunk's C.B products (2 Q N a group), the masked products
    over the heads' values (2 Q H P), the state's update and its read-out
    (2 H P N each)."""
    q, n = hf["mamba_chunk_size"], hf["mamba_d_state"]
    hp = d_ssm(hf)
    return 2 * q * n * hf["mamba_n_groups"] + 2 * q * hp + 4 * hp * n


def pair_operations(hf: dict) -> int:
    """Operations of one causal (query, context) pair in one layer, all
    heads: the score and the weighted sum over head_dim."""
    return 4 * hf["num_attention_heads"] * head_dim(hf)


def _gauge(ctx, key: str):
    """A gauge of the worker's page, at the last scrape that holds it."""
    for at in ("window_end", "capture_end", "window_mid", "window_start"):
        page = (ctx.scrapes.get(at) or {}).get("worker") or {}
        if key in page:
            return page[key]
    return None


def _per_call_in_capture(ctx, what: str, calls: str):
    """`what` a call, over the calls the engine dispatched while the capture
    ran (its `dynamo_worker_ssm_capture_*` tallies, which move only inside
    a capture).  The capture's scrapes are no such edges: the second comes
    when the profile has been collected, 27 s after the first around a 3 s
    trace, and the rows of those seconds (the drain's among them) are not
    the trace's; a share read off them passed 100 %."""
    n = ctx.delta("worker", f"dynamo_worker_ssm_capture_{calls}_total",
                  "capture")
    total = ctx.delta("worker", f"dynamo_worker_ssm_capture_{what}_total",
                      "capture")
    return total / n if n and total is not None else None


def _row_steps_in_trace(ctx, role):
    """Live rows x decode steps of the steps the trace holds."""
    rows = _per_call_in_capture(ctx, "decode_row_steps", "decode_steps")
    return None if rows is None else role["steps"] * rows


def _scanned_in_trace(ctx, role):
    """Prompt tokens of the prefill calls the trace holds."""
    tokens = _per_call_in_capture(ctx, "prefill_tokens", "prefill_calls")
    return None if tokens is None else role["calls"] * tokens


@quiet
def decode_step_mfu_share(ctx):
    """The whole decode step's share of the peak that binds it, HBM bytes:
    the weights once a step, each live row's recurrent state read and
    written, the pages the engine's own model says attention swept, over
    what the HBM could deliver in the device time the steps took.  (The
    pages, under a hundredth of the bytes, are the one part still read off
    the capture's scrapes and scaled to the trace's steps.)"""
    held = latent_block.decode_in_trace(ctx)
    kv = readers._engine(ctx, "kv_read_bytes_modeled", "capture")
    if held is None or kv is None or not ctx.peaks:
        return None
    role, scale = held
    row_steps = _row_steps_in_trace(ctx, role)
    if row_steps is None:
        return None
    hf = ctx.config
    need = role["steps"] * weight_bytes_per_step(hf) \
        + row_steps * 2 * state_bytes_per_seq(hf) + scale * kv
    return 100.0 * need / (role["seconds"] * ctx.peaks["hbm_bytes_per_s"])


@quiet
def prefill_mfu_share(ctx):
    """The whole prefill chunk's operations over the bf16 peak in the
    device time the chunks took: every prompt token through every layer's
    matrices and the chunked scan, every causal pair through attention.
    (The head runs on one row a segment and is not counted; the pairs, a
    four-hundredth of the operations, are read off the capture's scrapes
    and scaled to the trace's calls.)"""
    held = latent_block.prefill_in_trace(ctx)
    pairs = ctx.delta("worker", "dynamo_worker_prefill_attn_pairs_total",
                      "capture")
    if held is None or pairs is None or not ctx.peaks:
        return None
    role, scale = held
    tokens = _scanned_in_trace(ctx, role)
    if tokens is None:
        return None
    hf = ctx.config
    need = hf["num_hidden_layers"] * (
        tokens * (2 * layer_matmul_params(hf)
                  + scan_operations_per_token(hf))
        + scale * pairs * pair_operations(hf))
    return 100.0 * need / (role["seconds"] * ctx.peaks["bf16_flops_per_s"])


@quiet
def state_update_roofline_share(ctx):
    """The decode step's state update's share of its roofline: each live
    row's scan state once in and once out a layer over the HBM peak (or its
    operations over the bf16 peak, whichever is larger: bytes bind), over
    the kernel's device time."""
    role = readers._role(ctx, "decode")
    seconds = (ctx.trace or {}).get("kernels_s", {}).get("ssm_update")
    if role is None or not seconds or not ctx.peaks:
        return None
    row_steps = _row_steps_in_trace(ctx, role)
    if row_steps is None:
        return None
    hf = ctx.config
    n = row_steps * hf["num_hidden_layers"]
    least = max(n * 2 * scan_state_bytes(hf) / ctx.peaks["hbm_bytes_per_s"],
                n * update_operations(hf) / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least / seconds


@quiet
def chunk_scan_roofline_share(ctx):
    """The prefill chunk's chunked scan's share of its roofline: its
    operations (they bind: a token's scan moves a few KB) over the bf16
    peak, over the kernel's device time."""
    role = readers._role(ctx, "prefill")
    seconds = (ctx.trace or {}).get("kernels_s", {}).get("ssm_scan")
    if role is None or not seconds or not ctx.peaks:
        return None
    scanned = _scanned_in_trace(ctx, role)
    if scanned is None:
        return None
    hf = ctx.config
    need = scanned * hf["num_hidden_layers"] * scan_operations_per_token(hf)
    return 100.0 * need / (seconds * ctx.peaks["bf16_flops_per_s"])


@quiet
def state_update_kernel_share(ctx):
    return readers.kernel_share(ctx, "ssm_update")


@quiet
def state_bytes_per_seq_gauge(ctx):
    """Bytes of recurrent state one sequence holds, from the worker's own
    gauge (it guards the state staying float32 and fixed in size)."""
    return _gauge(ctx, "dynamo_ssm_state_bytes_per_slot")


@quiet
def slots_used_share(ctx):
    """State slots live sequences hold, as a share of all, averaged over the
    window's scrapes."""
    shares = []
    for at in ("window_start", "capture_start", "capture_end", "window_mid",
               "window_end"):
        page = (ctx.scrapes.get(at) or {}).get("worker") or {}
        used = page.get('dynamo_ssm_state_slots{state="used"}')
        capacity = page.get('dynamo_ssm_state_slots{state="capacity"}')
        if used is not None and capacity:
            shares.append(100.0 * used / capacity)
    return sum(shares) / len(shares) if shares else None
