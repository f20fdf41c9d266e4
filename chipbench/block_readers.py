"""Arithmetic of the layer metrics of a block-diffusion engine over routed
experts, from what a run already holds: the worker's block and expert
tallies (`dynamo_worker_diffusion_*`, `dynamo_worker_moe_*`) at the scrapes
and the reduced capture.  A program without these series (the parent of the
PR that added them, or an engine that generates no blocks) has nothing to
read: every function returns None and the metric is left out.

The bytes and operations of the block's own kernels are NOT here: each
share of a roofline brings them in its own file under `layer_metrics/`."""

from __future__ import annotations

_FWD = 'dynamo_worker_diffusion_forwards_total{kind="%s"}'


def tally(ctx, name: str, scope: str = "window"):
    """Change of one `dynamo_worker_<name>_total` series between the
    scope's two scrapes, or None."""
    return ctx.delta("worker", f"dynamo_worker_{name}_total", scope)


def forwards(ctx, scope: str = "window"):
    """(denoising forwards, commit forwards) the block programs ran between
    the scope's scrapes; a commit is one block program call."""
    denoise = ctx.delta("worker", _FWD % "denoise", scope)
    commit = ctx.delta("worker", _FWD % "commit", scope)
    if denoise is None or not commit:
        return None
    return denoise, commit


def ratio(ctx, over: str, under: str, scale: float = 1.0):
    """`scale` x change of one tally over the change of another."""
    a, b = tally(ctx, over), tally(ctx, under)
    if a is None or not b:
        return None
    return scale * a / b


def trace_forwards(ctx):
    """Forwards the capture holds, counted from the trace's program calls
    (the counters' edges and the capture's are not the same instants, so
    whatever is read off counters is scaled to these): block program calls
    x the forwards a call ran (counters, capture scope), and prefill calls.
    Returns (block forwards, block calls, prefill calls) or None."""
    if not ctx.trace:
        return None
    fw = forwards(ctx, "capture")
    decode = ctx.trace["roles"].get("decode")
    if fw is None or not decode or decode["calls"] <= 0:
        return None
    prefill = ctx.trace["roles"].get("prefill") or {"calls": 0}
    per_call = (fw[0] + fw[1]) / fw[1]
    return decode["calls"] * per_call, decode["calls"], prefill["calls"]
