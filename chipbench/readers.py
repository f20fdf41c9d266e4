"""Arithmetic shared by the metric readers under `end_to_end/` and
`layer_metrics/`.  Each reader file is a few lines that name its layer, unit,
source and the end-to-end metric it should move, and bind one of these.

`ctx` is what a run measured (see run.py): `records` (the judged requests),
`all_records` (with the lead-in), `t0`, `seconds`, `setup_s`, `scrapes`
(Prometheus pages of frontend and worker at the window's and the capture's
edges), `trace` (trace_reduce's output, traced runs only), `config`, `mix`,
`params`, `peaks`, `mem`, `child` (what serve_child.py found and timed).  A
reader that finds nothing to read returns None and the harness leaves the
metric out."""

from __future__ import annotations

import re

from chipbench import model_bytes, stats


# -- client clock -----------------------------------------------------------

def itl_ms(ctx, q: float):
    """Time between successive streamed events of a request as the client
    received them, pooled over requests.  The frontend sends one event per
    token and the eight tokens of a decode window arrive within a
    millisecond of each other, so seven gaps in eight are near zero and the
    eighth is the window's interval.  A failed request adds one miss."""
    gaps = []
    for r in ctx.records:
        if not r["ok"]:
            gaps.append(stats.MISS)
            continue
        ts = [t for t, _n in r["chunks"]]
        gaps.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))
    return stats.percentile(gaps, q)


def itl_mean_ms(ctx):
    """Mean time per output token after the first, pooled over all tokens
    of the judged requests: the sum of (last token - first token) over the
    sum of (tokens - 1).  No percentile edge to sit on, so it reads the same
    from run to run within a percent."""
    if any(not r["ok"] for r in ctx.records):
        return stats.MISS
    span = sum(r["chunks"][-1][0] - r["chunks"][0][0]
               for r in ctx.records if r["chunks"])
    n = sum(sum(k for _t, k in r["chunks"]) - 1
            for r in ctx.records if r["chunks"])
    return span * 1e3 / n if n > 0 else None


def gen_late_ms(ctx, q: float):
    return stats.percentile([(r["sent"] - r["due"]) * 1e3
                             for r in ctx.records if r["sent"]], q)


# -- program counters -------------------------------------------------------

def _sum_matching(page: dict, pattern: str):
    rx = re.compile(pattern)
    vals = [v for k, v in page.items() if k != "_t" and rx.search(k)]
    return sum(vals) if vals else None


def _engine(ctx, name: str, scope: str = "window"):
    return ctx.delta("worker", f"dynamo_worker_engine_{name}", scope)


def _decode_steps(ctx, scope: str = "window"):
    """Decode steps the engine dispatched between two scrapes: its windows
    times their length plus its single steps."""
    windows = _engine(ctx, "window_dispatches", scope)
    singles = _engine(ctx, "single_step_dispatches", scope)
    if windows is None or singles is None:
        return None
    return windows * ctx.config["assumed"]["decode_window"] + singles


def batch_rows(ctx):
    """Decode tokens emitted per decode step: the mean number of live rows
    a step carried."""
    toks, steps = _engine(ctx, "decode_tokens_emitted"), _decode_steps(ctx)
    if toks is None or not steps:
        return None
    return toks / steps


def kv_pool_used_share(ctx):
    """Blocks of the device's KV pool that live requests hold, as a share
    of the pool, averaged over the scrapes taken inside the window (its
    start, its middle or the capture's edges, its end): a few instants of a
    gauge, so a rough reading.  The pool itself is reserved at start-up and
    is most of `memory_peak_bytes` whatever is live in it."""
    shares = []
    for page in ctx.scrapes.values():
        wk = page.get("worker") or {}
        active = _sum_matching(wk, r"^dynamo_kv_pool_active_blocks\{")
        cap = _sum_matching(wk, r"^dynamo_kv_pool_capacity_blocks\{")
        if active is not None and cap:
            shares.append(100.0 * active / cap)
    return stats.mean(shares)


def warm_programs_s(ctx):
    """Seconds of set-up spent dispatching every program shape the traffic
    can reach once: the sum over the configuration's warm-ups that say they
    dispatch step programs (`STEP_PROGRAMS`; for today's engine decode
    windows, single steps and packed prefills).  With a warm compile cache,
    the time to trace, lower and read each back."""
    parts = [w["seconds"] for w in ctx.child.get("warmups") or []
             if w.get("step_programs")]
    return sum(parts) if parts else None


def host_syncs_per_window(ctx):
    syncs, windows = _engine(ctx, "host_syncs"), _engine(ctx, "window_dispatches")
    if syncs is None or not windows:
        return None
    return syncs / windows


# -- device trace -----------------------------------------------------------

def _role(ctx, role: str):
    if not ctx.trace:
        return None
    r = ctx.trace["roles"].get(role)
    return r if r and r["calls"] > 0 and r["seconds"] > 0 else None


def decode_step_dev_ms(ctx):
    """Device time of the decode programs over the decode steps they ran
    inside the capture (each program's calls x its steps per call, as the
    configuration's `programs` states them)."""
    r = _role(ctx, "decode")
    return None if r is None else r["seconds"] * 1e3 / r["steps"]


def prefill_call_dev_ms(ctx):
    """Device time of one prefill program call (a packed chunk)."""
    r = _role(ctx, "prefill")
    return None if r is None else r["seconds"] * 1e3 / r["calls"]


def prefill_calls_per_s(ctx):
    """Prefill program calls per second of the traced window: how often
    the scheduler lets a prefill chunk ride behind the decode windows."""
    if not ctx.trace or ctx.trace["window_s"] <= 0:
        return None
    r = ctx.trace["roles"].get("prefill")
    return None if r is None else r["calls"] / ctx.trace["window_s"]


def decode_hbm_share(ctx):
    """Bytes the decode steps of the capture had to read -- the weights once
    a step, and the KV the engine's own model says attention swept -- over
    what the chip's HBM could deliver in the device time they took.  In
    percent of the published peak bandwidth."""
    r = _role(ctx, "decode")
    kv = _engine(ctx, "kv_read_bytes_modeled", "capture")
    counted = _decode_steps(ctx, "capture")
    if r is None or kv is None or not counted or not ctx.peaks:
        return None
    # The counter's edges and the capture's are not the same instants:
    # scale the modeled KV bytes to the steps the trace really holds.
    need = model_bytes.weight_bytes_per_step(ctx.config) * r["steps"] \
        + kv * r["steps"] / counted
    return 100.0 * need / (r["seconds"] * ctx.peaks["hbm_bytes_per_s"])


def kernel_share(ctx, label: str):
    if not ctx.trace or ctx.trace["busy_s"] <= 0:
        return None
    s = ctx.trace["kernels_s"].get(label)
    return None if not s else 100.0 * s / ctx.trace["busy_s"]


def device_idle_share(ctx):
    return None if not ctx.trace else 100.0 * ctx.trace["idle_share"]
