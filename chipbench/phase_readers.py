"""Arithmetic of the layer metrics that read the engine thread's phase clock
(`dynamo_worker_engine_phase_seconds_total{phase=...}`), the program-build
accounting (`dynamo_worker_program_build_seconds_total{stage=...}`) and the
labels of the idle gaps, all from what a run already holds: `ctx.scrapes`,
`ctx.delta`, `ctx.trace`.  Nothing here re-reads the capture.

A program without these series (the parent of the PR that added them) has
nothing to read: every function returns None and the metric is left out."""

from __future__ import annotations

import re

from chipbench.readers import _engine

_PHASE = re.compile(
    r'^dynamo_worker_engine_phase_seconds_total\{phase="([^"]+)"\}$')
_ENTRIES = re.compile(
    r'^dynamo_worker_engine_phase_entries_total\{phase="[^"]+"\}$')
_BUILD = 'dynamo_worker_program_build_seconds_total{stage="%s"}'


def phase_deltas(ctx, scope: str = "window"):
    """{phase: seconds the engine thread spent in it between the scope's two
    scrapes}, or None when a scrape or the series is missing.  The worker
    adds the open phase's elapsed part at each scrape, so the values sum to
    the time between the scrapes."""
    start = (ctx.scrapes.get(f"{scope}_start") or {}).get("worker") or {}
    out = {}
    for key in start:
        m = _PHASE.match(key)
        if m:
            d = ctx.delta("worker", key, scope)
            if d is None:
                return None
            out[m.group(1)] = d
    return out or None


def phase_share(ctx, phase: str):
    """Percent of the engine thread's time in the window spent in `phase`."""
    d = phase_deltas(ctx)
    total = sum(d.values()) if d else 0.0
    if not d or phase not in d or total <= 0:
        return None
    return 100.0 * d[phase] / total


def host_ms_per_window(ctx):
    """Milliseconds of host work per decode window: every phase but the
    blocked one (`wait_device`) and the one with nothing to do (`idle`),
    over the windows dispatched."""
    d, windows = phase_deltas(ctx), _engine(ctx, "window_dispatches")
    if not d or not windows:
        return None
    work = sum(v for k, v in d.items() if k not in ("wait_device", "idle"))
    return work * 1e3 / windows


def phase_entries_per_window(ctx):
    """Phase transitions of the engine thread per decode window: what the
    phase clock is charged for (one clock read and two adds each)."""
    start = (ctx.scrapes.get("window_start") or {}).get("worker") or {}
    deltas = [ctx.delta("worker", key) for key in start
              if _ENTRIES.match(key)]
    windows = _engine(ctx, "window_dispatches")
    if not deltas or None in deltas or not windows:
        return None
    return sum(deltas) / windows


def emit_us_per_token(ctx):
    """Microseconds of the token loop per decode token emitted."""
    d, toks = phase_deltas(ctx), _engine(ctx, "decode_tokens_emitted")
    if not d or "emit" not in d or not toks:
        return None
    return d["emit"] * 1e6 / toks


def prefill_tokens_per_window(ctx):
    """Prompt tokens handed to prefill programs per decode window."""
    toks = _engine(ctx, "prefill_tokens_dispatched")
    windows = _engine(ctx, "window_dispatches")
    if toks is None or not windows:
        return None
    return toks / windows


def idle_gap_named_share(ctx):
    """Of the idle seconds the reduction lists (its longest gap labels),
    the percent whose label names an engine phase as what the host did."""
    gaps = (ctx.trace or {}).get("idle_gaps") or []
    total = sum(s for _label, s in gaps)
    if total <= 0:
        return None
    return 100.0 * sum(s for label, s in gaps
                       if "host: engine." in label) / total


def compiles_in_window(ctx):
    """Programs built inside the window that the persistent cache did not
    hold: backend-compile events less cache hits."""
    builds = ctx.delta("worker", "dynamo_worker_program_builds_total")
    hits = ctx.delta("worker", "dynamo_worker_compile_cache_hits_total")
    if builds is None or hits is None:
        return None
    return builds - hits


def build_seconds(ctx, stage: str):
    """Seconds of set-up JAX spent in one stage of building programs, as
    the worker had summed them when the window started."""
    page = (ctx.scrapes.get("window_start") or {}).get("worker") or {}
    return page.get(_BUILD % stage)


def build_compile_s(ctx):
    """Backend time less the reads from the persistent cache it contains:
    what the XLA compiler itself took."""
    backend = build_seconds(ctx, "backend")
    read = build_seconds(ctx, "cache_read")
    if backend is None or read is None:
        return None
    return backend - read
