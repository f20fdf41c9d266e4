"""Arithmetic of the layer metrics that read the engine's request-state clock
(`dynamo_worker_request_state_{seconds,entries}_total{state=...}`: the
integral of the requests in each state over time, and the entries into it),
the tallies beside it (`dynamo_worker_request_{first,output}_tokens_total`,
`..._admit_blocked_seconds_total{reason=...}`,
`..._prefill_chances_total{outcome=...}`) and the two TTFT histograms that
bound the frontend (`dynamo_request_ttft_seconds` on both pages), all from
what a run already holds: `ctx.scrapes`, `ctx.delta`, `ctx.records`.

Every value is request-seconds over requests or tokens (or over the seconds
between the scrapes), so the idle tail that a late closing scrape holds adds
to neither side.  A program without the series (the parent of the PR that
added them) has nothing to read: every function returns None and the metric
is left out."""

from __future__ import annotations

import math
import re

from chipbench import readers

_SECONDS = 'dynamo_worker_request_state_seconds_total{state="%s"}'
_ENTRIES = 'dynamo_worker_request_state_entries_total{state="%s"}'
_TOKENS = "dynamo_worker_request_%s_tokens_total"
_CHANCE = re.compile(
    r'^dynamo_worker_prefill_chances_total\{outcome="([^"]+)"\}$')
_BLOCKED = re.compile(r"^dynamo_worker_admit_blocked_seconds_total\{")
_TTFT = r"^dynamo_request_ttft_seconds_%s(\{|$)"

# The states that lie before a request's first token, and after it.
BEFORE_FIRST_TOKEN = ("waiting", "budget_wait", "prefill", "first_token")
AFTER_FIRST_TOKEN = ("cohort_wait", "decode", "preempted")


def state_seconds(ctx, *states):
    """Request-seconds spent in `states` between the window's scrapes."""
    deltas = [ctx.delta("worker", _SECONDS % s) for s in states]
    return None if not deltas or None in deltas else sum(deltas)


def ms_per_entry(ctx, state: str):
    """Mean milliseconds a request spent in `state`: the state's request-
    seconds over the requests that entered it, both between the window's
    scrapes.  None where no request entered it (a state this engine has
    not)."""
    secs = state_seconds(ctx, state)
    entries = ctx.delta("worker", _ENTRIES % state)
    if secs is None or not entries:
        return None
    return secs * 1e3 / entries


def _matching_deltas(ctx, rx):
    """{key: change over the window} of the worker's series matching `rx`,
    or None where one has no value at an edge."""
    start = (ctx.scrapes.get("window_start") or {}).get("worker") or {}
    out = {}
    for key in start:
        if rx.match(key):
            out[key] = ctx.delta("worker", key)
    return None if None in out.values() else out


def chances_per_chunk(ctx):
    """Engine iterations that had a request waiting for prefill per
    iteration that dispatched a chunk: 1.0 when every chance is taken."""
    d = _matching_deltas(ctx, _CHANCE)
    taken = [v for k, v in (d or {}).items()
             if _CHANCE.match(k).group(1) == "dispatched"]
    if not taken or not taken[0]:
        return None
    return sum(d.values()) / taken[0]


def admit_blocked_share(ctx):
    """Percent of the time between the window's scrapes during which the
    head of the queue stood unadmitted, whatever the reason."""
    d = _matching_deltas(ctx, _BLOCKED)
    a = (ctx.scrapes.get("window_start") or {}).get("worker") or {}
    b = (ctx.scrapes.get("window_end") or {}).get("worker") or {}
    if not d or "_t" not in a or "_t" not in b or b["_t"] <= a["_t"]:
        return None
    return 100.0 * sum(d.values()) / (b["_t"] - a["_t"])


def _tokens_after_first(ctx):
    out = ctx.delta("worker", _TOKENS % "output")
    first = ctx.delta("worker", _TOKENS % "first")
    return None if out is None or first is None else out - first


def itl_inside_ms(ctx):
    """The inter-token latency as the engine sees it: request-seconds from
    first token to finish over the tokens emitted after a first one."""
    secs, toks = state_seconds(ctx, *AFTER_FIRST_TOKEN), \
        _tokens_after_first(ctx)
    if secs is None or not toks or toks < 0:
        return None
    return secs * 1e3 / toks


def cohort_wait_share(ctx):
    """Percent of the request-seconds from first token to finish spent
    waiting for the first decode dispatch to hold the row."""
    wait = state_seconds(ctx, AFTER_FIRST_TOKEN[0])
    whole = state_seconds(ctx, *AFTER_FIRST_TOKEN)
    if wait is None or not whole:
        return None
    return 100.0 * wait / whole


def _over(inside, client):
    if inside is None or client is None or not math.isfinite(client) \
            or client <= 0:
        return None
    return inside / client


def itl_inside_over_client(ctx):
    """The engine's own inter-token latency over the client's pooled one:
    what is left to 1.0 is delivery, wire and HTTP."""
    return _over(itl_inside_ms(ctx), readers.itl_mean_ms(ctx))


def ttft_inside_ms(ctx):
    """Mean milliseconds from arrival at the engine to first token:
    request-seconds before the first token over first tokens."""
    secs = state_seconds(ctx, *BEFORE_FIRST_TOKEN)
    first = ctx.delta("worker", _TOKENS % "first")
    if secs is None or not first:
        return None
    return secs * 1e3 / first


def client_ttft_ms(ctx):
    """Mean time to first token of the judged requests that got one, from
    their due times, at the client."""
    got = [(r["first"] - r["due"]) * 1e3 for r in ctx.records
           if r["ok"] and r["first"]]
    return sum(got) / len(got) if got else None


def ttft_inside_over_client(ctx):
    return _over(ttft_inside_ms(ctx), client_ttft_ms(ctx))


def _histogram_mean(ctx, source: str):
    """Mean of the observations a TTFT histogram took between the window's
    scrapes, its labelled series summed."""
    a = (ctx.scrapes.get("window_start") or {}).get(source)
    b = (ctx.scrapes.get("window_end") or {}).get(source)
    if not a or not b:
        return None
    parts = []
    for what in ("sum", "count"):
        x = readers._sum_matching(a, _TTFT % what)
        y = readers._sum_matching(b, _TTFT % what)
        if y is None:
            return None
        parts.append(y - (x or 0.0))
    return parts[0] / parts[1] if parts[1] > 0 else None


def frontend_ttft_overhead_ms(ctx):
    """Milliseconds the frontend adds to a request's first token: its own
    TTFT (from HTTP entry) less the worker's (from its RPC boundary), each
    the mean over the window: preprocess, route and the RPC both ways."""
    fe, wk = _histogram_mean(ctx, "frontend"), _histogram_mean(ctx, "worker")
    return None if fe is None or wk is None else (fe - wk) * 1e3
