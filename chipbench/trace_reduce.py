"""Reduction of a `jax.profiler` capture (`*.xplane.pb`) to the numbers the
benchmark reports: device busy and idle time, time per program, per
operation and per named kernel, and the longest idle gaps with what the host
was doing in them.

Two stages, so that the arithmetic is testable without a trace reader:
`load_events` turns the protobuf into plain lists (`{plane: {line: [[name,
start_ns, dur_ns], ...]}}`, the format of `testdata/*.events.json`), and
`reduce` works on those.  Run as a program it prints one JSON object:

    python -m chipbench.trace_reduce <capture dir or .xplane.pb>
        [--roles '{"decode": {"jit_run": 8}, ...}'] [--kernels '{...}']
        [--inventory]      # planes, lines and the commonest names, to look at

Names are those XLA and Pallas print today; nothing here renames them."""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)
HOST_MIN_NS = 50_000        # host events shorter than this name no gap
_SUFFIX = re.compile(r"[.(]\d+\)?$")


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return found[-1]


def load_events(path: str, host_min_ns: int = HOST_MIN_NS,
                device_regex=DEVICE_PLANE) -> dict:
    """{plane: {line: [[name, start_ns, dur_ns], ...]}} of one capture.
    Device planes are kept whole; host lines keep events long enough to
    explain a gap."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    out: Dict[str, Dict[str, list]] = {}
    for plane in data.planes:
        device = bool(device_regex.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = {}
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.duration_ns >= host_min_ns]
            if events:
                lines.setdefault(line.name, []).extend(events)
        if lines:
            out[plane.name] = lines
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def short(name: str) -> str:
    """An operation's event is named by its whole HLO instruction
    (`%fusion.12 = (f32[2]...) fusion(...)`): keep `fusion.12`."""
    return name.split(" = ", 1)[0].lstrip("%")


# Operations that only enclose others (a decode window is one `while` around
# its eight steps): counted in busy time through their children.
CONTAINERS = ("while", "conditional", "call")


def _norm(name: str) -> str:
    """`jit_run(123)` -> `jit_run`; `%fusion.45 = ...` -> `fusion`."""
    name = short(name)
    prev = None
    while prev != name:
        prev, name = name, _SUFFIX.sub("", name)
    return name


def _top(table: Dict[str, float], n: int = 10) -> list:
    return [[k, v / 1e9] for k, v in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def reduce(events: dict, roles: dict = None, kernels: dict = None,
           device_regex=DEVICE_PLANE) -> dict:
    """See the module docstring.  `roles` maps a role ("decode", "prefill")
    to the programs that play it, each with the model steps one call of it
    runs (a decode window of eight steps: 8; a single step: 1); `kernels`
    maps a kernel's label to a regex over operation names."""
    roles = roles or {}
    kernels = kernels or {}
    device_planes = {p: ls for p, ls in events.items()
                     if device_regex.match(p)}
    if not device_planes:
        raise ValueError("the capture holds no /device:TPU:<n> plane: "
                         f"planes are {sorted(events)}")
    # The window is the span of the chips' own events.  The host's lines
    # start earlier and end up to a second later (starting and collecting
    # the trace), when the chip is no longer traced: counting that as idle
    # would charge the profiler's own work to the program.
    starts, ends = [], []
    for lines in device_planes.values():
        for evs in lines.values():
            for _n, s, d in evs:
                starts.append(s)
                ends.append(s + d)
    t0, t1 = min(starts), max(ends)
    window_ns = t1 - t0

    busy_ns = []
    program_ns: Dict[str, float] = {}
    program_calls: Dict[str, int] = {}
    op_ns: Dict[str, float] = {}
    kernel_ns = {label: 0.0 for label in kernels}
    kernel_re = {label: re.compile(rx) for label, rx in kernels.items()}
    gaps: List[Tuple[float, float, str]] = []
    for plane, lines in sorted(device_planes.items()):
        modules = sorted((s, s + d, _norm(n)) for key in MODULE_LINES
                         for n, s, d in lines.get(key, ()))
        ops = [(s, s + d, short(n)) for key in OP_LINES
               for n, s, d in lines.get(key, ())]
        if not ops and not modules:
            # Unknown line names: take every line of the plane as work.
            ops = [(s, s + d, n) for evs in lines.values()
                   for n, s, d in evs]
        busy = _union([(s, e) for s, e, _ in (ops or modules)])
        busy_ns.append(sum(e - s for s, e in busy))
        for s, e, name in modules:
            program_ns[name] = program_ns.get(name, 0.0) + (e - s)
            program_calls[name] = program_calls.get(name, 0) + 1
        # An operation belongs to the program whose interval holds its start.
        mi = 0
        for s, e, name in sorted(ops):
            while mi + 1 < len(modules) and modules[mi + 1][0] <= s:
                mi += 1
            owner = (modules[mi][2] if modules and
                     modules[mi][0] <= s < modules[mi][1] else "?")
            if _norm(name) in CONTAINERS:
                continue
            key = f"{owner}/{_norm(name)}"
            op_ns[key] = op_ns.get(key, 0.0) + (e - s)
            for label, rx in kernel_re.items():
                if rx.search(name):
                    kernel_ns[label] += e - s
        # Idle gaps of this chip, between programs where it has them.
        covered = _union([(s, e) for s, e, _ in modules]) or busy
        names = modules or [(s, e, "op") for s, e in busy]
        edge = t0
        for s, e in covered:
            if s > edge:
                before = [n for ms, me, n in names if me <= edge + 1][-1:] \
                    or ["start"]
                after = [n for ms, me, n in names if ms >= s - 1][:1] \
                    or ["end"]
                gaps.append((edge, s, f"{before[0]}->{after[0]}"))
            edge = max(edge, e)
        if t1 > edge:
            gaps.append((edge, t1, "tail"))

    # What was the host doing in each of the longest gaps?
    host = sorted((s, s + d, n) for p, lines in events.items()
                  if p not in device_planes
                  for evs in lines.values() for n, s, d in evs)
    gap_ns: Dict[str, float] = {}
    for gs, ge, between in sorted(gaps, key=lambda g: g[0] - g[1])[:200]:
        best, best_overlap = None, 0.0
        for s, e, n in host:
            if s >= ge:
                break
            overlap = min(e, ge) - max(s, gs)
            if overlap > best_overlap:
                best, best_overlap = n, overlap
        label = (f"{between} | host: {_norm(best)}"
                 if best and best_overlap >= 0.5 * (ge - gs) else between)
        gap_ns[label] = gap_ns.get(label, 0.0) + (ge - gs)

    n_dev = len(device_planes)
    busy_s = sum(busy_ns) / n_dev / 1e9
    out = {
        "devices": n_dev,
        "window_s": window_ns / 1e9,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (window_ns / 1e9),
        "programs": {k: {"seconds": v / 1e9 / n_dev,
                         "calls": program_calls[k] / n_dev}
                     for k, v in program_ns.items()},
        "kernels_s": {k: v / 1e9 / n_dev for k, v in kernel_ns.items()},
        "device_ops": [[k, v / n_dev] for k, v in _top(op_ns)],
        "idle_gaps": [[k, v / n_dev] for k, v in _top(gap_ns)],
        "idle_gap_total_s": sum(e - s for s, e, _ in gaps) / 1e9 / n_dev,
        "roles": {},
    }
    for role, steps_per_call in roles.items():
        played = [(out["programs"][n], k) for n, k in steps_per_call.items()
                  if n in out["programs"]]
        out["roles"][role] = {
            "seconds": sum(p["seconds"] for p, _k in played),
            "calls": sum(p["calls"] for p, _k in played),
            "steps": sum(p["calls"] * k for p, k in played)}
    return out


def inventory(path: str) -> dict:
    """Planes, lines, event counts and the commonest names with one
    example's statistics: for looking at a trace before trusting `reduce`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            count: Dict[str, List[float]] = {}
            example = {}
            n = 0
            for e in line.events:
                n += 1
                c = count.setdefault(e.name, [0, 0.0])
                c[0] += 1
                c[1] += e.duration_ns
                if e.name not in example and len(example) < 400:
                    example[e.name] = {str(k): str(v)[:120]
                                       for k, v in list(e.stats)[:12]}
            top = sorted(count.items(), key=lambda kv: -kv[1][1])[:40]
            lines[line.name] = {
                "events": n,
                "top": [[k, c, ns / 1e9, example.get(k, {})]
                        for k, (c, ns) in top]}
        out[plane.name] = lines
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser("chipbench.trace_reduce")
    p.add_argument("path")
    p.add_argument("--roles", default="{}")
    p.add_argument("--kernels", default="{}")
    p.add_argument("--inventory", action="store_true")
    p.add_argument("--device-regex", default=DEVICE_PLANE.pattern,
                   help="which planes are chips (a CPU rehearsal passes "
                        "the host plane to walk the same code)")
    p.add_argument("--dump-events", default=None,
                   help="also write the first --dump-ms of device events "
                        "(and host events) as JSON: a test fixture")
    p.add_argument("--dump-ms", type=float, default=300.0)
    args = p.parse_args(argv)
    if args.inventory:
        json.dump(inventory(args.path), sys.stdout)
        return 0
    dev_rx = re.compile(args.device_regex)
    events = load_events(args.path, device_regex=dev_rx)
    if args.dump_events:
        t0 = min(s for ls in events.values() for evs in ls.values()
                 for _n, s, _d in evs)
        # Start the cut at the first device event so the fixture has work.
        dev0 = min((s for p_, ls in events.items() if dev_rx.match(p_)
                    for evs in ls.values() for _n, s, _d in evs), default=t0)
        lo, hi = dev0 - 5e6, dev0 + args.dump_ms * 1e6
        cut = {pl: {ln: [[short(n), s - lo, d] for n, s, d in evs
                         if lo <= s and s + d <= hi]
                    for ln, evs in ls.items()}
               for pl, ls in events.items()}
        cut = {pl: {ln: evs for ln, evs in ls.items() if evs}
               for pl, ls in cut.items()}
        with open(args.dump_events, "w") as f:
            json.dump({pl: ls for pl, ls in cut.items() if ls}, f)
    json.dump(reduce(events, json.loads(args.roles),
                     json.loads(args.kernels), dev_rx), sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
