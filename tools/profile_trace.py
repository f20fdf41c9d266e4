#!/usr/bin/env python
"""Capture a bounded device profile of a tiny live engine + print the
XLA cost registry — the offline face of the device-truth plane
(runtime/device_profiler.py).

Runs a tiny-model EngineCore for a few decode windows with the device
profiler enabled: the dispatch sites harvest XLA's cost analysis for
every compiled program (flops / bytes accessed), a bounded
jax.profiler capture runs over the steady windows, and the top-K
programs by bytes-accessed print as a table.  The capture directory is
`deviceprofile_<service>_<pid>` under --out-dir, mergeable onto host
trace lanes with `tools/trace_merge.py --device <dir>`.

Exits NONZERO when no xplane/trace output lands (a build without the
profiler plugin used to silently print an empty glob and exit 0 — a
no-op that read as success).

    JAX_PLATFORMS=cpu python tools/profile_trace.py --ms 300
    python tools/profile_trace.py --model llama-3-1b --out-dir /tmp/prof

For a LIVE worker use `/debug/deviceprofile?ms=N` on its status port or
the control-plane `profile/<pid>` command instead — this tool builds
its own throwaway engine.
"""

from __future__ import annotations

import argparse
import os
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        "tools/profile_trace.py", description=__doc__.splitlines()[0])
    p.add_argument("--model", default="tiny-test",
                   help="model config name (default tiny-test)")
    p.add_argument("--ms", type=int, default=500,
                   help="device-capture bound in milliseconds")
    p.add_argument("--out-dir", default="/tmp/dynamo_deviceprofile",
                   help="capture destination (the capture lands in a "
                        "deviceprofile_<service>_<pid> subdirectory)")
    p.add_argument("--steps", type=int, default=40,
                   help="engine steps to run under the capture")
    p.add_argument("--top", type=int, default=10,
                   help="programs to print from the cost registry")
    args = p.parse_args(argv)

    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.models import config as mcfg
    from dynamo_tpu.runtime import device_profiler
    from dynamo_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    prof = device_profiler.configure(
        service="profile_trace", enabled=True,
        max_capture_ms=max(args.ms, 1), dump_dir=args.out_dir)

    core = EngineCore(EngineConfig(
        model=mcfg.get_config(args.model), num_blocks=128,
        enable_prefix_cache=False, decode_window=2,
        window_pipeline_depth=2,
        scheduler=SchedulerConfig(
            max_seqs=8, block_size=8, max_pages_per_seq=32,
            max_prefill_chunk=128, decode_buckets=(1, 2, 4, 8),
            prefill_buckets=(16, 128))))
    core.add_request("p0", list(range(1, 71)),
                     SamplingParams(max_tokens=max(args.steps, 8)))
    for _ in range(8):          # prefill + window warmup (compiles land)
        core.step()

    # The capture sleeps for its bound on a helper thread; stepping
    # stays HERE — the engine-thread contract pins step() to the thread
    # that warmed it up — so the device trace has real work under it.
    box = {}

    def run_capture():
        box["res"] = prof.capture(args.ms)

    t = threading.Thread(target=run_capture, daemon=True)
    t.start()
    while t.is_alive():
        core.step()
    t.join(timeout=10.0)
    res = box.get("res", {"ok": False, "error": "capture thread died"})

    print(f"registry: {prof.registry.size()} program(s) harvested "
          f"({prof.harvest_failures} failure(s))")
    rows = prof.registry.top_by("bytes_accessed", args.top)
    if rows:
        width = max(len(label) for label, _ in rows)
        print(f"{'program':<{width}}  {'bytes_accessed':>14}  "
              f"{'flops':>14}  optimal_s")
        for label, costs in rows:
            opt = costs.get("optimal_s")
            print(f"{label:<{width}}  {costs['bytes_accessed']:>14.0f}  "
                  f"{costs['flops']:>14.0f}  "
                  f"{opt if opt is not None else '-'}")

    if not res.get("ok"):
        print(f"error: device capture produced no trace output: "
              f"{res.get('error', 'unknown')}", file=sys.stderr)
        return 1
    print(f"capture: {res['ms']} ms -> {res['dir']}")
    for f in res["files"]:
        print(f"  {f}")
    print("merge onto host lanes with: "
          f"python tools/trace_merge.py <sources> --device {res['dir']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
