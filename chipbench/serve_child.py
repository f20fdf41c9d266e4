"""The engine worker of a benchmark run: `python -m chipbench.serve_child
<own flags> -- <flags of dynamo_tpu.worker>`.

The program resolves `--model` to a preset name or a directory WITH weights,
so a configuration that is a file has no way in.  This entry makes one, with
no edit to the program: it reads the configuration's file, maps it with the
program's own `config_from_hf`, registers it under the configuration's name in
the program's `PRESETS` dict, and calls the program's real worker `main()`.

It also (all of it set-up, before the worker serves a request):
- refuses to run on anything but the TPU chips the cell asks for;
- gives the model a tokenizer whose every token is a visible word, so that
  each generated token reaches the client (the program streams no chunk for
  a token that decodes to nothing, which with seeded weights over a 32k
  vocabulary and the byte tokenizer is 99 % of them);
- compares the built engine with `reference.py` (`check.py`);
- dispatches every decode-window, single-step and packed-prefill shape the
  cell's traffic can reach, through the engine's own jitted functions and
  shape ladders (the program's `--prewarm-prefill` covers only the last
  set).  If the program's internals moved, the worker dies here and the run
  fails: a run never serves on a warm-up it could not finish;
- answers `GET /mem` on a side port with the device's memory statistics.
What it found goes to `--result-file` as JSON."""

from __future__ import annotations

import argparse
import http.server
import json
import os
import sys
import threading
import time


def synthetic_tokenizer_json(vocab_size: int) -> str:
    """A word-level tokenizer: id i <-> the word `w<i>`."""
    return json.dumps({
        "version": "1.0", "truncation": None, "padding": None,
        "added_tokens": [], "normalizer": None,
        "pre_tokenizer": {"type": "WhitespaceSplit"},
        "post_processor": None, "decoder": None,
        "model": {"type": "WordLevel", "unk_token": "w0",
                  "vocab": {f"w{i}": i for i in range(vocab_size)}}})


def _warm_windows(core, max_context: int) -> dict:
    """Dispatch the greedy decode-window program once for every (row bucket,
    page bucket) the traffic can reach, all rows dead (context 0)."""
    import jax

    t0 = time.monotonic()
    done = 0
    sched = core.scheduler.config
    k = core.config.decode_window
    if k <= 1:
        return {"shapes": 0, "seconds": 0.0}
    lag = core.config.window_pipeline_depth
    top = sched.bucket_for_pages(
        -(-(max_context + (lag + 1) * k) // core.block_size))
    widths = [w for w in sched.page_bucket_ladder() if w <= top]
    rows = sorted({sched.bucket_for_decode(n)
                   for n in range(1, sched.max_seqs + 1)})
    fn = core._window_fn(True)
    for b in rows:
        i32 = jax.numpy.zeros((b,), jax.numpy.int32)
        f32 = jax.numpy.zeros((b,), jax.numpy.float32)
        pos = jax.numpy.full((b,), core._pad_position, jax.numpy.int32)
        keys = jax.numpy.zeros((b, 2), jax.numpy.uint32)
        for w in widths:
            if not core.counters.note_dispatch("window", True, b, w):
                continue
            bts = jax.numpy.zeros((b, w), jax.numpy.int32)
            out = fn(core.params, core.cache, i32, pos, i32, bts, f32,
                     i32, f32 + 1.0, keys, i32)
            core.cache = out[0]
            done += 1
    jax.block_until_ready(core.cache)
    return {"shapes": done, "seconds": time.monotonic() - t0}


def _warm_single_steps(core, max_context: int) -> dict:
    """The fused greedy single decode step, for every (row bucket, page
    bucket): the engine takes it whenever every decoding request has just
    left prefill (none is in the window cohort yet), and whenever every
    one of them has less than half a window left to generate (the engine's
    end-of-life guard), which most requests of unaligned length reach."""
    import jax

    t0 = time.monotonic()
    done = 0
    if not core._fused_greedy_capable:
        raise RuntimeError("the engine has no fused greedy single step: its "
                           "single-step shapes cannot be warmed from here")
    sched = core.scheduler.config
    top = sched.bucket_for_pages(-(-max_context // core.block_size))
    widths = [w for w in sched.page_bucket_ladder() if w <= top]
    rows = sorted({sched.bucket_for_decode(n)
                   for n in range(1, sched.max_seqs + 1)})
    fn = core._greedy_step_fn()
    for b in rows:
        i32 = jax.numpy.zeros((b,), jax.numpy.int32)
        tok = jax.numpy.zeros((b, 1), jax.numpy.int32)
        pos = jax.numpy.full((b, 1), core._pad_position, jax.numpy.int32)
        for w in widths:
            if not core.counters.note_dispatch("decode1g", b, w):
                continue
            out = fn(core.params, core.cache, tok, pos, i32,
                     jax.numpy.zeros((b, w), jax.numpy.int32), i32)
            core.cache = out[1]
            done += 1
    jax.block_until_ready(core.cache)
    return {"shapes": done, "seconds": time.monotonic() - t0}


def _warm_prefill(core) -> dict:
    """Dispatch every packed-prefill shape once, all segments empty: what
    the program's `--prewarm-prefill` does, done here with the other two
    sets so that one place counts and times all of them."""
    import jax

    t0 = time.monotonic()
    done = 0
    if not core._use_packed_prefill:
        return {"shapes": 0, "seconds": 0.0}
    fn = core._packed_prefill_fn()
    for (t, r, p) in core.packed_prefill_shape_set():
        if not core.counters.note_dispatch("prefill_packed", t, r, p):
            continue
        zt = jax.numpy.zeros((t,), jax.numpy.int32)
        zr = jax.numpy.zeros((r,), jax.numpy.int32)
        pos = jax.numpy.full((t,), core._pad_position, jax.numpy.int32)
        out = fn(core.params, core.cache, zt, pos, zt,
                 jax.numpy.zeros((r, p), jax.numpy.int32), zr, zr, zr, zr)
        core.cache = out[1]
        done += 1
    jax.block_until_ready(core.cache)
    return {"shapes": done, "seconds": time.monotonic() - t0}


def _warm_first_tokens(core, vocab: int) -> dict:
    """n prompts that finish prefill in one pack sample n first tokens in
    one call: run n = 1 .. the pack's segment count through the engine's
    public add_request / step, one token each."""
    from dynamo_tpu.engine.sampling import SamplingParams

    t0 = time.monotonic()
    top = core.scheduler.config.packed_prefill_segments
    for n in range(1, top + 1):
        for i in range(n):
            core.add_request(f"chipbench-warm-{n}-{i}",
                             [1 + (7 * n + i) % (vocab - 1)] * 5,
                             SamplingParams(max_tokens=1))
        while core.has_work:
            core.step()
    return {"packs": top, "seconds": time.monotonic() - t0}


def _seen_shapes(core) -> list:
    """The (program, shape) pairs the engine has dispatched, for naming a
    shape that first appears inside a window."""
    return sorted(repr(k) for k in core.counters._seen_shapes)


class _Side(http.server.BaseHTTPRequestHandler):
    core = None

    def do_GET(self):  # noqa: N802 (http.server's name)
        import jax

        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        body = json.dumps({
            "shapes": _seen_shapes(self.core) if self.core else [],
            "peak_bytes_in_use": max(
                (s.get("peak_bytes_in_use", 0) for s in stats), default=0),
            "bytes_in_use": max(
                (s.get("bytes_in_use", 0) for s in stats), default=0),
            "bytes_limit": max(
                (s.get("bytes_limit", 0) for s in stats), default=0),
        }).encode()
        self.send_response(200)
        self.send_header("content-type", "application/json")
        self.send_header("content-length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    split = argv.index("--") if "--" in argv else len(argv)
    p = argparse.ArgumentParser("chipbench.serve_child")
    p.add_argument("--config-file", required=True)
    p.add_argument("--name", required=True)
    p.add_argument("--chips", type=int, default=1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--side-port", type=int, default=0)
    p.add_argument("--result-file", required=True)
    p.add_argument("--max-context", type=int, default=8192)
    p.add_argument("--allow-cpu", action="store_true")
    p.add_argument("--override", default="{}",
                   help="JSON of config keys to replace (CPU rehearsal)")
    p.add_argument("--check-lengths", default="")
    own = p.parse_args(argv[:split])
    worker_argv = argv[split + 1:]

    with open(own.config_file) as f:
        hf = json.load(f)
    hf.update(json.loads(own.override))
    for k, v in (hf.get("env") or {}).items():
        os.environ.setdefault(k, v)

    t_start = time.monotonic()
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    result = {"device": device}

    def write_result() -> None:
        tmp = own.result_file + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f)
        os.replace(tmp, own.result_file)

    write_result()
    if not own.allow_cpu and (device["platform"] != "tpu"
                              or device["count"] < own.chips):
        print(f"chipbench: needs {own.chips} TPU chip(s), JAX reports "
              f"{device}", file=sys.stderr, flush=True)
        sys.exit(3)

    from dynamo_tpu.engine import engine as engine_mod
    from dynamo_tpu.models import config as mcfg
    from dynamo_tpu.models import loader

    cfg = loader.config_from_hf(hf, own.name)
    if hf.get("torch_dtype") == "float32":
        cfg = cfg.replace(dtype=jnp.float32)
    mcfg.PRESETS[own.name] = cfg

    # By file, not inline in the model card: the control plane reads its
    # frames with asyncio's default 64 KiB line limit, and a card that
    # carries a 32k-word tokenizer.json (0.6 MB) drops the connection.
    tok_path = os.path.join(os.path.dirname(os.path.abspath(
        own.result_file)), "tokenizer.json")
    with open(tok_path, "w") as f:
        f.write(synthetic_tokenizer_json(cfg.vocab_size))
    resolve = loader.resolve_model

    def resolve_with_tokenizer(name):
        out = resolve(name)
        if name == own.name:
            return out[0], out[1], {"kind": "hf_file", "path": tok_path}, out[3]
        return out

    loader.resolve_model = resolve_with_tokenizer

    init = engine_mod.EngineCore.__init__

    def init_then_check(self, *a, **kw):
        init(self, *a, **kw)
        result["engine_built_s"] = time.monotonic() - t_start
        from chipbench import check

        lengths = (tuple(int(x) for x in own.check_lengths.split(","))
                   if own.check_lengths else check.LENGTHS)
        result["check"] = check.run_check(self, hf, own.seed, lengths)
        result["warm_windows"] = _warm_windows(self, own.max_context)
        result["warm_single_steps"] = _warm_single_steps(self,
                                                         own.max_context)
        result["warm_prefill"] = _warm_prefill(self)
        result["warm_first_tokens"] = _warm_first_tokens(self, cfg.vocab_size)
        result["counters_after_warm"] = self.counters.to_dict()
        _Side.core = self
        result["shapes_after_warm"] = _seen_shapes(self)
        write_result()
        print("chipbench: check", json.dumps(result["check"]), flush=True)
        print("chipbench: warm_windows", json.dumps(result["warm_windows"]),
              flush=True)
        for key in ("warm_single_steps", "warm_prefill",
                    "warm_first_tokens"):
            print(f"chipbench: {key}", json.dumps(result[key]), flush=True)

    engine_mod.EngineCore.__init__ = init_then_check

    side = http.server.ThreadingHTTPServer(("127.0.0.1", own.side_port),
                                           _Side)
    threading.Thread(target=side.serve_forever, daemon=True,
                     name="chipbench-side").start()

    from dynamo_tpu.worker.main import main as worker_main

    worker_main(worker_argv)


if __name__ == "__main__":
    main()
