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
- fails, before JAX is touched, on a configuration that does not name its
  `reference`, `comparison` and `warmups`, or names a file that is not there;
- compares the built engine with the reference the configuration names, by
  the comparison it names (`check.py` loads both and holds the result to
  its contract);
- runs the warm-ups the configuration names, in its order (`warmups/`: for
  today's engine every decode-window, single-step and packed-prefill shape
  the cell's traffic can reach, through the engine's own jitted functions
  and shape ladders).  If the program's internals moved, the worker dies
  here and the run fails: a run never serves on a warm-up it could not
  finish;
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

from chipbench import pieces


def synthetic_tokenizer_json(vocab_size: int) -> str:
    """A word-level tokenizer: id i <-> the word `w<i>`."""
    return json.dumps({
        "version": "1.0", "truncation": None, "padding": None,
        "added_tokens": [], "normalizer": None,
        "pre_tokenizer": {"type": "WhitespaceSplit"},
        "post_processor": None, "decoder": None,
        "model": {"type": "WordLevel", "unk_token": "w0",
                  "vocab": {f"w{i}": i for i in range(vocab_size)}}})


def run_warmups(core, names, max_context: int, vocab: int,
                root: str = pieces.HERE) -> list:
    """Run the warm-ups a configuration names, in its order.  Each is
    `warmups/<name>.py` under `root`: `warm(core, max_context, vocab)` returns
    a dict with `seconds` and a count of what it dispatched, and the module's
    `STEP_PROGRAMS` says whether those were step programs.  A warm-up that
    cannot finish raises, the worker dies and the run fails."""
    done = []
    for name in names:
        mod = pieces.load("warmups", name, root,
                          needs=("warm", "STEP_PROGRAMS"))
        out = mod.warm(core, max_context, vocab)
        if not isinstance(out, dict) or len(out) < 2 \
                or not isinstance(out.get("seconds"), (int, float)):
            raise RuntimeError(f"warmups/{name}.py returned {out!r}: wanted a "
                               "dict with `seconds` and a count")
        done.append(dict(out, name=name,
                         step_programs=bool(mod.STEP_PROGRAMS)))
    return done


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
    names = pieces.named(hf)      # raises, with the name, before JAX loads

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
                   if own.check_lengths else None)
        result["check"] = check.run_check(self, hf, own.seed, lengths)
        result["warmups"] = run_warmups(self, names["warmups"],
                                        own.max_context, cfg.vocab_size)
        result["counters_after_warm"] = self.counters.to_dict()
        _Side.core = self
        result["shapes_after_warm"] = _seen_shapes(self)
        write_result()
        print("chipbench: check", json.dumps(result["check"]), flush=True)
        for w in result["warmups"]:
            print(f"chipbench: warm {w['name']}", json.dumps(w), flush=True)

    engine_mod.EngineCore.__init__ = init_then_check

    side = http.server.ThreadingHTTPServer(("127.0.0.1", own.side_port),
                                           _Side)
    threading.Thread(target=side.serve_forever, daemon=True,
                     name="chipbench-side").start()

    from dynamo_tpu.worker.main import main as worker_main

    worker_main(worker_argv)


if __name__ == "__main__":
    main()
