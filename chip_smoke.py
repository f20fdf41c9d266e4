#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

    python chip_smoke.py               # one TPU chip: serve, graph, parity
    python chip_smoke.py --multichip   # four TPU chips: the tp4 phase only

Phases of the default run, each in its own child process so the chip
belongs to one process at a time (this parent never imports jax):

  probe   what JAX finds: anything but the expected TPU ends the run at
          once, before a server spends minutes starting on the CPU.
  serve   `python -m dynamo_tpu.frontend --model llama-3-1b` exactly as the
          README starts it, driven over plain HTTP: unary and streaming
          chat, a burst of 8 concurrent completions, a repeated prompt,
          SIGTERM.
  graph   `python -m dynamo_tpu.launcher` on a graph with an engine-less
          frontend, a `--mocker` worker and one real worker; the same
          requests through the control plane.  Engine-less processes
          must never initialise a JAX backend.
  parity  two `EngineCore`s over one seed and one ragged prompt set —
          kernel planes on `auto` against the XLA gather path — compared
          on prefill logits and greedy tokens, bf16 and int8.

Every failure is an exception; the first one ends the run with exit code
1.  The last stdout line of a run that passed is exactly
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.

The phase functions take a model and the platform they expect, so
`tests/test_chip_smoke.py` runs them at `tiny-test` size on the CPU.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import random
import re
import signal
import socket
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0

# bf16 tolerance on last-position logits, kernel planes against the gather
# path (and tp4 against one chip).  Random-weight logits are ~N(0, 1) with
# a maximum near 4.5 over the 128k vocabulary; both paths contract in bf16
# (eps 2^-8) with f32 accumulation but in different orders (online-softmax
# tiles against one full softmax), across 16 to 32 layers.  Measured on a
# TPU v5 lite in PR 21: 0.076 at most.  The tolerance is twice that, ~3%
# of the logit range.  A greedy token may differ only where the
# reference's own margin between the two candidates is inside twice the
# tolerance.
LOGIT_ATOL = 0.15
# int8 KV: the kernel multiplies the f32 scales into the scores, the gather
# path rounds the dequantized K/V to bf16 first.  Measured 0.097.
LOGIT_ATOL_INT8 = 0.2


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# Parent side: processes and HTTP.  Nothing below imports jax.


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (HERE, env.get("PYTHONPATH")) if p)
    env["PYTHONUNBUFFERED"] = "1"
    # JAX's own debug lines: "Initializing backend" (which processes took
    # a device) and persistent-cache hits and misses.
    env["JAX_DEBUG_LOG_MODULES"] = "jax._src.compiler,jax._src.xla_bridge"
    return env


class Child:
    """A subprocess with its output in a log file.  It leads a process
    group of its own, and leaving the `with` block kills whatever is
    left of the group: a launcher that dies on a failed check must not
    leave its services behind."""

    def __init__(self, args, log_path: str) -> None:
        self.log_path = log_path
        self._log = open(log_path, "wb")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, *args], stdout=self._log,
            stderr=subprocess.STDOUT, env=_child_env(), cwd=HERE,
            start_new_session=True)

    def __enter__(self) -> "Child":
        return self

    def __exit__(self, *exc) -> None:
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass    # the whole group already exited
        self.proc.wait()
        self._log.close()

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def tail(self, n: int = 40) -> str:
        return "\n".join(self.log_text().splitlines()[-n:])

    def terminate(self, timeout: float = 120.0) -> int:
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"process ignored SIGTERM for {timeout:.0f}s; log tail:\n"
                + self.tail()) from None


def _http(port: int, method: str, path: str, body=None,
          timeout: float = 600.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        data = json.dumps(body).encode() if body is not None else None
        conn.request(method, path, body=data,
                     headers={"content-type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _wait_models(port: int, names, child: Child, timeout: float) -> float:
    """Seconds from the child's start until /v1/models lists `names`."""
    deadline = child.t0 + timeout
    while time.monotonic() < deadline:
        require(child.proc.poll() is None,
                f"server exited with code {child.proc.returncode} before "
                f"it was ready; log tail:\n{child.tail()}")
        try:
            status, raw = _http(port, "GET", "/v1/models", timeout=5.0)
        except OSError:
            status, raw = 0, b""
        if status == 200:
            have = {m["id"] for m in json.loads(raw)["data"]}
            if set(names) <= have:
                return time.monotonic() - child.t0
        time.sleep(0.5)
    raise SmokeFailure(f"models {sorted(names)} not served within "
                       f"{timeout:.0f}s; log tail:\n{child.tail()}")


def _prompt(rng: random.Random, n_bytes: int) -> str:
    words = []
    size = 0
    while size < n_bytes:
        w = "".join(rng.choice("abcdefghijklmnopqrstuvwxyz")
                    for _ in range(rng.randint(2, 9)))
        words.append(w)
        size += len(w) + 1
    return " ".join(words)[:n_bytes]


def _check_usage(usage: dict, finish: str, max_tokens: int,
                 prompt_tokens=None) -> None:
    if prompt_tokens is not None:
        require(usage["prompt_tokens"] == prompt_tokens,
                f"usage.prompt_tokens {usage['prompt_tokens']} != "
                f"{prompt_tokens} prompt bytes")
    if finish == "length":
        require(usage["completion_tokens"] == max_tokens,
                f"usage.completion_tokens {usage['completion_tokens']} != "
                f"max_tokens {max_tokens}")
    else:
        require(0 < usage["completion_tokens"] <= max_tokens,
                f"bad completion_tokens {usage}")
    require(usage["total_tokens"]
            == usage["prompt_tokens"] + usage["completion_tokens"],
            f"usage does not add up: {usage}")


def _complete(port: int, model: str, prompt: str, max_tokens: int) -> str:
    status, raw = _http(port, "POST", "/v1/completions", {
        "model": model, "prompt": prompt, "max_tokens": max_tokens,
        "temperature": 0})
    require(status == 200, f"/v1/completions -> {status}: {raw[:300]!r}")
    out = json.loads(raw)
    choice = out["choices"][0]
    # The byte tokenizer: one prompt byte is one token.
    _check_usage(out["usage"], choice["finish_reason"], max_tokens,
                 prompt_tokens=len(prompt.encode()))
    return choice["text"]


def drive_requests(port: int, model: str, prompt_bytes, max_tokens) -> None:
    """The smoke's traffic: unary chat, streaming chat, a burst of 8
    concurrent completions with prompts of `prompt_bytes[0]` to
    `prompt_bytes[1]` byte-tokens, and a repeat of one burst prompt."""
    rng = random.Random(SEED)
    chat = {"model": model, "temperature": 0, "max_tokens": max_tokens[0],
            "messages": [{"role": "user", "content": _prompt(rng, 60)}]}
    status, raw = _http(port, "POST", "/v1/chat/completions", chat)
    require(status == 200, f"unary chat -> {status}: {raw[:300]!r}")
    out = json.loads(raw)
    _check_usage(out["usage"], out["choices"][0]["finish_reason"],
                 max_tokens[0])
    require(out["usage"]["prompt_tokens"] >= 60, f"chat usage {out['usage']}")
    say(f"  unary chat: 200, usage {out['usage']}")

    status, raw = _http(port, "POST", "/v1/chat/completions", dict(
        chat, stream=True, stream_options={"include_usage": True}))
    require(status == 200, f"streaming chat -> {status}: {raw[:300]!r}")
    events = [ln[6:] for ln in raw.decode().splitlines()
              if ln.startswith("data: ")]
    require(events and events[-1] == "[DONE]",
            f"stream did not end with [DONE]: {events[-2:]}")
    chunks = [json.loads(e) for e in events[:-1]]
    finishes = [c["choices"][0].get("finish_reason")
                for c in chunks if c["choices"]]
    usage = [c["usage"] for c in chunks if c.get("usage")]
    require(finishes and finishes[-1] and len(usage) == 1,
            f"stream lacks a finish_reason or a usage chunk: {events[-3:]}")
    _check_usage(usage[0], finishes[-1], max_tokens[0])
    say(f"  streaming chat: 200, {len(chunks)} chunks, usage {usage[0]}")

    lo, hi = prompt_bytes
    burst = [(_prompt(rng, lo + (hi - lo) * i // 7),
              max_tokens[0] + (max_tokens[1] - max_tokens[0]) * i // 7)
             for i in range(8)]
    texts = [None] * 8
    errors = []

    def one(i: int) -> None:
        try:
            texts[i] = _complete(port, model, *burst[i])
        except Exception as e:  # re-raised on the caller's thread below
            errors.append(e)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    say(f"  burst: 8 concurrent completions 200, prompts "
        f"{[len(p) for p, _ in burst]} bytes, "
        f"max_tokens {[n for _, n in burst]}")

    again = _complete(port, model, *burst[5])
    require(again == texts[5],
            f"repeated greedy prompt gave different text: "
            f"{texts[5]!r} then {again!r}")
    say("  repeat of burst prompt 5 (prefix-cache hit): identical text")


ENGINE_BUILT = re.compile(
    r"engine built: platform=(\S+) device_kind='([^']*)' devices=(\d+) "
    r"pallas_decode=(\S+) packed_prefill=(\S+) moe_mode=(\S+)")


# JAX's own handler stamps the date; `logging.basicConfig` repeats the line.
CACHE_LINE = re.compile(r"^DEBUG:\d{4}-\S+ \S+:jax\._src\.compiler:\d+: "
                        r"(Persistent compilation cache hit|PERSISTENT "
                        r"COMPILATION CACHE MISS) for '(\w+)'", re.M)
STEP_PROGRAMS = ("jit_step", "jit_run", "jit_fused")


def _engine_report(log: str, platform: str, require_kernels: bool,
                   second_start: bool = False) -> dict:
    """Check and print what an engine process logged; returns its device.
    `second_start`: another process compiled these step programs before,
    so on the chip this one must find them in the compile cache."""
    built = ENGINE_BUILT.search(log)
    require(built, "no 'engine built:' line in the log")
    say("  " + built.group(0))
    plat, kind, count, pallas, packed, _moe = built.groups()
    require(plat == platform,
            f"the engine runs on platform {plat!r}, not {platform!r}")
    if require_kernels:
        require(pallas == "True" and packed == "True",
                f"kernel planes resolved off: pallas_decode={pallas} "
                f"packed_prefill={packed}")
    stopped = re.search(r"engine stopped: counters=(\{.*?\}) "
                        r"peak_bytes_in_use=(\[.*?\])", log)
    require(stopped, "no 'engine stopped:' line in the log")
    counters = json.loads(stopped.group(1))
    say("  counters: " + json.dumps(
        {k: counters[k] for k in ("host_syncs", "xla_cache_misses",
                                  "window_dispatches",
                                  "packed_prefill_dispatches")}))
    say(f"  peak_bytes_in_use per device: {stopped.group(2)}")
    require(counters["window_dispatches"] > 0, "no decode window ran")
    if require_kernels:
        require(counters["packed_prefill_dispatches"] > 0,
                "no packed prefill ran")
    hashing = re.search(r"block hashing: .*", log)
    require(hashing, "no 'block hashing:' line in the log")
    say("  " + hashing.group(0))
    cache_dir = re.search(r"compile cache: (\S+)", log)
    require(cache_dir, "no 'compile cache:' line in the log")
    entries = (len(os.listdir(cache_dir.group(1)))
               if os.path.isdir(cache_dir.group(1)) else 0)
    lookups = CACHE_LINE.findall(log)
    hits = [name for what, name in lookups if "hit" in what]
    step_hits = sum(name in STEP_PROGRAMS for name in hits)
    step_misses = sum(name in STEP_PROGRAMS and "MISS" in what
                      for what, name in lookups)
    say(f"  compile cache {cache_dir.group(1)}: {entries} entries now; "
        f"this process: {len(hits)} hits / {len(lookups) - len(hits)} "
        f"misses, of them step programs {step_hits} hits / {step_misses} "
        "misses")
    if second_start and require_kernels:
        require(step_hits > 0 and step_misses <= step_hits,
                "a second start found few of its step programs in the "
                f"compile cache ({step_hits} hits, {step_misses} misses)")
    return {"platform": plat, "kind": kind, "count": int(count)}


def phase_serve(workdir: str, model: str = "llama-3-1b",
                platform: str = "tpu", require_kernels: bool = True,
                prompt_bytes=(100, 1500), max_tokens=(32, 64),
                ready_timeout: float = 600.0) -> dict:
    """The single-process server, started as README.md starts it."""
    port = _free_port()
    with Child(["-m", "dynamo_tpu.frontend", "--model", model,
                "--model-name", "m", "--http-port", str(port)],
               os.path.join(workdir, "serve.log")) as server:
        ready = _wait_models(port, {"m"}, server, ready_timeout)
        say(f"  ready after {ready:.1f}s")
        drive_requests(port, "m", prompt_bytes, max_tokens)
        rc = server.terminate()
        require(rc == 0, f"SIGTERM gave exit code {rc}; log tail:\n"
                + server.tail())
        say("  SIGTERM: exit code 0")
        return _engine_report(server.log_text(), platform, require_kernels)


def phase_graph(workdir: str, model: str = "llama-3-1b",
                platform: str = "tpu", require_kernels: bool = True,
                prompt_bytes=(100, 1500), max_tokens=(32, 64),
                ready_timeout: float = 600.0) -> dict:
    """The distributed form on one chip: launcher, control plane, an
    engine-less frontend, a mocker worker (started BEFORE the real one,
    under its own component: instances of one endpoint are replicas of
    one model) and one real worker."""
    port, health = _free_port(), _free_port()
    log_dir = os.path.join(workdir, "graph_logs")
    os.makedirs(log_dir, exist_ok=True)
    graph = os.path.join(workdir, "graph.toml")
    with open(graph, "w") as f:
        f.write(f'''[graph]
serve_control_plane = true
control_plane = "127.0.0.1:0"
log_dir = "{log_dir}"

[services.frontend]
module = "dynamo_tpu.frontend"
args = ["--http-port", "{port}"]
restart = "never"

[services.mocker]
module = "dynamo_tpu.worker"
args = ["--mocker", "--model-name", "mock", "--component", "mock"]
restart = "never"

[services.worker]
module = "dynamo_tpu.worker"
args = ["--model", "{model}", "--model-name", "m",
        "--health-port", "{health}"]
restart = "never"
''')
    with Child(["-m", "dynamo_tpu.launcher", graph],
               os.path.join(workdir, "launcher.log")) as launcher:

        def service_log(name: str) -> str:
            path = os.path.join(
                log_dir, f"dynamo_graph_{launcher.proc.pid}_{name}_0.log")
            with open(path, errors="replace") as f:
                return f.read()

        try:
            ready = _wait_models(port, {"m", "mock"}, launcher,
                                 ready_timeout)
        except SmokeFailure:
            for name in ("frontend", "mocker", "worker"):
                tail = "\n".join(service_log(name).splitlines()[-30:])
                say(f"--- {name} log tail ---\n{tail}")
            raise
        say(f"  both models discovered after {ready:.1f}s")
        drive_requests(port, "m", prompt_bytes, max_tokens)
        text = _complete(port, "mock", "hello mocker", 8)
        say(f"  mocker model: 200, {len(text)} chars")
        status, raw = _http(health, "GET", "/metrics")
        require(status == 200, f"worker /metrics -> {status}")
        metrics = dict(ln.rsplit(" ", 1) for ln in raw.decode().splitlines()
                       if ln.startswith("dynamo_worker_engine_"))
        say("  worker /metrics: " + json.dumps(
            {k[len("dynamo_worker_engine_"):]: metrics[k]
             for k in sorted(metrics)}))
        rc = launcher.terminate()
        require(rc == 0, f"launcher exit code {rc}; log tail:\n"
                + launcher.tail())
        say("  SIGTERM: launcher exit code 0")

        # A process that hosts no engine must never take a device.
        for name in ("frontend", "mocker"):
            log = service_log(name)
            require("Initializing backend" not in log
                    and "engine built" not in log,
                    f"the engine-less {name} process initialised a JAX "
                    "backend")
        say("  frontend and mocker worker initialised no JAX backend")
        worker = service_log("worker")
        plane = re.search(r"device transfer plane on \S+ \((\w+)", worker)
        require(plane, "the worker did not start its transfer plane")
        say(f"  worker transfer plane: {plane.group(0)})")
        return _engine_report(worker, platform, require_kernels,
                              second_start=True)


# ---------------------------------------------------------------------------
# In-process phases.  These import jax and run in a child of their own.


def _device(platform: str, count: int) -> dict:
    import jax

    devices = jax.devices()
    require(devices[0].platform == platform and len(devices) == count,
            f"needs exactly {count} {platform} device(s); JAX reports "
            f"{len(devices)} x {devices[0].platform}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def phase_probe(platform: str = "tpu", count: int = 1) -> dict:
    return _device(platform, count)


def _ragged_prompts(vocab: int, lengths):
    import numpy as np

    rng = np.random.default_rng(SEED)
    return [rng.integers(1, vocab, size=n).tolist() for n in lengths]


def _run_engine(core, prompts, max_tokens: int, tag: str = "r"):
    """Greedy-generate every prompt as request `tag<i>`; returns
    ({id: tokens}, {id: the f32 logits row that chose the first token})."""
    import numpy as np

    from dynamo_tpu.engine.sampling import SamplingParams

    logits = {}
    finish = core._finish_prefill_items

    # The one completion tail both prefill planes share: row i of its
    # `logits` belongs to items[i], at the chunk's last real token.
    def capture(items, rows, async_first):
        host = np.asarray(rows, dtype=np.float32)
        for i, work in enumerate(items):
            if work.start + work.length == len(work.request.prompt_tokens):
                logits[work.request.request_id] = host[i]
        return finish(items, rows, async_first)

    core._finish_prefill_items = capture
    for i, p in enumerate(prompts):
        core.add_request(f"{tag}{i}", p,
                         SamplingParams(max_tokens=max_tokens))
    tokens = {f"{tag}{i}": [] for i in range(len(prompts))}
    while core.has_work:
        for delta in core.step():
            tokens[delta.request_id].extend(delta.token_ids)
    core._finish_prefill_items = finish
    return tokens, logits


def _compare(name: str, ref, got, ref_core, prompts, atol: float) -> None:
    """`got` against `ref` ((tokens, logits) of _run_engine): logits
    inside `atol`, greedy tokens equal wherever the reference's own
    margin is outside 2 * atol."""
    import numpy as np

    (ref_tokens, ref_logits), (got_tokens, got_logits) = ref, got
    worst = 0.0
    flips = 0
    for i, prompt in enumerate(prompts):
        rid = f"r{i}"
        a, b = ref_logits[rid], got_logits[rid]
        require(a.shape == b.shape and np.isfinite(b).all(),
                f"{name} {rid}: logits {b.shape} not finite or misshapen")
        worst = max(worst, float(np.max(np.abs(a - b))))
        require(len(got_tokens[rid]) == len(ref_tokens[rid]),
                f"{name} {rid}: {len(got_tokens[rid])} tokens, expected "
                f"{len(ref_tokens[rid])}")
        diff = [j for j, (x, y) in enumerate(zip(ref_tokens[rid],
                                                 got_tokens[rid])) if x != y]
        if not diff:
            continue
        # Greedy streams part at the first differing token.  Re-derive the
        # reference logits there (a prefill of prompt + agreed tokens) and
        # allow the flip only inside the margin.
        j = diff[0]
        _, at = _run_engine(ref_core, [prompt + ref_tokens[rid][:j]], 1,
                            tag=f"{name} {rid} margin ")
        (row,) = at.values()
        margin = abs(float(row[ref_tokens[rid][j]])
                     - float(row[got_tokens[rid][j]]))
        require(margin <= 2 * atol,
                f"{name} {rid}: token {j} differs ({ref_tokens[rid][j]} vs "
                f"{got_tokens[rid][j]}) with reference margin {margin:.4f} "
                f"> {2 * atol}")
        flips += 1
    require(worst <= atol, f"{name}: max |logit difference| {worst:.4f} > "
            f"tolerance {atol}")
    say(f"  {name}: max |logit diff| {worst:.3g} (tolerance {atol}), "
        f"{len(prompts) - flips}/{len(prompts)} rows token-identical, "
        f"{flips} flipped inside the margin")


def phase_parity(model: str = "llama-3-1b", platform: str = "tpu",
                 kernels=None, lengths=(5, 17, 64, 100, 129, 300, 511, 700),
                 max_tokens: int = 9) -> dict:
    """Kernel planes against the plain XLA gather path, one process.
    `kernels=None` is the engine's `auto`; the CPU test passes True to
    run the kernels in interpret mode."""
    import jax

    from dynamo_tpu import native
    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.models import config as mcfg
    from dynamo_tpu.models.llama import init_params
    from dynamo_tpu.runtime.compile_cache import enable_compile_cache

    device = _device(platform, 1)
    say(f"  compile cache: {enable_compile_cache()}")
    say(f"  block hashing: {native.backend()}")
    cfg = mcfg.get_config(model)
    params = jax.jit(lambda: init_params(cfg, jax.random.key(SEED)))()
    prompts = _ragged_prompts(cfg.vocab_size, lengths)

    def engine(kv_quant: str, on) -> EngineCore:
        return EngineCore(EngineConfig(
            model=cfg, seed=SEED, kv_quant=kv_quant,
            use_pallas_decode=on, packed_prefill=on), params=params)

    for kv_quant, atol in (("none", LOGIT_ATOL), ("int8", LOGIT_ATOL_INT8)):
        gather = engine(kv_quant, False)
        ref = _run_engine(gather, prompts, max_tokens)
        kernel = engine(kv_quant, kernels)
        require(platform != "tpu" or (kernel._use_pallas
                                      and kernel._use_packed_prefill),
                "auto resolved a kernel plane off on the TPU")
        got = _run_engine(kernel, prompts, max_tokens)
        require(kernel.counters.packed_prefill_dispatches > 0
                and kernel.counters.window_dispatches > 0,
                "the kernel engine ran no packed prefill or no window")
        _compare(f"kv_quant={kv_quant} kernels vs gather", ref, got,
                 gather, prompts, atol)
        del gather, kernel
    return device


def phase_multichip(big: str = "llama-3-8b", small: str = "llama-3-1b",
                    platform: str = "tpu",
                    lengths=(5, 17, 64, 100, 129, 300, 511, 700),
                    max_tokens: int = 9) -> dict:
    """Four chips, one process: `big` at tp4 through the worker's
    `build_mesh` and the normal EngineCore, then `small` at tp4 against
    `small` on one chip."""
    import jax

    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.models import config as mcfg
    from dynamo_tpu.runtime.compile_cache import enable_compile_cache
    from dynamo_tpu.worker.main import build_mesh

    device = _device(platform, 4)
    say(f"  compile cache: {enable_compile_cache()}")
    mesh = build_mesh(argparse.Namespace(tp=4, dp=1, ep=1, sp=1, pp=1,
                                         num_processes=1))

    def tp4(model: str) -> EngineCore:
        return EngineCore(EngineConfig(model=mcfg.get_config(model),
                                       seed=SEED, mesh=mesh))

    core = tp4(big)
    # Born sharded: straight after construction no device holds more than
    # its share (memory_stats where the backend has them).
    stats = [d.memory_stats() for d in jax.devices()]
    require(platform != "tpu" or all(stats),
            "the TPU backend reported no memory_stats")
    if all(stats):
        used = [s["bytes_in_use"] for s in stats]
        peak = [s["peak_bytes_in_use"] for s in stats]
        say(f"  {big} tp4 bytes_in_use per device: {used}")
        say(f"  {big} tp4 peak_bytes_in_use per device: {peak}")
        others = sum(peak[1:]) / 3
        require(peak[0] <= 1.1 * others,
                f"device 0 peaked at {peak[0]} bytes, the others at "
                f"{others:.0f} on average: params or cache were not born "
                "sharded")
    cfg = core.config.model
    tokens, logits = _run_engine(
        core, _ragged_prompts(cfg.vocab_size, lengths[:4]), max_tokens)
    require(all(len(t) == max_tokens for t in tokens.values()),
            f"{big} tp4 did not answer every request: "
            f"{ {k: len(v) for k, v in tokens.items()} }")
    say(f"  {big} tp4: answered {len(tokens)} requests, "
        f"pallas_decode={bool(core._use_pallas)}")

    # The sharded decode step must hold its tensor-parallel collectives.
    import jax.numpy as jnp

    B, P = 4, 2
    rows = jnp.zeros((B,), jnp.int32)
    text = core._window_fn(True).lower(
        core.params, core.cache, rows, rows, rows,
        jnp.zeros((B, P), jnp.int32), jnp.zeros((B,), jnp.float32), rows,
        jnp.ones((B,), jnp.float32), jnp.zeros((B, 2), jnp.uint32),
        rows).compile().as_text()
    n_reduce = len(re.findall(r"\ball-reduce(?:-start)?\(", text))
    say(f"  {big} tp4 decode window: {n_reduce} all-reduce ops, "
        f"{text.count('tpu_custom_call')} kernel calls")
    require(n_reduce >= 2 * cfg.num_layers,
            f"expected at least {2 * cfg.num_layers} all-reduces (one per "
            f"attention and MLP block), found {n_reduce}")
    del core

    # tp4 against one chip, same seed and prompts.
    cfg = mcfg.get_config(small)
    prompts = _ragged_prompts(cfg.vocab_size, lengths)
    one = EngineCore(EngineConfig(model=cfg, seed=SEED))
    ref = _run_engine(one, prompts, max_tokens)
    four = tp4(small)
    require(platform != "tpu" or four._use_pallas,
            f"{small} tp4: the Pallas decode plane resolved off")
    got = _run_engine(four, prompts, max_tokens)
    _compare(f"{small} tp4 vs one chip", ref, got, one, prompts, LOGIT_ATOL)
    return device


# ---------------------------------------------------------------------------


def _run_phase_child(phase: str, workdir: str) -> dict:
    """Run an in-process phase in a child: its stdout streams through,
    its stderr (JAX's debug lines, a traceback) goes to a log file."""
    log_path = os.path.join(workdir, f"{phase}.log")
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--phase", phase],
            stdout=subprocess.PIPE, stderr=log, env=_child_env(), cwd=HERE,
            text=True)
        device = None
        try:
            for line in proc.stdout:
                if line.startswith("DEVICE "):
                    device = json.loads(line[len("DEVICE "):])
                else:
                    print(line, end="", flush=True)
        finally:
            if proc.poll() is None and sys.exc_info()[0] is not None:
                proc.kill()     # interrupted: do not leave it on the chip
            rc = proc.wait()
    with open(log_path, errors="replace") as f:
        tail = "\n".join(f.read().splitlines()[-30:])
    require(rc == 0, f"phase {phase} exited with code {rc}; log tail:\n"
            + tail)
    require(device is not None, f"phase {phase} reported no device")
    return device


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--multichip", action="store_true",
                   help="run only the four-chip phase (needs 4 TPU chips)")
    p.add_argument("--phase", choices=("probe", "parity", "multichip"),
                   help=argparse.SUPPRESS)   # the child side of a phase
    args = p.parse_args(argv)

    if args.phase:
        device = {"probe": phase_probe, "parity": phase_parity,
                  "multichip": phase_multichip}[args.phase]()
        say("DEVICE " + json.dumps(device))
        return 0

    workdir = os.path.join(HERE, "chiprun_out", "chip_smoke")
    os.makedirs(workdir, exist_ok=True)
    phases = (["multichip"] if args.multichip
              else ["probe", "serve", "graph", "parity"])
    devices = []
    try:
        for name in phases:
            say(f"== phase {name}")
            t0 = time.monotonic()
            if name == "serve":
                devices.append(phase_serve(workdir))
            elif name == "graph":
                devices.append(phase_graph(workdir))
            else:
                devices.append(_run_phase_child(name, workdir))
            say(f"== phase {name} passed in {time.monotonic() - t0:.0f}s")
        require(all(d == devices[0] for d in devices),
                f"phases disagree on the device: {devices}")
    except SmokeFailure as e:
        say(f"FAILED: {e}")
        return 1
    print(json.dumps({"ok": True, "device": devices[0]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
