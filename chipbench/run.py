"""One run of one cell of the benchmark:

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX.  It reads the cell's files, starts the served
system (control plane, an engine-less frontend, one engine worker that holds
the chip), offers open-loop traffic drawn from the seed, measures from the
client's side, reduces, prints one JSON line last and tears everything down.
See README.md for the layout and for what is printed before the last line."""

from __future__ import annotations

import argparse
import asyncio
import importlib.util
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import loadgen, pieces, traffic  # noqa: E402

READY_TIMEOUT_S = 1100.0
RUNS_DIR = os.path.join(ROOT, ".chipbench_runs")


class BenchFailure(Exception):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """A subprocess leading a process group of its own, output in a log
    file; leaving the `with` block kills the group and waits for it."""

    def __init__(self, name: str, args, log_dir: str, env: dict) -> None:
        self.name = name
        self.log_path = os.path.join(log_dir, name + ".log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, *args], stdout=self._log,
            stderr=subprocess.STDOUT, env=env, cwd=ROOT,
            start_new_session=True)

    def __enter__(self) -> "Child":
        return self

    def __exit__(self, *exc) -> None:
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()
        self._log.close()

    def tail(self, n: int = 30) -> str:
        with open(self.log_path, errors="replace") as f:
            return "\n".join(f.read().splitlines()[-n:])


def load_cell(workload: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json; "
                           f"have {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    mix = traffic.load_mix(os.path.join(HERE, "traffic"), cell["traffic"])
    with open(os.path.join(HERE, "cells", workload + ".json")) as f:
        params = json.load(f)
    try:
        pieces.named(config)
    except pieces.MissingPiece as e:
        raise BenchFailure(f"configuration {cfg_entry['name']!r}: {e}")
    return bench, cell, cfg_entry, config, mix, params


def metrics_for(bench: dict, workload: str, kind: str):
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def load_reader(kind: str, name: str):
    """The reader of one metric: `<kind>/<name>.py`, found by name."""
    return pieces.load(kind, name, HERE, needs=("read",))


def _child_env(extra: dict = None, cpu: bool = False) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (ROOT, env.get("PYTHONPATH")) if p)
    env["PYTHONUNBUFFERED"] = "1"
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    env.update(extra or {})
    return env


async def _wait_ready(port: int, model: str, children, timeout: float):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for c in children:
            if c.proc.poll() is not None:
                raise BenchFailure(
                    f"{c.name} exited with code {c.proc.returncode} before "
                    f"the model was served; log tail:\n{c.tail()}")
        try:
            status, raw = await loadgen.http_get(port, "/v1/models", 5.0)
            if status == 200 and model in {
                    m["id"] for m in json.loads(raw)["data"]}:
                return
        except (OSError, asyncio.TimeoutError, ValueError, KeyError):
            pass
        await asyncio.sleep(0.5)
    raise BenchFailure(f"model {model!r} not served within {timeout:.0f}s; "
                       f"worker log tail:\n{children[-1].tail()}")


async def _offer(port: int, model: str, prompt_of, reqs, t0: float):
    """Send each request at t0 + due_s; returns (records, tasks)."""
    records, tasks = [], []

    async def one(req, rec):
        delay = rec["due"] - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        await loadgen.stream_completion(port, model, prompt_of(req), rec)

    for req in reqs:
        rec = loadgen.new_record(req.index, t0 + req.due_s, req.n_in,
                                 req.n_out)
        records.append(rec)
        tasks.append(asyncio.create_task(one(req, rec)))
    return records, tasks


async def _sleep_until(t: float) -> None:
    delay = t - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)


async def _scrape_pair(ports) -> dict:
    fe, wk = await asyncio.gather(loadgen.scrape(ports["http"]),
                                  loadgen.scrape(ports["health"]))
    return {"frontend": fe, "worker": wk}


def _start_reduce(capture: dict, config: dict, cpu: bool):
    """The reduction runs in a child held to the CPU backend (this process
    stays off JAX), started as soon as the capture is on disk so that it is
    done by the time the window and its drain are."""
    return subprocess.Popen(
        [sys.executable, "-m", "chipbench.trace_reduce", capture["dir"],
         "--roles", json.dumps(config.get("programs", {})),
         "--kernels", json.dumps(config.get("kernels", {})),
         *(["--device-regex", "^/host:CPU$"] if cpu else [])],
        cwd=ROOT, env=_child_env(cpu=True), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)


async def _window(ports, model, prompt_of, mix, rate, seed, seconds, trace_ms,
                  drain_s, reduce_with=None):
    """Lead-in, measured window, drain.  Returns everything measured."""
    lead = float(mix.get("lead_in_s", 10.0))
    reqs = traffic.schedule(mix, rate, seed, lead, seconds)
    t0 = time.monotonic() + lead + 0.25
    records, tasks = await _offer(ports["http"], model, prompt_of, reqs, t0)
    scrapes = {}
    await _sleep_until(t0)
    scrapes["window_start"] = await _scrape_pair(ports)
    capture = None
    if trace_ms:
        await _sleep_until(t0 + seconds / 3.0)
        scrapes["capture_start"] = await _scrape_pair(ports)
        t_cap = time.monotonic()
        try:
            status, raw = await loadgen.http_get(
                ports["health"], f"/debug/deviceprofile?ms={trace_ms}",
                timeout=trace_ms / 1000.0 + 120.0)
            capture = json.loads(raw)
            capture["status"] = status
        except (OSError, asyncio.TimeoutError, ValueError) as e:
            capture = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        capture["t_start"], capture["t_end"] = t_cap, time.monotonic()
        scrapes["capture_end"] = await _scrape_pair(ports)
        if capture.get("ok") and reduce_with is not None:
            capture["reducer"] = _start_reduce(capture, *reduce_with)
    else:
        await _sleep_until(t0 + seconds / 2.0)
        scrapes["window_mid"] = await _scrape_pair(ports)
    await _sleep_until(t0 + seconds)
    end_scrape = asyncio.create_task(_scrape_pair(ports))
    if tasks:
        _done, pending = await asyncio.wait(tasks, timeout=drain_s)
        for t in pending:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    scrapes["window_end"] = await end_scrape
    return {"t0": t0, "seconds": seconds, "records": records,
            "scrapes": scrapes, "capture": capture, "rate": rate}


def _in_flight(records, t: float) -> int:
    return sum(1 for r in records
               if r["due"] <= t and (r["done"] is None or r["done"] > t))


def _summary(w) -> dict:
    """What the window looked like from the client, for the line printed
    before the result and for a sweep: the knee rule reads `failed`,
    `in_flight_mid` and `in_flight_end`."""
    from chipbench import stats

    t0, sec = w["t0"], w["seconds"]
    judged = [r for r in w["records"] if r["due"] >= t0]
    ttft = [(r["first"] - r["due"]) * 1e3 if r["ok"] and r["first"]
            else stats.MISS for r in judged]
    toks = sum(1 for r in w["records"] for t in r["token_times"]
               if t0 <= t < t0 + sec)
    return {"rate_rps": w["rate"], "attempted": len(judged),
            "failed": sum(1 for r in judged if not r["ok"]),
            "unfinished": sum(1 for r in judged if r["cancelled"]),
            "in_flight_mid": _in_flight(w["records"], t0 + sec / 2),
            "in_flight_end": _in_flight(w["records"], t0 + sec),
            "ttft_ms_mean": stats.mean(ttft),
            "ttft_ms_p50": stats.percentile(ttft, 50),
            "ttft_ms_p90": stats.percentile(ttft, 90),
            "itl_ms_p95": stats.percentile(
                [(b[0] - a[0]) * 1e3 for r in judged if r["ok"]
                 for a, b in zip(r["chunks"], r["chunks"][1:])], 95),
            "tokens_per_s": toks / sec,
            "late_ms_max": max(((r["sent"] - r["due"]) * 1e3
                                for r in judged if r["sent"]), default=None)}


def _reduce_trace(capture: dict, run_dir: str) -> dict:
    proc = capture.pop("reducer")
    try:
        out, err = proc.communicate(timeout=600)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        raise BenchFailure("trace reduction did not finish in 600 s")
    if proc.returncode != 0:
        raise BenchFailure("trace reduction failed:\n" + err[-2000:])
    reduced = json.loads(out)
    with open(os.path.join(run_dir, "trace_reduced.json"), "w") as f:
        json.dump(reduced, f)
    return reduced


def _finite(v):
    """JSON has no infinity: a tail that reached into the misses prints as
    1e18 (the run reports failed > 0 beside it)."""
    return 1e18 if math.isinf(v) else v


async def run(args) -> int:
    bench, cell, cfg_entry, config, mix, params = load_cell(args.workload)
    if importlib.util.find_spec("dynamo_tpu") is None:
        raise BenchFailure("the system under test (dynamo_tpu) is not in "
                           "this checkout")
    t_begin = time.monotonic()
    name = cfg_entry["name"]
    run_dir = os.path.join(RUNS_DIR, args.workload,
                           f"seed{args.seed}_trace{args.trace}")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    ports = {k: _free_port() for k in ("cp", "http", "health", "side")}
    result_file = os.path.join(run_dir, "child_result.json")
    override = dict(config.get("cpu_rehearsal", {})) if args.rehearse_cpu \
        else {}
    vocab = override.get("vocab_size", config["vocab_size"])
    reserved = config.get("reserved_token_ids", ())

    def prompt_of(req) -> str:
        return loadgen.prompt_text(traffic.prompt_ids(req, vocab, reserved))

    max_ctx = mix["input_tokens"]["max"] + mix["output_tokens"]["max"]
    trace_ms = int(mix.get("trace_ms", 3000)) if args.trace else 0
    worker_args = ["-m", "chipbench.serve_child",
                   "--config-file", os.path.join(ROOT, cfg_entry["file"]),
                   "--name", name, "--chips", str(cell["chips"]),
                   "--seed", str(args.seed % (2 ** 31)),
                   "--side-port", str(ports["side"]),
                   "--result-file", result_file,
                   "--max-context", str(max_ctx)]
    flags = list(config.get("engine_flags", []))
    if args.rehearse_cpu:
        worker_args += ["--allow-cpu", "--override", json.dumps(override),
                        "--check-lengths", "5,17,40"]
        flags = list(config.get("cpu_rehearsal_flags", flags))
    worker_args += ["--", "--control-plane", f"127.0.0.1:{ports['cp']}",
                    "--model", name, "--model-name", name,
                    "--health-port", str(ports["health"]),
                    "--flight-dump-dir", run_dir,
                    "--device-profile-max-ms", str(max(trace_ms, 1000)),
                    *flags]
    cpu_env = _child_env(cpu=True)
    worker_env = _child_env(
        dict(config.get("env") or {},
             JAX_DEBUG_LOG_MODULES="jax._src.compiler"),   # cache hits/misses
        cpu=args.rehearse_cpu)
    with Child("control_plane", ["-m", "dynamo_tpu.control_plane_service",
                                 "--port", str(ports["cp"])],
               run_dir, cpu_env) as cp, \
            Child("frontend", ["-m", "dynamo_tpu.frontend",
                               "--control-plane", f"127.0.0.1:{ports['cp']}",
                               "--http-port", str(ports["http"])],
                  run_dir, cpu_env) as fe, \
            Child("worker", worker_args, run_dir, worker_env) as wk:
        await _wait_ready(ports["http"], name, [cp, fe, wk], READY_TIMEOUT_S)
        t_ready = time.monotonic()
        with open(result_file) as f:
            child = json.load(f)
        device = child["device"]
        say(f"chipbench: ready after {t_ready - t_begin:.1f}s on {device}")
        say("chipbench: check " + json.dumps(child.get("check")))
        for warm in child.get("warmups") or []:
            say(f"chipbench: warm {warm['name']} " + json.dumps(warm))
        if not args.rehearse_cpu and device["platform"] != "tpu":
            raise BenchFailure(f"the worker runs on {device}, not a TPU")

        # The HTTP path, end to end, before any load: two short requests
        # whose usage must count exactly what was sent and asked for.
        probes = []
        for i, (n_in, n_out) in enumerate(((40, 9), (600, 17))):
            req = traffic.Request(-1 - i, 0.0, n_in, n_out, 12345 + i)
            rec = loadgen.new_record(req.index, time.monotonic(), n_in, n_out)
            await loadgen.stream_completion(ports["http"], name,
                                            prompt_of(req), rec)
            probes.append(rec)
        probe_ok = all(r["ok"] for r in probes)
        say("chipbench: http probe " + json.dumps(
            [{k: r[k] for k in ("ok", "error", "prompt_tokens",
                                "completion_tokens")} for r in probes]))

        rate = float(params["rate_rps"])
        drain_s = float(mix.get("drain_s", 30.0))
        if args.sweep:
            for item in args.sweep.split(","):
                r, _, sd = item.partition(":")
                w = await _window(ports, name, prompt_of, mix, float(r),
                                  int(sd) if sd else args.seed,
                                  args.seconds, 0, 120.0)
                say("chipbench: sweep " + json.dumps(
                    dict(_summary(w), seed=int(sd) if sd else args.seed)))
            return 0
        w = await _window(ports, name, prompt_of, mix, rate, args.seed,
                          args.seconds, trace_ms, drain_s,
                          reduce_with=(config, args.rehearse_cpu))
        setup_s = w["t0"] - t_begin
        try:
            _s, raw = await loadgen.http_get(ports["side"], "/mem", 10.0)
            mem = json.loads(raw)
        except (OSError, asyncio.TimeoutError, ValueError):
            mem = {}
        final = await _scrape_pair(ports)
    # Every process of the served system has ended here.

    scr = w["scrapes"]
    trace = None
    if args.trace:
        cap = w["capture"] or {}
        if not cap.get("ok") or "reducer" not in cap:
            raise BenchFailure(f"device capture failed: {cap}")
        trace = _reduce_trace(cap, run_dir)
        shutil.rmtree(cap["dir"], ignore_errors=True)

    from chipbench import model_bytes

    peaks = None
    if device["platform"] == "tpu":
        peaks = model_bytes.peaks_for(device["kind"])
    judged = [r for r in w["records"] if r["due"] >= w["t0"]]
    ctx = types.SimpleNamespace(
        records=judged, all_records=w["records"], t0=w["t0"],
        seconds=w["seconds"], setup_s=setup_s, scrapes=scr, final=final,
        trace=trace, capture=w["capture"], config=config, mix=mix,
        cell=cell, params=params, peaks=peaks, mem=mem, child=child)

    def delta(source, key, scope="window"):
        a = scr.get(f"{scope}_start", {}).get(source)
        b = scr.get(f"{scope}_end", {}).get(source)
        if not a or not b or key not in a or key not in b:
            return None
        return b[key] - a[key]

    ctx.delta = delta
    compiles = delta("worker", "dynamo_worker_engine_xla_cache_misses")
    # One rule: a request that did not finish with exactly its tokens,
    # the drain included, has failed.
    failed = [r for r in judged if not r["ok"]]
    for r in failed[:5]:
        say(f"chipbench: failed request {r['index']}: {r['error']}")
    summary = _summary(w)
    say("chipbench: window " + json.dumps(summary))
    say(f"chipbench: compiles inside the window: {compiles}")
    new_shapes = sorted(set(mem.get("shapes", ()))
                        - set(child.get("shapes_after_warm", ())))
    if new_shapes:
        say("chipbench: shapes first dispatched after warm-up (lead-in, "
            f"window or drain): {new_shapes}")
    with open(os.path.join(run_dir, "scrapes.json"), "w") as f:
        json.dump({"scrapes": scr, "final": final, "mem": mem,
                   "capture": w["capture"]}, f)
    with open(os.path.join(run_dir, "records.json"), "w") as f:
        json.dump({"summary": summary, "records": [
            dict({k: v for k, v in r.items()
                  if k not in ("token_times", "chunks")},
                 chunks=[[round(t - w["t0"], 4), n] for t, n in r["chunks"]])
            for r in w["records"]], "t0": w["t0"]}, f)

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metrics_for(bench, args.workload, kind):
        value = load_reader("layer_metrics" if args.trace else "end_to_end",
                            m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": _finite(float(value)),
                                  "unit": m["unit"]}
    check = child.get("check") or {}
    correct = bool(
        not args.rehearse_cpu and device["platform"] == "tpu"
        and check.get("ok") and probe_ok and compiles == 0 and not failed)
    # Every number `correct` rests on beside its limit, last on standard
    # error: what the driver's record keeps of a run that is not correct.
    compared = [(i["name"], i["value"], i["limit"])
                for i in check.get("limits") or []]
    compared += [("http_probes_failed", sum(not r["ok"] for r in probes), 0),
                 ("programs_first_built_in_window", compiles, 0),
                 ("failed_requests", len(failed), 0)]
    for problem in check.get("problems", ["no check ran"]):
        print(f"chipbench: check problem: {problem}", file=sys.stderr)
    for name, value, limit in compared:
        print(f"chipbench: compared {name} {value!r} limit {limit!r}",
              file=sys.stderr)
    sys.stderr.flush()
    out = {"correct": correct, "attempted": len(judged),
           "failed": len(failed), "metrics": metrics,
           "device": {"platform": device["platform"], "kind": device["kind"],
                      "count": device["count"],
                      "memory_peak_bytes": mem.get("peak_bytes_in_use", 0)}}
    if args.rehearse_cpu:
        out["metrics"] = {}     # no device metric is printed from a CPU run
        out["cpu_rehearsal"] = {k: v["value"] for k, v in metrics.items()}
    elif trace is not None:
        out["device"]["busy_s"] = trace["busy_s"]
        out["device"]["window_s"] = trace["window_s"]
        out["breakdown"] = {"device_ops": trace["device_ops"][:10],
                            "idle_gaps": trace["idle_gaps"][:10]}
    print(json.dumps(out), flush=True)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser("chipbench.run")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="tiny widths on the CPU backend: control flow only; "
                        "prints no device metric and `correct` is false")
    p.add_argument("--sweep", default="",
                   help="comma-separated rates (or rate:seed): one window "
                        "each inside one server start, a line per rate, no "
                        "result line")
    args = p.parse_args(argv)
    try:
        return asyncio.run(run(args))
    except BenchFailure as e:
        print(f"chipbench: FAILED: {e}", file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main())
