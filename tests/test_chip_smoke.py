"""chip_smoke.py's phases at `tiny-test` size on the CPU.

The smoke proves the serving path on the chip; these tests keep its own
control flow honest without one: every phase function runs end to end
here (servers as real child processes, the in-process phases in children
of their own with the device count they demand), and the default command
must fail, fast and without a result line, where JAX finds no TPU.
"""

import os
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = dict(model="tiny-test", platform="cpu", require_kernels=False,
            prompt_bytes=(20, 300), max_tokens=(8, 16), ready_timeout=120.0)
LENGTHS = (5, 17, 64, 129, 300)


def _env(devices: int) -> dict:
    return dict(chip_smoke._child_env(), JAX_PLATFORMS="cpu",
                XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")


def _spawn(code: str, devices: int) -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, "-c", "import chip_smoke as cs; " + code],
        env=_env(devices), cwd=REPO, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)


@pytest.fixture(scope="module")
def phases(tmp_path_factory):
    """All four phases started together (they are separate processes
    anyway, and the suite's time limit is tight): the two server phases
    on threads of this process, the two jax-importing phases in children
    of their own with the device count they demand."""
    procs = {
        "parity": _spawn(
            "print(cs.phase_parity(model='tiny-test', platform='cpu', "
            f"kernels=True, lengths={LENGTHS}))", devices=1),
        "multichip": _spawn(
            "print(cs.phase_multichip(big='tiny-test', small='tiny-test', "
            f"platform='cpu', lengths={LENGTHS}))", devices=4),
    }
    servers = {"serve": chip_smoke.phase_serve,
               "graph": chip_smoke.phase_graph}
    with ThreadPoolExecutor(max_workers=2) as pool:
        futures = {name: pool.submit(fn, str(tmp_path_factory.mktemp(name)),
                                     **TINY)
                   for name, fn in servers.items()}
        yield {**procs, **futures}
    for p in procs.values():
        if p.poll() is None:
            p.kill()
        p.communicate()


@pytest.mark.parametrize("name", ["serve", "graph"])
def test_server_phase_tiny_on_cpu(phases, name):
    """`serve`: the README server; `graph`: launcher + engine-less
    frontend + mocker worker + real worker.  A failed check raises."""
    assert phases[name].result(timeout=300)["platform"] == "cpu"


def test_parity_phase_tiny_on_cpu(phases):
    """Kernels in interpret mode against the gather path, bf16 and int8."""
    out, _ = phases["parity"].communicate(timeout=300)
    assert phases["parity"].returncode == 0, out[-3000:]
    assert "kv_quant=none kernels vs gather" in out
    assert "kv_quant=int8 kernels vs gather" in out
    assert "{'platform': 'cpu', 'kind': 'cpu', 'count': 1}" in out


def test_multichip_phase_on_four_cpu_devices(phases):
    out, _ = phases["multichip"].communicate(timeout=300)
    assert phases["multichip"].returncode == 0, out[-3000:]
    assert "tp4 vs one chip" in out and "all-reduce ops" in out
    assert "{'platform': 'cpu', 'kind': 'cpu', 'count': 4}" in out


def test_child_exit_kills_the_whole_process_group(tmp_path):
    """A launcher killed after a failed check must not leave services
    behind: leaving the `with` block ends the child's whole group."""
    pid_file = tmp_path / "grandchild.pid"
    code = ("import subprocess, sys, time; "
            "p = subprocess.Popen([sys.executable, '-c', "
            "'import time; time.sleep(600)']); "
            f"open({str(pid_file)!r}, 'w').write(str(p.pid)); "
            "time.sleep(600)")
    with chip_smoke.Child(["-c", code], str(tmp_path / "child.log")):
        for _ in range(100):
            if pid_file.exists() and pid_file.read_text():
                break
            time.sleep(0.05)
        grandchild = int(pid_file.read_text())
    for _ in range(100):
        try:
            os.kill(grandchild, 0)
        except ProcessLookupError:
            return
        # A zombie still answers signal 0 until init reaps it.
        with open(f"/proc/{grandchild}/stat") as f:
            if f.read().split()[2] == "Z":
                return
        time.sleep(0.05)
    pytest.fail(f"grandchild {grandchild} survived its group's kill")


def test_compare_allows_a_flip_only_inside_the_margin():
    """A differing greedy token passes only where the reference's own
    margin between the two candidates is within twice the tolerance."""
    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.models import config as mcfg

    core = EngineCore(EngineConfig(model=mcfg.get_config("tiny-test")))
    prompts = chip_smoke._ragged_prompts(256, (12,))
    tokens, logits = chip_smoke._run_engine(core, prompts, 4)
    (row,) = logits.values()
    assert row.shape == (256,) and tokens["r0"][0] == int(np.argmax(row))
    # The same run "flipped" at token 2 to the reference's runner-up.
    _, at = chip_smoke._run_engine(core, [prompts[0] + tokens["r0"][:2]], 1,
                                   tag="probe")
    (nxt,) = at.values()
    runner_up = int(np.argsort(nxt)[-2])
    margin = float(nxt[tokens["r0"][2]] - nxt[runner_up])
    flipped = {"r0": tokens["r0"][:2] + [runner_up] + tokens["r0"][3:]}
    chip_smoke._compare("wide", (tokens, logits), (flipped, logits), core,
                        prompts, atol=margin)
    with pytest.raises(chip_smoke.SmokeFailure, match="reference margin"):
        chip_smoke._compare("tight", (tokens, logits), (flipped, logits),
                            core, prompts, atol=margin / 4)


def test_default_command_fails_without_a_tpu():
    """`python chip_smoke.py` under JAX_PLATFORMS=cpu: non-zero exit, no
    `"ok": true`, and the probe ends it before any server starts."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO, text=True,
        capture_output=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "== phase serve" not in proc.stdout
