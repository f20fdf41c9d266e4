"""The program store (dynamo_tpu/runtime/program_store.py): compiled step
programs found again by shape, without a trace.  CPU, temporary
directories; JAX's own persistent cache is off around every test that
writes, because XLA:CPU cannot serialize an executable it read from there
(the store's guard for that has a test of its own)."""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import SchedulerConfig
from dynamo_tpu.models.config import PRESETS
from dynamo_tpu.runtime import compile_cache, program_store
from dynamo_tpu.runtime.program_store import (
    ProgramStore, StoredProgram, stored)


@pytest.fixture
def fresh_compiles():
    """Every compile goes through the compiler, none through JAX's cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _counts():
    b = compile_cache.program_builds()
    return dict({k: b["program_store"][k]
                 for k in ("hits", "misses", "errors")},
                read_s=b["seconds"]["store_read"])


def _delta(before):
    now = _counts()
    return {k: now[k] - before[k] for k in now}


def _step(params, cache, x):
    y = x @ params["w"][0]
    return y.sum(-1), {"k": cache["k"].at[0].set(y[0])}


def _args(rows=2, dtype=jnp.float32):
    return ({"w": [jnp.full((8, 8), 0.5, dtype)]},
            {"k": jnp.zeros((4, 8), dtype)},
            jnp.arange(rows * 8, dtype=dtype).reshape(rows, 8))


BUILD = {"model": "m", "block_size": 8, "decode_window": 8,
         "use_pallas_decode": False, "greedy_only": True,
         "moe_mode": "dense", "with_expert_load": False,
         "kv_quant": False, "cache_dtype": "float32"}


def _fresh_step():
    """`_step` as a function JAX has never seen: its in-memory caches of
    traces and lowerings are keyed by the function."""
    def step(params, cache, x):
        return _step(params, cache, x)
    return step


def _wrap(store, build=BUILD, name="step", step=_step):
    return stored(jax.jit(step, donate_argnums=(1,)), name,
                  json.dumps(build, sort_keys=True), store,
                  fixed_argnums=2)


def _entries(store):
    return sorted(os.listdir(store.dir)) if os.path.isdir(store.dir) else []


def test_miss_writes_then_a_fresh_wrapper_hits_with_donation(
        tmp_path, fresh_compiles):
    want, _ = jax.jit(_step)(*_args())
    before = _counts()
    params, cache, x = _args()
    out, new_cache = _wrap(ProgramStore(str(tmp_path)))(params, cache, x)
    assert _delta(before) == {"hits": 0, "misses": 1, "errors": 0,
                              "read_s": 0.0}
    assert cache["k"].is_deleted()
    np.testing.assert_array_equal(out, want)

    store = ProgramStore(str(tmp_path))
    assert len(_entries(store)) == 1
    fn = _wrap(store)
    before = _counts()
    params, cache, x = _args()
    out2, cache2 = fn(params, cache, x)
    d = _delta(before)
    assert (d["hits"], d["misses"], d["errors"]) == (1, 0, 0)
    assert d["read_s"] > 0.0
    assert cache["k"].is_deleted()
    np.testing.assert_array_equal(out2, want)
    np.testing.assert_array_equal(cache2["k"], new_cache["k"])
    # Every later call: the dict, no disk, no count.
    before = _counts()
    out3, _ = fn(params, cache2, x)
    assert _delta(before) == {"hits": 0, "misses": 0, "errors": 0,
                              "read_s": 0.0}
    np.testing.assert_array_equal(out3, want)
    # A numpy argument of the same form is the same program.
    out4, _ = fn(params, _args()[1], np.asarray(x))
    np.testing.assert_array_equal(out4, want)
    assert len(_entries(store)) == 1


def _change_build(field):
    def change(monkeypatch):
        value = BUILD[field]
        return {"build": dict(BUILD, **{
            field: (not value) if isinstance(value, bool)
            else value + 1 if isinstance(value, int) else value + "x"})}
    return change


def _change_fingerprint(what):
    def change(monkeypatch):
        if what == "source":
            monkeypatch.setattr(program_store, "source_digest",
                                lambda: "another tree")
        elif what in ("jax", "jaxlib"):
            import jaxlib

            monkeypatch.setattr({"jax": jax, "jaxlib": jaxlib}[what],
                                "__version__", "0.0.1")
        else:
            monkeypatch.setenv(what, "--some_flag=1")
        return {}
    return change


KEY_PARTS = {
    **{f"build.{f}": _change_build(f) for f in BUILD},
    **{w: _change_fingerprint(w)
       for w in ("source", "jax", "jaxlib", "LIBTPU_INIT_ARGS")},
    "name": lambda monkeypatch: {"name": "window"},
    "shape": lambda monkeypatch: {"args": {"rows": 3}},
    "dtype": lambda monkeypatch: {"args": {"dtype": jnp.bfloat16}},
}


@pytest.mark.parametrize("part", sorted(KEY_PARTS))
def test_each_part_of_the_key_misses_when_it_changes(
        part, tmp_path, monkeypatch, fresh_compiles):
    _wrap(ProgramStore(str(tmp_path)))(*_args())
    # Unchanged, a fresh store and wrapper hit ...
    before = _counts()
    _wrap(ProgramStore(str(tmp_path)))(*_args())
    assert _delta(before)["hits"] == 1
    # ... and with this one part changed they do not.
    changed = KEY_PARTS[part](monkeypatch)
    before = _counts()
    fn = _wrap(ProgramStore(str(tmp_path)),
               build=changed.get("build", BUILD),
               name=changed.get("name", "step"))
    out, _ = fn(*_args(**changed.get("args", {})))
    d = _delta(before)
    assert (d["hits"], d["misses"], d["errors"]) == (0, 1, 0)
    np.testing.assert_array_equal(
        out, jax.jit(_step)(*_args(**changed.get("args", {})))[0])


def test_the_fingerprint_holds_what_the_issue_lists(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", "") + " ")
    fp = program_store.environment_fingerprint()
    assert {"source", "jax", "jaxlib", "platform_version", "device_kind",
            "LIBTPU_INIT_ARGS", "XLA_FLAGS", "host_cpu"} <= set(fp)
    assert fp["XLA_FLAGS"].endswith(" ")
    # By content and by the path inside the package, not by where the
    # checkout is or when it was written.
    assert program_store.source_digest() == fp["source"]


def _copy_tree(src, dst):
    import shutil

    shutil.copytree(src, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "*.pyc"))


def test_source_digest_follows_content_not_place_or_time(tmp_path):
    pkg = os.path.dirname(os.path.dirname(program_store.__file__))
    sub = os.path.join(pkg, "runtime")
    a, b = str(tmp_path / "a"), str(tmp_path / "elsewhere" / "b")
    _copy_tree(sub, a)
    _copy_tree(sub, b)
    os.utime(os.path.join(b, "program_store.py"), (1, 1))
    assert (program_store.source_digest(a) == program_store.source_digest(b)
            == program_store.source_digest(sub))
    with open(os.path.join(b, "program_store.py"), "a") as f:
        f.write("\n# one edit\n")
    assert program_store.source_digest(a) != program_store.source_digest(b)


@pytest.mark.parametrize("damage", ["truncated", "garbage", "empty",
                                    "other_fingerprint", "other_key"])
def test_an_entry_that_cannot_be_trusted_is_recompiled_and_overwritten(
        damage, tmp_path, monkeypatch, fresh_compiles):
    store = ProgramStore(str(tmp_path))
    want, _ = _wrap(store)(*_args())
    (name,) = _entries(store)
    path = os.path.join(store.dir, name)
    with open(path, "rb") as f:
        whole = f.read()
    if damage == "truncated":
        bad = whole[:len(whole) - 100]
    elif damage == "garbage":
        bad = whole[:200] + bytes(len(whole) - 200)
    elif damage == "empty":
        bad = b""
    elif damage == "other_fingerprint":
        # A file of another fingerprint's directory, copied into ours.
        monkeypatch.setenv("LIBTPU_INIT_ARGS", "--another=1")
        other = ProgramStore(str(tmp_path))
        _wrap(other)(*_args())
        monkeypatch.delenv("LIBTPU_INIT_ARGS")
        with open(os.path.join(other.dir, _entries(other)[0]), "rb") as f:
            bad = f.read()
    else:
        _wrap(store, name="window")(*_args())
        (second,) = set(_entries(store)) - {name}
        with open(os.path.join(store.dir, second), "rb") as f:
            bad = f.read()
    with open(path, "wb") as f:
        f.write(bad)

    before = _counts()
    out, _ = _wrap(ProgramStore(str(tmp_path)))(*_args())
    d = _delta(before)
    assert (d["hits"], d["misses"], d["errors"]) == (0, 1, 1)
    np.testing.assert_array_equal(out, want)
    with open(path, "rb") as f:             # overwritten, whole again
        header, payload = program_store.split_entry(f.read())
    assert header["key"] == program_store.split_entry(whole)[0]["key"]
    assert len(payload) == header["payload_bytes"] > 0
    before = _counts()
    _wrap(ProgramStore(str(tmp_path)))(*_args())
    assert _delta(before)["hits"] == 1


def test_an_entry_that_does_not_load_never_raises_into_the_call(
        tmp_path, monkeypatch, fresh_compiles):
    store = ProgramStore(str(tmp_path))
    want, _ = _wrap(store)(*_args())

    def refuse(payload, devices):
        raise RuntimeError("the runtime refuses this executable")

    monkeypatch.setattr(program_store, "_load", refuse)
    before = _counts()
    out, _ = _wrap(ProgramStore(str(tmp_path)))(*_args())
    d = _delta(before)
    assert (d["hits"], d["misses"], d["errors"]) == (0, 1, 1)
    np.testing.assert_array_equal(out, want)


def test_a_directory_that_cannot_be_written_serves_all_the_same(
        tmp_path, fresh_compiles):
    blocked = tmp_path / "cache"
    blocked.write_text("a file where the directory should be")
    assert program_store.open_store(str(blocked)) is not None
    before = _counts()
    out, _ = _wrap(ProgramStore(str(blocked)))(*_args())
    d = _delta(before)
    assert (d["hits"], d["misses"]) == (0, 1) and d["errors"] >= 1
    np.testing.assert_array_equal(out, jax.jit(_step)(*_args())[0])


def test_two_writers_of_one_entry_leave_one_whole_file(tmp_path):
    store = ProgramStore(str(tmp_path))
    key = {"name": "step", "call": "x"}
    payloads = [bytes([i]) * 300_000 for i in range(8)]
    start = threading.Barrier(len(payloads))

    def write(p):
        start.wait(timeout=10)
        for _ in range(5):
            ProgramStore(str(tmp_path)).write(key, p, None)

    threads = [threading.Thread(target=write, args=(p,)) for p in payloads]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    assert _entries(store) == [os.path.basename(store.path_for(key))]
    before = _counts()
    payload, cost = store.read(key)
    assert payload in payloads and cost is None
    assert _delta(before)["errors"] == 0


def test_the_fifth_fingerprint_evicts_the_oldest_and_only_that(
        tmp_path, monkeypatch):
    dirs = []
    for i in range(5):
        monkeypatch.setenv("LIBTPU_INIT_ARGS", f"--fingerprint={i}")
        store = ProgramStore(str(tmp_path))
        if i < 4:
            store.write({"n": i}, b"x", None)
            os.utime(store.dir, (1000 + i, 1000 + i))
        dirs.append(store.dir)
    root = os.path.join(str(tmp_path), program_store.SUBDIR)
    # Opening the fifth store evicts nothing; using the oldest again
    # makes the second oldest the one to go.
    assert len(os.listdir(root)) == 4
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "--fingerprint=0")
    ProgramStore(str(tmp_path))
    assert os.path.getmtime(dirs[0]) > 2000
    store.write({"n": 4}, b"x", None)
    assert sorted(os.listdir(root)) == sorted(
        os.path.basename(d) for d in (dirs[0], dirs[2], dirs[3], dirs[4]))
    assert os.listdir(dirs[0])              # the others keep their files


def test_what_is_not_a_plain_jit_object_passes_through(tmp_path,
                                                       monkeypatch):
    store = ProgramStore(str(tmp_path))

    def plain(*args):                       # pp stage programs: no .lower
        return args

    jitted = jax.jit(_step)
    assert stored(plain, "step", "k", store) is plain
    assert stored(jitted, "step", "k", None) is jitted
    assert isinstance(stored(jitted, "step", "k", store), StoredProgram)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    assert stored(jitted, "step", "k", store) is jitted


def test_sharded_arguments_are_left_to_the_jit_object(tmp_path):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    x = jax.device_put(jnp.ones((4, 8)), NamedSharding(
        mesh, PartitionSpec("x")))
    store = ProgramStore(str(tmp_path))
    fn = stored(jax.jit(lambda a: a * 2), "double", "k", store)
    before = _counts()
    np.testing.assert_array_equal(fn(x), np.full((4, 8), 2.0))
    assert _delta(before) == {"hits": 0, "misses": 0, "errors": 0,
                              "read_s": 0.0}
    assert _entries(store) == []


def test_cpu_executables_read_from_jaxs_cache_are_not_stored(tmp_path):
    """XLA:CPU serializes such an executable without its kernels.  The
    first build of a program compiles and is stored; the second, of a
    function object of its own so that no jit's memory serves it, is
    read from JAX's cache and is not.  The program holds a constant no
    run before this one had, so what the suite's shared cache holds does
    not decide which build is which."""
    scale = 1.0 + (time.time_ns() % 10**9) / 10**9

    def build():
        def unique(params, cache, x):
            return _step(params, cache, x * scale)

        store = ProgramStore(str(tmp_path))
        fn = stored(jax.jit(unique, donate_argnums=(1,)), "step", "k",
                    store, fixed_argnums=2)
        hits = compile_cache.cache_hits_on_this_thread()
        out, _ = fn(*_args())
        np.testing.assert_allclose(out, _step(*_args())[0] * scale,
                                   rtol=1e-5)
        return store, compile_cache.cache_hits_on_this_thread() - hits

    store, _ = build()
    assert len(_entries(store)) == 1
    for name in _entries(store):
        os.unlink(os.path.join(store.dir, name))
    store, hits = build()
    assert hits > 0, "JAX's persistent cache did not serve the second build"
    assert _entries(store) == []


# -- one tiny engine -------------------------------------------------------


def _serve(store, join=False, **cfg):
    core = EngineCore(EngineConfig(
        model=PRESETS["tiny-test"], num_blocks=64, program_store=store,
        scheduler=SchedulerConfig(block_size=8), **cfg))
    if join:
        core.join_read_ahead()
    for i in range(3):
        core.add_request(f"r{i}", [1 + i, 2, 3, 4, 5, 6, 7][:4 + i],
                         SamplingParams(max_tokens=12))
    out = {}
    while core.has_work:
        for d in core.step():
            out.setdefault(d.request_id, []).extend(d.token_ids)
    return core, out


def test_an_engine_starts_from_the_store_and_answers_the_same(
        tmp_path, monkeypatch, fresh_compiles):
    _plain_core, want = _serve(None)
    assert not isinstance(_plain_core._step, StoredProgram)

    before = _counts()
    core, got = _serve(ProgramStore(str(tmp_path)))
    first = _delta(before)
    assert got == want
    assert first["misses"] >= 2 and first["hits"] == first["errors"] == 0
    for fn in (core._step, core._window_fn(True), core._window_fn(False),
               core._greedy_step_fn()):
        assert isinstance(fn, StoredProgram)
    names = {program_store.split_entry(blob)[0]["key"]["name"]
             for blob in _entry_blobs(core.config.program_store)}
    assert names <= {"step", "window", "greedy_step", "packed_prefill"}
    assert len(names) >= 2

    before = _counts()
    core, got = _serve(ProgramStore(str(tmp_path)))
    second = _delta(before)
    assert got == want
    assert (second["hits"], second["misses"], second["errors"]) \
        == (first["misses"], 0, 0)

    # Another engine geometry must not load these programs.
    before = _counts()
    _serve(ProgramStore(str(tmp_path)), decode_window=4)
    third = _delta(before)
    assert third["misses"] >= 1 and third["errors"] == 0
    # A changed source tree, LIBTPU_INIT_ARGS: cold, and still right.
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "--xla_some_limit=1")
    before = _counts()
    _core, got = _serve(ProgramStore(str(tmp_path)))
    assert got == want and _delta(before)["hits"] == 0


def _entry_blobs(store):
    for name in _entries(store):
        with open(os.path.join(store.dir, name), "rb") as f:
            yield f.read()


def test_the_engines_build_key_names_every_build_argument(tmp_path):
    core = EngineCore(EngineConfig(
        model=PRESETS["tiny-test"], num_blocks=64,
        program_store=ProgramStore(str(tmp_path)),
        scheduler=SchedulerConfig(block_size=8)))
    build = json.loads(core._window_fn(True)._build_key)
    assert set(build) == {"model", "block_size", "decode_window",
                          "use_pallas_decode", "greedy_only", "moe_mode",
                          "with_expert_load", "kv_quant", "cache_dtype"}
    assert build["model"] == repr(PRESETS["tiny-test"])
    assert build["greedy_only"] is True
    assert json.loads(core._window_fn(False)._build_key)["greedy_only"] \
        is False
    assert core._step._name != core._greedy_step_fn()._name


@pytest.mark.parametrize("mesh_kind", ["tp", "pp"])
def test_mesh_and_pp_engines_keep_their_own_programs(mesh_kind, tmp_path):
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(**{mesh_kind: 2}), jax.devices()[:2])
    store = ProgramStore(str(tmp_path))
    core = EngineCore(EngineConfig(
        model=PRESETS["tiny-test"], num_blocks=64, mesh=mesh,
        program_store=store, scheduler=SchedulerConfig(block_size=8)))
    for fn in (core._step, core._window_fn(True), core._greedy_step_fn()):
        assert not isinstance(fn, StoredProgram)
    assert _entries(store) == []


@pytest.fixture
def lowerings():
    """The jaxpr-to-MLIR lowerings JAX has run since the test began."""
    from jax._src import monitoring

    seen = []

    def on(event, duration_secs, **_kw):
        if event.endswith("jaxpr_to_mlir_module_duration"):
            seen.append(duration_secs)

    monitoring.register_event_duration_secs_listener(on)
    yield seen
    monitoring.unregister_event_duration_listener(on)


def test_harvest_takes_the_cost_from_the_store_and_lowers_once(
        tmp_path, fresh_compiles, lowerings):
    from dynamo_tpu.runtime.device_profiler import DeviceProfiler

    want = jax.jit(_step).lower(*_args()).cost_analysis()
    for expect_hit in (False, True):
        fn = _wrap(ProgramStore(str(tmp_path)), step=_fresh_step())
        prof = DeviceProfiler(enabled=True)
        args = _args()
        del lowerings[:]
        before = _counts()
        assert prof.harvest("step", (2,), fn, args)
        (_label, rec), = prof.registry.items()
        assert rec["flops"] == pytest.approx(want["flops"])
        assert rec["bytes_accessed"] == pytest.approx(
            want["bytes accessed"])
        assert not args[1]["k"].is_deleted()    # nothing ran, nothing donated
        fn(*args)                               # the dispatch that follows
        assert args[1]["k"].is_deleted()
        d = _delta(before)
        assert (d["hits"], d["misses"]) == ((1, 0) if expect_hit else (0, 1))
        assert len(lowerings) == (0 if expect_hit else 1)


def test_a_miss_costs_what_the_jit_call_costs(tmp_path, fresh_compiles,
                                               lowerings):
    """One lowering, one compile: what is written is what the call built,
    read back from JAX's in-memory caches."""
    builds = compile_cache.program_builds()["builds"]
    store = ProgramStore(str(tmp_path))
    fn = _wrap(store, step=_fresh_step())
    fn(*_args())
    assert len(lowerings) == 1
    assert compile_cache.program_builds()["builds"] - builds == 1
    assert len(_entries(store)) == 1
    fn(*_args())                                # in memory: the jit object
    assert len(lowerings) == 1


def test_an_entry_written_by_a_plain_call_answers_the_harvest_by_lowering(
        tmp_path, fresh_compiles):
    """Nobody had asked that call for the analysis, so the entry holds
    none; the harvest then lowers, as it did before there was a store."""
    want = jax.jit(_step).lower(*_args()).cost_analysis()
    _wrap(ProgramStore(str(tmp_path)))(*_args())
    fn = _wrap(ProgramStore(str(tmp_path)))
    before = _counts()
    cost = fn.cost_analysis(*_args())
    assert _delta(before)["hits"] == 1
    assert cost["flops"] == pytest.approx(want["flops"])
    assert fn.cost_analysis(*_args()) is cost


@pytest.mark.parametrize("writer,reader", [("zstd", "zlib"), ("zlib", "zstd"),
                                           ("zlib", "zlib")])
def test_entries_are_compressed_with_what_the_installation_has(
        writer, reader, tmp_path, monkeypatch, fresh_compiles):
    zstd = program_store.zstandard
    if zstd is None:
        pytest.skip("no zstandard in this installation")
    have = {"zstd": zstd, "zlib": None}
    monkeypatch.setattr(program_store, "zstandard", have[writer])
    store = ProgramStore(str(tmp_path))
    want, _ = _wrap(store)(*_args())
    with open(os.path.join(store.dir, _entries(store)[0]), "rb") as f:
        _header, payload = program_store.split_entry(f.read())
    assert payload[:1] == {"zstd": b"Z", "zlib": b"z"}[writer]
    monkeypatch.setattr(program_store, "zstandard", have[reader])
    before = _counts()
    out, _ = _wrap(ProgramStore(str(tmp_path)))(*_args())
    d = _delta(before)
    # Without zstandard a zstandard entry is one more that does not load.
    assert (d["hits"], d["misses"], d["errors"]) == (
        (0, 1, 1) if (writer, reader) == ("zstd", "zlib") else (1, 0, 0))
    np.testing.assert_array_equal(out, want)


def test_no_analysis_is_an_answer_that_is_stored_too(
        tmp_path, monkeypatch, fresh_compiles, lowerings):
    """TPU lowerings that hold a kernel have no cost analysis: asked once,
    that is written with the entry, and a start from the store lowers
    nothing to ask again."""
    monkeypatch.setattr(program_store, "_cost_of", lambda lowered: {})
    fn = _wrap(ProgramStore(str(tmp_path)), step=_fresh_step())
    assert fn.cost_analysis(*_args()) is None
    fn(*_args())
    del lowerings[:]
    fn = _wrap(ProgramStore(str(tmp_path)), step=_fresh_step())
    assert fn.cost_analysis(*_args()) is None
    fn(*_args())
    assert lowerings == []


def test_store_series_on_the_metrics_page(tmp_path, fresh_compiles):
    _wrap(ProgramStore(str(tmp_path)))(*_args())
    _wrap(ProgramStore(str(tmp_path)))(*_args())
    page = dict(line.rsplit(" ", 1) for line in compile_cache.metrics_lines())
    for series in ("hits", "misses", "errors"):
        assert f"dynamo_worker_program_store_{series}_total" in page
    assert int(page["dynamo_worker_program_store_hits_total"]) >= 1
    assert int(page["dynamo_worker_program_store_misses_total"]) >= 1
    assert float(page['dynamo_worker_program_build_seconds_total'
                      '{stage="store_read"}']) > 0.0


# -- read-ahead ------------------------------------------------------------
# Deterministic: `threads=0` queues and the test runs `_load_ahead` itself,
# or a real thread is held at a gate the test opens; no sleep.

FAMILY = {k: v for k, v in BUILD.items() if k != "greedy_only"}
_AHEAD = "program-store-read-ahead"


def _tally():
    b = compile_cache.program_builds()
    return dict(b["program_store"], read_s=b["seconds"]["store_read"],
                wait_s=b["seconds"]["store_wait"])


def _since(before):
    now = _tally()
    return {k: now[k] - before[k] for k in now}


@pytest.fixture
def loads(monkeypatch):
    """The thread of every `deserialize_and_load` since the test began."""
    seen, real = [], program_store._load

    def counted(payload, devices):
        seen.append(threading.current_thread().name)
        return real(payload, devices)

    monkeypatch.setattr(program_store, "_load", counted)
    return seen


def _device():
    return jax.local_devices()[0]


def _ahead_threads():
    return [t for t in threading.enumerate() if t.name.startswith(_AHEAD)]


def test_a_prefetched_entry_is_served_without_a_second_load(
        tmp_path, fresh_compiles, loads):
    want, _ = _wrap(ProgramStore(str(tmp_path)))(*_args())
    store = ProgramStore(str(tmp_path))
    before = _tally()
    assert store.read_ahead(FAMILY, _device(), threads=0) == 1
    store._load_ahead(_device())
    d = _since(before)
    assert len(loads) == 1 and d["read_s"] > 0.0
    # Loaded is not served: the counts move when a first call takes it.
    assert (d["hits"], d["prefetched"], d["misses"]) == (0, 0, 0)
    fn = _wrap(store)
    params, cache, x = _args()
    out, _ = fn(params, cache, x)
    d = _since(before)
    assert (d["hits"], d["prefetched"], d["misses"], d["errors"]) \
        == (1, 1, 0, 0)
    assert len(loads) == 1 and cache["k"].is_deleted()
    np.testing.assert_array_equal(out, want)
    # Another signature that spells the same key shares the one copy.
    out, _ = fn(params, _args()[1], np.asarray(x))
    np.testing.assert_array_equal(out, want)
    assert len(loads) == 1 and _since(before)["hits"] == 1
    assert store._ahead == {}


def test_a_first_call_that_meets_a_load_in_flight_waits_for_it(
        tmp_path, monkeypatch, fresh_compiles):
    want, _ = _wrap(ProgramStore(str(tmp_path)))(*_args())
    began, release, real, loaded = (threading.Event(), threading.Event(),
                                    program_store._load, [])

    def held(payload, devices):
        began.set()
        assert release.wait(timeout=60)
        loaded.append(threading.current_thread().name)
        return real(payload, devices)

    monkeypatch.setattr(program_store, "_load", held)
    store = ProgramStore(str(tmp_path))
    before = _tally()
    store.read_ahead(FAMILY, _device(), threads=1)
    assert began.wait(timeout=60)
    (rec,) = store._ahead.values()
    assert rec.state == "loading"

    class OpensTheGate(threading.Event):
        """The load may only end once the first call waits for it."""

        def wait(self, timeout=None):
            release.set()
            return super().wait(timeout)

    rec.done = OpensTheGate()
    out, _ = _wrap(store)(*_args())
    np.testing.assert_array_equal(out, want)
    d = _since(before)
    assert (d["hits"], d["prefetched"], d["misses"], d["errors"]) \
        == (1, 1, 0, 0)
    assert len(loaded) == 1 and loaded[0].startswith(_AHEAD)
    assert d["wait_s"] > 0.0
    store.join_read_ahead(None)
    assert not _ahead_threads()


def test_a_first_call_loads_what_is_queued_while_it_waits(
        tmp_path, monkeypatch, fresh_compiles):
    fn = _wrap(ProgramStore(str(tmp_path)))
    want = {r: np.asarray(fn(*_args(rows=r))[0]) for r in (2, 3)}
    began, release, real, loaded = (threading.Event(), threading.Event(),
                                    program_store._load, [])

    def held_on_a_thread(payload, devices):
        name = threading.current_thread().name
        if name.startswith(_AHEAD):
            began.set()
            assert release.wait(timeout=60)
        loaded.append(name)
        return real(payload, devices)

    monkeypatch.setattr(program_store, "_load", held_on_a_thread)
    store = ProgramStore(str(tmp_path))
    before = _tally()
    assert store.read_ahead(FAMILY, _device(), threads=1) == 2
    assert began.wait(timeout=60)
    first, second = store._ahead.values()       # oldest first
    assert (first.state, second.state) == ("loading", "queued")

    class OpensTheGate(threading.Event):
        def wait(self, timeout=None):
            release.set()
            return super().wait(timeout)

    first.done = OpensTheGate()
    fn = _wrap(store)
    np.testing.assert_array_equal(fn(*_args(rows=2))[0], want[2])
    # It waited for the thread's load and loaded the other meanwhile.
    assert loaded[0] == "MainThread" and loaded[1].startswith(_AHEAD)
    assert second.state == "done" and second.entry is not None
    np.testing.assert_array_equal(fn(*_args(rows=3))[0], want[3])
    d = _since(before)
    assert len(loaded) == 2
    assert (d["hits"], d["prefetched"], d["misses"]) == (2, 2, 0)
    store.join_read_ahead(None)
    assert not _ahead_threads()


@pytest.mark.parametrize("other", ["family", "device"])
def test_another_familys_or_devices_entry_is_not_read_ahead(
        other, tmp_path, fresh_compiles, loads):
    store = ProgramStore(str(tmp_path))
    _wrap(store)(*_args())
    if other == "family":       # two models' programs share a directory
        _wrap(store, build=dict(BUILD, model="another"))(*_args())
    else:
        _wrap(store)(*jax.device_put(_args(), jax.local_devices()[1]))
    assert len(_entries(store)) == 2
    store = ProgramStore(str(tmp_path))
    (key,) = store.held_for(FAMILY, _device())
    assert json.loads(key["build"])["model"] == "m" and key["device"] == 0
    assert store.read_ahead(FAMILY, _device(), threads=0) == 1
    store._load_ahead(_device())
    assert len(loads) == 1
    assert list(store._ahead) == [store.path_for(key)]
    # A family that no entry carries: nothing queued, no thread.
    empty = ProgramStore(str(tmp_path))
    assert empty.read_ahead(dict(FAMILY, block_size=16), _device()) == 0
    assert empty._threads == [] and not _ahead_threads()


def test_a_corrupt_entry_met_ahead_is_one_error_and_the_jit_serves(
        tmp_path, fresh_compiles, loads):
    store = ProgramStore(str(tmp_path))
    want, _ = _wrap(store)(*_args())
    path = os.path.join(store.dir, _entries(store)[0])
    with open(path, "rb") as f:
        whole = f.read()
    with open(path, "wb") as f:             # the header stands
        f.write(whole[:-64] + bytes(64))
    store = ProgramStore(str(tmp_path))
    before = _tally()
    assert store.read_ahead(FAMILY, _device(), threads=0) == 1
    store._load_ahead(_device())
    assert _since(before)["errors"] == 1 and loads == []
    out, _ = _wrap(store)(*_args())
    np.testing.assert_array_equal(out, want)
    d = _since(before)
    assert (d["hits"], d["prefetched"], d["misses"], d["errors"]) \
        == (0, 0, 1, 1)
    before = _tally()                       # overwritten, whole again
    _wrap(ProgramStore(str(tmp_path)))(*_args())
    assert _since(before)["hits"] == 1


def test_an_entry_no_thread_has_begun_is_loaded_by_its_first_call(
        tmp_path, fresh_compiles, loads):
    """`hits` and `misses`, and so `program_store_hit_share`, count as
    they did: one hit a program served from the store, whoever loaded."""
    want, _ = _wrap(ProgramStore(str(tmp_path)))(*_args())
    store = ProgramStore(str(tmp_path))
    store.read_ahead(FAMILY, _device(), threads=0)
    (rec,) = store._ahead.values()
    before = _tally()
    fn = _wrap(store)
    out, _ = fn(*_args())
    np.testing.assert_array_equal(out, want)
    d = _since(before)
    assert (d["hits"], d["prefetched"], d["misses"]) == (1, 0, 0)
    assert d["wait_s"] >= d["read_s"] > 0.0
    assert loads == ["MainThread"] and rec.state == "taken"
    store._load_ahead(_device())            # a thread that comes later
    assert loads == ["MainThread"]
    fn(*_args(rows=3))                      # not in the store: a miss
    d = _since(before)
    assert (d["hits"], d["prefetched"], d["misses"]) == (1, 0, 1)


def _other_geometry():
    params, _cache, x = _args()
    return params, {"k": jnp.zeros((6, 8), jnp.float32)}, x


@pytest.mark.parametrize("loaded", [False, True])
def test_the_join_releases_another_geometrys_and_keeps_this_ones(
        loaded, tmp_path, fresh_compiles, loads):
    store = ProgramStore(str(tmp_path))
    want, _ = _wrap(store)(*_args())
    _wrap(store)(*_other_geometry())
    store = ProgramStore(str(tmp_path))
    before = _tally()
    assert store.read_ahead(FAMILY, _device(), threads=0) == 2
    if loaded:
        store._load_ahead(_device())
    program_store.join_read_ahead(store, _args()[:2])
    d = _since(before)
    if loaded:
        # Both were loaded by then; the one this engine cannot ask for
        # is released and counted, the other waits for its first call.
        assert len(loads) == 2 and d["prefetch_unclaimed"] == 1
        (rec,) = store._ahead.values()
        assert rec.entry is not None
    else:
        # Nothing had begun: the other geometry's is never begun.
        store._load_ahead(_device())
        assert len(loads) == 1 and d["prefetch_unclaimed"] == 0
    (rec,) = store._ahead.values()
    assert rec.key["fixed"] == program_store._spell(
        program_store._signature(_args()[:2]))[0]
    n = len(loads)
    fn = _wrap(store)
    out, _ = fn(*_args())                   # after the join: no load
    np.testing.assert_array_equal(out, want)
    assert len(loads) == n
    d = _since(before)
    assert (d["hits"], d["prefetched"], d["misses"]) == (1, 1, 0)
    fn = _wrap(store)
    fn(*_other_geometry())                  # released: from the disk again
    assert loads[n:] == ["MainThread"]
    d = _since(before)
    assert (d["hits"], d["prefetched"], d["misses"]) == (2, 1, 0)


def test_many_first_calls_race_the_threads_and_each_entry_loads_once(
        tmp_path, fresh_compiles, loads):
    import sys

    rows = list(range(1, 13))
    fn = _wrap(ProgramStore(str(tmp_path)))
    want = {r: np.asarray(fn(*_args(rows=r))[0]) for r in rows}
    store = ProgramStore(str(tmp_path))
    before = _tally()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        assert store.read_ahead(FAMILY, _device(), threads=8) == len(rows)
        fn = _wrap(store)
        for r in reversed(rows):            # against the threads' order
            np.testing.assert_array_equal(fn(*_args(rows=r))[0], want[r])
        store.join_read_ahead(None)
    finally:
        sys.setswitchinterval(interval)
    d = _since(before)
    assert len(loads) == len(rows) and not _ahead_threads()
    assert (d["hits"], d["misses"], d["errors"]) == (len(rows), 0, 0)
    assert d["prefetched"] + loads.count("MainThread") == len(rows)
    assert store._ahead == {} and d["prefetch_unclaimed"] == 0


def test_an_engine_takes_its_programs_from_the_read_ahead(
        tmp_path, fresh_compiles, loads):
    _core, want = _serve(ProgramStore(str(tmp_path)))
    stored_n = len(_entries(_core.config.program_store))
    before = _tally()
    core, got = _serve(ProgramStore(str(tmp_path)), join=True)
    d = _since(before)
    assert got == want
    # Joined before the first request: every load ran on a read-ahead
    # thread, once, and no first call went to the disk.
    assert len(loads) == stored_n
    assert all(name.startswith(_AHEAD) for name in loads)
    assert d["hits"] == d["prefetched"] > 0
    assert (d["misses"], d["errors"], d["prefetch_unclaimed"]) == (0, 0, 0)
    assert not _ahead_threads()
    # Unclaimed and of this geometry: loaded, kept for its first call.
    kept = core.config.program_store._ahead
    assert len(kept) == stored_n - d["hits"]
    assert all(rec.entry is not None for rec in kept.values())


@pytest.mark.parametrize("kind", ["no-store", "tp", "pp", "multihost"])
def test_no_store_a_mesh_or_several_hosts_read_nothing_ahead(
        kind, tmp_path, monkeypatch):
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    asked = []
    monkeypatch.setattr(ProgramStore, "read_ahead",
                        lambda self, *a, **kw: asked.append(a))
    store = None if kind == "no-store" else ProgramStore(str(tmp_path))
    if kind == "multihost":
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        program_store.read_ahead(store, FAMILY)
    else:
        mesh = (None if kind == "no-store" else make_mesh(
            MeshConfig(**{kind: 2}), jax.devices()[:2]))
        core = EngineCore(EngineConfig(
            model=PRESETS["tiny-test"], num_blocks=64, mesh=mesh,
            program_store=store, scheduler=SchedulerConfig(block_size=8)))
        core.join_read_ahead()
    assert asked == [] and not _ahead_threads()


def test_the_worker_joins_the_read_ahead_before_its_engine_serves(
        tmp_path, monkeypatch):
    import asyncio

    from dynamo_tpu.engine.engine import InferenceEngine
    from dynamo_tpu.worker import main as worker_main

    order = []
    join, start = EngineCore.join_read_ahead, InferenceEngine.start

    def joined(self):
        join(self)
        order.append(("join", len(_ahead_threads())))

    async def started(self):
        order.append(("start", len(_ahead_threads())))
        await start(self)

    monkeypatch.setattr(EngineCore, "join_read_ahead", joined)
    monkeypatch.setattr(InferenceEngine, "start", started)
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda *a: str(tmp_path))
    args = worker_main.parse_args(
        ["--control-plane", "127.0.0.1:1", "--model", "tiny-test",
         "--num-blocks", "64", "--block-size", "8"])

    async def build():
        *_rest, shutdown, _card, _engine = await worker_main.build_engine(
            args, lambda event: None)
        await shutdown()

    asyncio.run(build())
    assert order == [("join", 0), ("start", 0)]
