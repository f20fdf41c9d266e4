"""The feature-composition grid (ISSUE 12): every (feature × mesh) cell
of the README "Sharded serving" matrix is either exercised against the
meshless oracle HERE (bf16 cells token-exact, int8 cells by logits
against the meshless int8 engine), or declared impossible in the ONE
capability table (parallel.sharding.plane_capability) with a pointed
error this file asserts — no silent gaps.

The matrix used to be a code grid (per-combo step builders + engine
rejection lists); the PlaneSpec refactor collapsed it to this test grid.
One shared tiny geometry (identical to tests/test_sharded_serving.py's)
keeps the compiled-shape set compile-cache-friendly; the heaviest cells
are slow-marked so the warm tier-1 suite stays inside its budget.  The
lockstep-2proc column runs as subprocess pairs in
tests/test_multihost.py (`fused_int8` is the grid's multihost cell).
"""

import jax
import pytest

from dynamo_tpu.engine.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import SchedulerConfig
from dynamo_tpu.models import config as mcfg
from dynamo_tpu.parallel import MeshConfig, make_mesh
from dynamo_tpu.parallel.sharding import PlaneSpec, plane_capability

from logit_parity import assert_logit_parity, greedy

# SAME geometry as tests/test_sharded_serving.py — the grid's engines
# lower to already-cached HLO wherever the cell's program shape repeats.
SCHED = dict(max_seqs=4, block_size=8, max_pages_per_seq=8,
             max_prefill_chunk=16, decode_buckets=(2, 4),
             prefill_buckets=(8, 16))

PROMPTS = {"a": [5, 6, 7, 8, 9, 10, 5, 6, 7, 8],
           "b": list(range(20, 34))}

MESHES = {
    "tp2": (MeshConfig(tp=2), {}),
    "dp2": (MeshConfig(dp=2), {}),
    "dp_local": (MeshConfig(tp=2, dp=2), dict(dp_attention=True)),
    "sp2": (MeshConfig(sp=2, tp=2), dict(sp_prefill_threshold=8)),
    "pp2": (MeshConfig(pp=2), {}),
    "ep2": (MeshConfig(dp=2, ep=2), {}),
    "ep2tp2": (MeshConfig(dp=2, ep=2, tp=2), {}),
}


def _build_cell(mesh_name=None, kv_quant="none", spec=0, decode_window=1,
                model="tiny-test", **extra):
    kwargs = dict(enable_prefix_cache=False)
    mesh = None
    if mesh_name is not None:
        mesh_cfg, mesh_kwargs = MESHES[mesh_name]
        mesh = make_mesh(mesh_cfg, jax.devices()[:mesh_cfg.size])
        kwargs.update(mesh_kwargs)
    kwargs.update(extra)
    return EngineCore(EngineConfig(
        model=mcfg.get_config(model), num_blocks=64, mesh=mesh,
        kv_quant=kv_quant, speculative_tokens=spec,
        decode_window=decode_window, window_pipeline_depth=2,
        scheduler=SchedulerConfig(**SCHED), **kwargs))


def _run_cell(**kwargs):
    core = _build_cell(**kwargs)
    for rid, toks in PROMPTS.items():
        core.add_request(rid, toks, SamplingParams(max_tokens=12))
    outputs = {}
    for _ in range(300):
        for d in core.step():
            outputs.setdefault(d.request_id, []).extend(d.token_ids)
        if not core._requests:
            break
    assert not core._requests, "engine did not finish"
    return core, outputs


@pytest.fixture(scope="module")
def oracle():
    """Meshless single-step greedy output — the parity reference every
    exercised bf16 cell must match byte-identically."""
    _, out = _run_cell()
    return out


@pytest.fixture(scope="module")
def int8_oracle():
    """The int8 cells' reference: the meshless engine over the same
    int8 cache, as (core, `greedy` result).  A cell is held to its
    logits (tests/logit_parity.py says why not to the bf16 tokens)."""
    core = _build_cell(kv_quant="int8")
    return core, greedy(core, list(PROMPTS.values()))


# (cell id, engine kwargs, extra post-run asserts key) — each cell is a
# NEW composition this PR opened (the pre-existing yes-cells keep their
# pins in test_sharded_serving.py / test_kv_quant.py).
CELLS = {
    # int8 × spec × head-sharded tp: quantized verify chunks.
    "tp2+int8+spec": dict(mesh_name="tp2", kv_quant="int8", spec=3),
    # int8 × dp window: replicated-cache dp with quantized windows.
    "dp2+int8+window": dict(mesh_name="dp2", kv_quant="int8",
                            decode_window=4),
    # ISSUE 12 leg 5: spec verify resolves rows to the owning shard's
    # slot range under dp-attention locality.
    "dp_local+spec": dict(mesh_name="dp_local", spec=3),
    # ISSUE 12 leg 1: quantized ring-SP exchange, then int8 decode.
    "sp2+int8+window": dict(mesh_name="sp2", kv_quant="int8",
                            decode_window=4),
    # ISSUE 19: pallas × ring-SP — the flash ring kernel (double-
    # buffered RDMA exchange under the fold, interpret mode on CPU)
    # serves the sp prefill; the ring-path AND kernel-path counters
    # are asserted so an XLA-ring fallback can't pass silently.
    "sp2+pallas": dict(mesh_name="sp2", use_pallas_decode=True),
    # ISSUE 19: sp_prefill × pallas × int8 — int8 rows + scales ride
    # the kernel's RDMA streams and dequantize in VMEM.
    "sp2+pallas+int8": dict(mesh_name="sp2", use_pallas_decode=True,
                            kv_quant="int8"),
    # ISSUE 12 leg 3: the pp decode window (schedule-looping program).
    "pp2+window": dict(mesh_name="pp2", decode_window=4),
    # ISSUE 12 leg 3: the all-in-one fused pp greedy step.
    "pp2+fused": dict(mesh_name="pp2", decode_window=1),
    # ISSUE 12 leg 2: int8 through the stacked pp layout.
    "pp2+int8": dict(mesh_name="pp2", kv_quant="int8", decode_window=1),
}

SLOW_CELLS = {
    # spec × ring-SP mesh (the sp axis idles during decode; the matrix
    # row claims yes, so it gets a pin).
    "sp2+spec": dict(mesh_name="sp2", spec=3),
    # int8 × spec × dp-attention locality — the heaviest three-way cell.
    "dp_local+int8+spec": dict(mesh_name="dp_local", kv_quant="int8",
                               spec=3),
    # int8 × pp × window.
    "pp2+int8+window": dict(mesh_name="pp2", kv_quant="int8",
                            decode_window=4),
}

# MoE row of the matrix (ISSUE 17): every exclusion this PR killed
# becomes an exercised cell against the tiny-moe meshless dense oracle.
MOE_CELLS = {
    # moe × decode window (meshless dense).
    "moe+window": dict(model="tiny-moe", decode_window=4),
    # moe × fused greedy through the GROUPED fast path (interpret on
    # CPU) — the ops-level byte-identity surviving the fused program.
    "moe+grouped": dict(model="tiny-moe", moe_mode="grouped"),
    # grouped × decode window.
    "moe+grouped+window": dict(model="tiny-moe", moe_mode="grouped",
                               decode_window=4),
    # moe × int8 KV × window (vs the int8 meshless oracle: int8 KV is
    # lossy and the router's top-k amplifies it, so the honest parity
    # reference shares the quantizer and pins the PLANE composition).
    "moe+int8": dict(model="tiny-moe", kv_quant="int8", decode_window=4),
    # moe × packed ragged prefill (the exclusion killed in the engine).
    "moe+packed": dict(model="tiny-moe", packed_prefill=True),
    # moe × head-sharded tp (dense GSPMD expert einsums).
    "moe+tp2": dict(model="tiny-moe", mesh_name="tp2"),
    # moe × ep dispatch (all-to-all over the ep axis).
    "moe+ep2": dict(model="tiny-moe", mesh_name="ep2"),
}

MOE_SLOW_CELLS = {
    # ep × tp dispatch: tp-sharded expert MLPs under the all-to-all.
    "moe+ep2+tp2": dict(model="tiny-moe", mesh_name="ep2tp2"),
    # dispatch × decode window × int8 KV — the heaviest MoE cell.
    "moe+ep2+int8+window": dict(model="tiny-moe", mesh_name="ep2",
                                kv_quant="int8", decode_window=4),
}


def _assert_cell(name, kwargs, oracle):
    """`oracle`: the tokens a cell must equal, or for the tiny-test int8
    cells the `int8_oracle` pair, to whose logits the cell is held."""
    if isinstance(oracle, tuple):
        ref_core, ref = oracle
        core = _build_cell(**kwargs)
        prompts = list(PROMPTS.values())
        assert_logit_parity(name, ref_core, ref, greedy(core, prompts),
                            prompts)
    else:
        core, out = _run_cell(**kwargs)
        assert out == oracle, \
            f"cell {name} diverged from the meshless oracle"
    # The cell must have run the plane it claims, not a fallback.
    if kwargs.get("spec"):
        assert core.counters.spec_dispatches > 0, \
            f"cell {name} never dispatched a speculative verify"
    if kwargs.get("mesh_name") == "sp2":
        assert core.sp_prefill_count == len(PROMPTS), \
            f"cell {name} prefill skipped the ring path"
        assert core.counters.ring_exchange_bytes_modeled > 0
        # Kernel-path attribution (ISSUE 19): pallas sp cells must have
        # run the flash ring kernel, non-pallas cells the XLA ring.
        want_kernel = len(PROMPTS) if kwargs.get("use_pallas_decode") \
            else 0
        assert core.counters.ring_kernel_prefills == want_kernel, \
            f"cell {name} ran the wrong ring implementation"
    if kwargs.get("decode_window", 1) > 1:
        assert core.counters.window_dispatches > 0, \
            f"cell {name} never dispatched a decode window"
    elif not kwargs.get("spec"):
        assert core._greedy_fused is not None, \
            f"cell {name} single-step decode did not take the fused path"
    if kwargs.get("packed_prefill"):
        assert core.counters.packed_prefill_dispatches > 0, \
            f"cell {name} never dispatched a packed prefill"
    if kwargs.get("model") == "tiny-moe":
        load = core.snapshot_expert_load()
        assert load is not None and int(load.sum()) > 0, \
            f"cell {name} lost the expert-load telemetry"
        assert core.moe_dropped_tokens == 0, \
            f"cell {name} dropped tokens at exact capacity"


def _ref(kw, oracle, int8_oracle):
    return int8_oracle if kw.get("kv_quant") == "int8" else oracle


@pytest.mark.parametrize("name", sorted(CELLS))
def test_composition_cell(name, oracle, int8_oracle):
    kw = CELLS[name]
    _assert_cell(name, kw, _ref(kw, oracle, int8_oracle))


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(SLOW_CELLS))
def test_composition_cell_slow(name, oracle, int8_oracle):
    kw = SLOW_CELLS[name]
    _assert_cell(name, kw, _ref(kw, oracle, int8_oracle))


@pytest.fixture(scope="module")
def moe_oracle():
    """tiny-moe meshless single-step dense output — the MoE row's parity
    reference (moe_dense is exact; grouped is byte-identical to it)."""
    _, out = _run_cell(model="tiny-moe")
    return out


@pytest.fixture(scope="module")
def moe_int8_oracle():
    """The int8-KV MoE reference: int8 cells share the quantizer with
    their oracle so the cell pins the plane composition, not the
    quantizer's (real, router-amplified) loss."""
    _, out = _run_cell(model="tiny-moe", kv_quant="int8")
    return out


def _moe_ref(kw, moe_oracle, moe_int8_oracle):
    return moe_int8_oracle if kw.get("kv_quant") == "int8" else moe_oracle


@pytest.mark.parametrize("name", sorted(MOE_CELLS))
def test_moe_composition_cell(name, moe_oracle, moe_int8_oracle):
    kw = MOE_CELLS[name]
    _assert_cell(name, kw, _moe_ref(kw, moe_oracle, moe_int8_oracle))


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(MOE_SLOW_CELLS))
def test_moe_composition_cell_slow(name, moe_oracle, moe_int8_oracle):
    kw = MOE_SLOW_CELLS[name]
    _assert_cell(name, kw, _moe_ref(kw, moe_oracle, moe_int8_oracle))


def test_pp_fused_step_counters():
    """The pp half of the r5 single-step cliff is dead (ISSUE 12 leg 3):
    steady pp single-step decode is ONE fused stage-program dispatch
    with ONE host sync and zero new compiled shapes per engine
    iteration — the same pin the meshless and tp paths carry."""
    mesh = make_mesh(MeshConfig(pp=2), jax.devices()[:2])
    core = EngineCore(EngineConfig(
        model=mcfg.get_config("tiny-test"), num_blocks=64,
        mesh=mesh, decode_window=1, enable_prefix_cache=False,
        scheduler=SchedulerConfig(**SCHED)))
    for rid, toks in PROMPTS.items():
        core.add_request(rid, toks, SamplingParams(max_tokens=30))
    for _ in range(6):   # prefill + warm the fused program
        core.step()
    assert core._greedy_fused is not None
    base = core.counters.snapshot()
    n = 8
    for _ in range(n):
        core.step()
    d = core.counters.delta(base)
    assert d["single_step_dispatches"] == n
    assert d["host_syncs"] == n, "fused pp step must cost 1 sync"
    assert d["xla_cache_misses"] == 0, "steady pp shape recompiled"


def test_sp_ring_exchange_bytes_halve_under_int8():
    """Modeled ring traffic honesty (ISSUE 12 satellite): the quantized
    ring exchange moves int8 rows + f32 scales instead of full-precision
    chunks, so the per-chip `ring_exchange_bytes_modeled` series must
    shrink by exactly the packed-payload ratio — the sp analog of the
    block-bytes ratio tests/test_kv_quant.py pins."""
    cfg = mcfg.get_config("tiny-test")
    _, _ = (None, None)
    core_bf, _ = _run_cell(mesh_name="sp2")
    core_i8, _ = _run_cell(mesh_name="sp2", kv_quant="int8")
    bf = core_bf.counters.ring_exchange_bytes_modeled
    i8 = core_i8.counters.ring_exchange_bytes_modeled
    assert bf > 0 and i8 > 0
    H, D = cfg.num_kv_heads, cfg.head_dim
    itemsize = jax.numpy.dtype(core_bf.cache_cfg.dtype).itemsize
    want = (H * D + 4 * H) / (H * D * itemsize)
    assert abs(i8 / bf - want) < 1e-6


def test_per_chip_modeled_bytes_pp_sp():
    """tp2 parity discipline (PR 9) extended to pp2/sp2 (ISSUE 12
    satellite): a pp2 engine's per-chip effective_bytes_per_token HALVES
    vs meshless (each stage sweeps its layer slice for all rows) — int8
    included, where the numerator also carries the stacked scale
    buffers; an sp2(+tp2) engine divides by dp·tp ONLY (the sp axis
    replicates decode — dividing by it would be flattering, not
    honest)."""
    meshless, _ = _run_cell()
    b0 = meshless.counters.effective_bytes_per_token
    assert b0 > 0

    pp2, _ = _run_cell(mesh_name="pp2")
    assert pp2.kv_traffic_shards == 2 and pp2.kv_shard_count == 2
    assert abs(pp2.counters.effective_bytes_per_token / b0 - 0.5) < 1e-6

    meshless_i8, _ = _run_cell(kv_quant="int8")
    pp2_i8, _ = _run_cell(mesh_name="pp2", kv_quant="int8")
    b0_i8 = meshless_i8.counters.effective_bytes_per_token
    assert b0_i8 > 0
    assert abs(pp2_i8.counters.effective_bytes_per_token / b0_i8
               - 0.5) < 1e-6

    sp2, _ = _run_cell(mesh_name="sp2")  # sp2 × tp2 mesh
    assert sp2.kv_traffic_shards == 2  # dp*tp — tp halves, sp does NOT
    assert abs(sp2.counters.effective_bytes_per_token / b0 - 0.5) < 1e-6

    # Residency honesty under pp+int8: per-chip block bytes report the
    # stacked pages AND scale buffers divided by the stage count.
    from dynamo_tpu.runtime.metrics import KvCacheMetrics, MetricsRegistry

    kvm = KvCacheMetrics(MetricsRegistry())
    kvm.observe_engine(pp2_i8)
    got = kvm.kv_bytes_per_block.value(labels={"kv_quant": "int8"})
    assert got == pp2_i8.cache_cfg.bytes_per_block / 2


# Cells the capability table declares impossible AND a user can ask an
# engine for: (mesh, the plane as the table sees it, a word of the
# reason, the engine arguments that ask for it).
REFUSED_CELLS = {
    # The stage program banks one sampled row.
    "spec+pp2": ("pp2", PlaneSpec(spec=True), "spec",
                 dict(speculative_tokens=3)),
    # Pages span shards without dp-attention locality.
    "pallas+dp_attention_nonlocal": (
        "dp_local", PlaneSpec(use_pallas=True, dp_attention=True),
        "locality", dict(dp_attention=True, dp_attention_local=False,
                         use_pallas_decode=True)),
    # The kernel is not wired into the stage scan; auto keeps pp on the
    # gather path, explicit True raises.
    "pallas+pp2": ("pp2", PlaneSpec(use_pallas=True), "stage scan",
                   dict(use_pallas_decode=True)),
    # The stage scan stacks per-stage weights into one batched pytree;
    # its body has no expert branch.
    "moe+pp2": ("pp2", PlaneSpec(moe=True), "expert",
                dict(model=mcfg.get_config("tiny-moe"))),
}


@pytest.mark.parametrize("name", sorted(REFUSED_CELLS))
def test_engine_refuses_a_declared_impossible_cell(name):
    """Acceptance, first half: a matrix '—' a user can ask for is
    DECLARED in the one capability table, and the engine raises that
    exact reason at construction — a silently-rejecting cell can't
    hide."""
    mesh_name, plane, word, asked = REFUSED_CELLS[name]
    mesh_cfg, _ = MESHES[mesh_name]
    mesh = make_mesh(mesh_cfg, jax.devices()[:mesh_cfg.size])
    cap = plane_capability(mesh, plane)
    assert not cap.ok and word in cap.reason
    kwargs = dict(model=mcfg.get_config("tiny-test"), num_blocks=64,
                  mesh=mesh, enable_prefix_cache=False,
                  scheduler=SchedulerConfig(**SCHED))
    kwargs.update(asked)
    with pytest.raises(ValueError) as ei:
        EngineCore(EngineConfig(**kwargs))
    assert str(ei.value) == cap.reason


def test_declared_impossible_cells_are_pointed():
    """Acceptance, second half: the cells no engine argument reaches
    (multihost, a role of a built engine) are declared in the table too,
    and the table and the grid above agree."""
    tp2 = make_mesh(MeshConfig(tp=2), jax.devices()[:2])
    pp2 = make_mesh(MeshConfig(pp=2), jax.devices()[:2])

    # spec × multihost: loudly versioned out of the lockstep stream.
    cap = plane_capability(tp2, PlaneSpec(spec=True), multihost=True)
    assert not cap.ok and "lockstep" in cap.reason

    # pallas × multihost: unaudited shard_map custom calls — declared;
    # auto keeps lockstep meshes on the gather path.
    cap = plane_capability(tp2, PlaneSpec(use_pallas=True), multihost=True)
    assert not cap.ok and "lockstep" in cap.reason

    # embeddings / multimodal × pp and × multihost: declared.
    for role in ("embed", "mm"):
        assert not plane_capability(pp2, PlaneSpec(role=role)).ok
        assert not plane_capability(tp2, PlaneSpec(role=role),
                                    multihost=True).ok
    core = EngineCore(EngineConfig(
        model=mcfg.get_config("tiny-test"), num_blocks=64, mesh=pp2,
        enable_prefix_cache=False, scheduler=SchedulerConfig(**SCHED)))
    cap = plane_capability(pp2, PlaneSpec(role="embed"))
    with pytest.raises(ValueError) as ei:
        core.embed_tokens([[1, 2, 3]])
    assert str(ei.value) == cap.reason

    # pp × multihost: declared.
    assert not plane_capability(pp2, PlaneSpec(), multihost=True).ok

    # moe × ring-SP prefill: the sp token chunking conflicts with the
    # dp×ep token dispatch — declared; the engine consults the table
    # and keeps MoE prefill on the padded plane (no error, no ring).
    sp2 = make_mesh(MeshConfig(sp=2, tp=2), jax.devices()[:4])
    cap = plane_capability(sp2, PlaneSpec(role="sp_prefill", moe=True))
    assert not cap.ok and "ring" in cap.reason

    # Every EXERCISED cell above must be capability-table-OK — a cell
    # that runs here but is declared impossible (or vice versa) means
    # the table and the grid drifted.  The MoE cells fold their `moe`
    # bit into the plane exactly the way the engine does.
    for name, kw in {**CELLS, **SLOW_CELLS, **MOE_CELLS,
                     **MOE_SLOW_CELLS}.items():
        if kw.get("mesh_name") is None:
            continue  # meshless cells never consult the table
        mesh_cfg, mesh_kwargs = MESHES[kw["mesh_name"]]
        mesh = make_mesh(mesh_cfg, jax.devices()[:mesh_cfg.size])
        plane = PlaneSpec(
            quant=kw.get("kv_quant") == "int8",
            spec=bool(kw.get("spec")),
            window=kw.get("decode_window", 1),
            fused=kw.get("decode_window", 1) <= 1,
            use_pallas=bool(kw.get("use_pallas_decode")),
            dp_attention=bool(mesh_kwargs.get("dp_attention")),
            dp_local=bool(mesh_kwargs.get("dp_attention")),
            moe=kw.get("model") == "tiny-moe")
        cap = plane_capability(mesh, plane)
        assert cap.ok, f"grid cell {name} is declared impossible: " \
                       f"{cap.reason}"
        if kw.get("mesh_name") == "sp2":
            # The sp cells ALSO consult the table with the sp_prefill
            # role (the engine's gate for building the ring step) —
            # including pallas × sp_prefill, the cell ISSUE 19 composed.
            sp_plane = PlaneSpec(
                role="sp_prefill", quant=plane.quant,
                use_pallas=plane.use_pallas,
                moe=kw.get("model") == "tiny-moe")
            cap = plane_capability(mesh, sp_plane)
            assert cap.ok, f"sp grid cell {name} declared impossible: " \
                           f"{cap.reason}"
