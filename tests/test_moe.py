"""MoE all-to-all dispatch vs the dense oracle (VERDICT r2 item 7).

Dense compute is exact by construction; dispatch with exact capacity must
reproduce it — standalone, and sharded over the 8-device CPU mesh's
dp×ep axes through the full forward step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import kv_cache as kvc
from dynamo_tpu.models import config as mcfg
from dynamo_tpu.models.llama import init_params, make_forward_step
from dynamo_tpu.ops import moe as moe_ops
from dynamo_tpu.parallel import (
    MeshConfig,
    cache_pspecs,
    make_mesh,
    make_sharded_step,
    param_pspecs,
    shard_pytree,
)

CFG = mcfg.get_config("tiny-moe")
BLOCK = 8


def _moe_params(key=0):
    p = init_params(CFG, jax.random.key(key), dtype=jnp.float32)
    return p["layers"][0]["moe"]


def test_dispatch_matches_dense_standalone():
    p = _moe_params()
    x = jax.random.normal(jax.random.key(1), (4, 16, CFG.hidden_size),
                          jnp.float32)
    want, load_d = moe_ops.moe_dense(CFG, p, x)
    got, load = moe_ops.moe_dispatch(CFG, p, x)  # exact capacity default
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=2e-5, atol=2e-5)
    # Same routing → same per-expert counts; totals = N*k.
    np.testing.assert_array_equal(np.asarray(load), np.asarray(load_d))
    assert int(load.sum()) == 4 * 16 * CFG.num_experts_per_token


def test_dispatch_capacity_drops_overflow():
    """Tiny capacity must drop assignments (gate mass lost), not crash or
    corrupt other tokens."""
    p = _moe_params()
    x = jax.random.normal(jax.random.key(2), (2, 8, CFG.hidden_size),
                          jnp.float32)
    got, _ = moe_ops.moe_dispatch(CFG, p, x, capacity=1)
    assert np.isfinite(np.asarray(got)).all()


def test_sharded_dispatch_step_matches_dense_reference():
    """Full forward step, dp=2 x ep=4 (tp=1): dispatch path output equals
    the single-device dense step."""
    params = init_params(CFG, jax.random.key(0), dtype=jnp.float32)
    batch, T = 8, 16  # batch divisible by dp*ep
    tokens = jax.random.randint(jax.random.key(5), (batch, T), 0,
                                CFG.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (batch, T))
    bt = np.zeros((batch, 8), np.int32)
    for i in range(batch):
        bt[i, :4] = np.arange(1 + 4 * i, 5 + 4 * i)
    seq_lens = jnp.full((batch,), T, jnp.int32)
    inputs = (tokens, positions, seq_lens, jnp.asarray(bt))
    sample_pos = jnp.full((batch,), T - 1, jnp.int32)

    ref_step = make_forward_step(CFG, BLOCK)
    ref_cache = kvc.init_cache(kvc.KvCacheConfig.for_model(
        CFG, num_blocks=64, block_size=BLOCK, dtype=jnp.float32))
    want, _ = ref_step(params, ref_cache, *inputs, sample_pos)

    mesh = make_mesh(MeshConfig(dp=2, ep=4), jax.devices())
    sharded = shard_pytree(params, param_pspecs(CFG, "dispatch"), mesh)
    cache = shard_pytree(
        kvc.init_cache(kvc.KvCacheConfig.for_model(
            CFG, num_blocks=64, block_size=BLOCK, dtype=jnp.float32)),
        cache_pspecs(CFG.num_layers), mesh)
    step = make_sharded_step(CFG, BLOCK, mesh, moe_mode="dispatch",
                             with_expert_load=True)
    got, _, load = step(sharded, cache, *inputs, sample_pos)

    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=5e-4, atol=5e-4)
    assert int(np.asarray(load).sum()) == (
        batch * T * CFG.num_experts_per_token * CFG.num_layers)


def test_dispatch_ep_tp_mesh_matches_dense_reference():
    """dp=2 x ep=2 x tp=2: dispatch with tp-sharded expert MLPs (F/tp
    slices, one psum on exit) matches the meshless dense oracle, with
    every assignment counted and nothing dropped at exact capacity."""
    params = init_params(CFG, jax.random.key(0), dtype=jnp.float32)
    batch, T = 8, 16  # batch divisible by dp*ep
    tokens = jax.random.randint(jax.random.key(5), (batch, T), 0,
                                CFG.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (batch, T))
    bt = np.zeros((batch, 8), np.int32)
    for i in range(batch):
        bt[i, :4] = np.arange(1 + 4 * i, 5 + 4 * i)
    inputs = (tokens, positions, jnp.full((batch,), T, jnp.int32),
              jnp.asarray(bt))
    sample_pos = jnp.full((batch,), T - 1, jnp.int32)

    ref_step = make_forward_step(CFG, BLOCK)
    ref_cache = kvc.init_cache(kvc.KvCacheConfig.for_model(
        CFG, num_blocks=64, block_size=BLOCK, dtype=jnp.float32))
    want, _ = ref_step(params, ref_cache, *inputs, sample_pos)

    mesh = make_mesh(MeshConfig(dp=2, ep=2, tp=2), jax.devices())
    sharded = shard_pytree(params, param_pspecs(CFG, "dispatch"), mesh)
    cache = shard_pytree(
        kvc.init_cache(kvc.KvCacheConfig.for_model(
            CFG, num_blocks=64, block_size=BLOCK, dtype=jnp.float32)),
        cache_pspecs(CFG.num_layers), mesh)
    step = make_sharded_step(CFG, BLOCK, mesh, moe_mode="dispatch",
                             with_expert_load=True)
    got, _, load = step(sharded, cache, *inputs, sample_pos)

    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=5e-4, atol=5e-4)
    load = np.asarray(load)
    assert load.shape == (CFG.num_experts + 1,)
    assert int(load[:-1].sum()) == (
        batch * T * CFG.num_experts_per_token * CFG.num_layers)
    assert load[:-1].sum() > 0
    assert int(load[-1]) == 0  # exact capacity: nothing dropped


# Two bf16 ulps (eps = 2**-7) of the output's scale: one rounding of the
# activation a term, summed over k experts (see the test below); the
# largest seen is one ulp, 0.0156 at a scale of 1.9.
BF16_ATOL = 2 * 2.0 ** -7


def _assert_bf16_close(want, got) -> None:
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=0,
                               atol=BF16_ATOL * np.abs(want).max())


def test_grouped_matches_dense_bitwise():
    """The grouped-GEMM path against the dense oracle (interpret mode on
    CPU): same routing, same expert math, same expert-index-ordered
    combine.  Held to the oracle by a tolerance of 2 ulps of the output
    scale in bf16, the serving dtype (BF16_ATOL): the kernel rounds the
    activation to bf16 after each elementwise step, where XLA may carry
    the oracle's `silu(h) * u` in float32 into one rounding (its excess
    precision), a difference of at most one rounding a term.  It was
    byte-identical while the kernel pinned that rounding with
    `optimization_barrier`, which Mosaic cannot lower for v5e; the name
    of the test is kept for the record of what it once held.

    f32 is pinned to 8 ulps of the output scale, not to the byte: on the
    installed XLA CPU backend the oracle's batched einsum
    (`bth,ehf->betf`) and the kernel's per-tile `[bm, H] @ [H, F]`
    matmul sum over H in different orders — `x2 @ w[e]` alone already
    differs from that einsum's slice e by a few f32 ulps (measured max
    6.6e-7 at scale 1.9), while matmuls of 8 and 32 rows agree.  bf16
    rounding absorbs it, so the bitwise pin holds where it matters."""
    p = _moe_params()
    for dt in (jnp.float32, jnp.bfloat16):
        pd = jax.tree.map(lambda a: a.astype(dt), p)
        x = jax.random.normal(jax.random.key(3), (2, 16, CFG.hidden_size),
                              jnp.float32).astype(dt)
        want, load_d = moe_ops.moe_dense(CFG, pd, x)
        got, load_g = moe_ops.moe_grouped(CFG, pd, x, interpret=True)
        if dt == jnp.bfloat16:
            _assert_bf16_close(want, got)
        else:
            want = np.asarray(want)
            np.testing.assert_allclose(
                np.asarray(got), want, rtol=0,
                atol=8 * np.finfo(np.float32).eps * np.abs(want).max())
        np.testing.assert_array_equal(np.asarray(load_g), np.asarray(load_d))
        assert int(load_g[-1]) == 0  # grouped is exact, never drops


@pytest.mark.parametrize("blocks_f", [1, 2])
def test_grouped_kernel_skipped_tiles_with_blocked_f(blocks_f):
    """The ragged kernel with its intermediate dim in `blocks_f` blocks and
    most tiles skipped (a one-row decode step: 2 live tiles of 8): the live
    rows equal each expert's plain SwiGLU whatever the blocking.  With two F
    blocks a skipped tile holds the last live tile's last block (no weight
    moves for it: on the chip a one-row step at 64 experts of two blocks
    took 1.37 ms a layer while it walked them, PERF.md section 6, PR 36)."""
    from dynamo_tpu.ops.pallas import grouped_expert_ffn

    p = _moe_params()
    E, H, F = p["w_gate"].shape
    bm, tiles, live = 8, 8, 2
    x = jax.random.normal(jax.random.key(5), (tiles * bm, H), jnp.float32)
    tile_expert = jnp.asarray([1, 3] + [3] * (tiles - live), jnp.int32)
    got = grouped_expert_ffn(
        x, tile_expert, p["w_gate"], p["w_up"], p["w_down"],
        live_tiles=jnp.asarray([live], jnp.int32), block_rows=bm,
        block_f=F // blocks_f, interpret=True)
    for t in range(live):
        e = int(tile_expert[t])
        rows = x[t * bm:(t + 1) * bm]
        want = (jax.nn.silu(rows @ p["w_gate"][e]) * (rows @ p["w_up"][e])
                ) @ p["w_down"][e]
        np.testing.assert_allclose(
            np.asarray(got[t * bm:(t + 1) * bm]), np.asarray(want),
            rtol=0, atol=1e-5 * float(jnp.abs(want).max()))


# 64 sigmoid-routed experts, 4 a token (GLM-4.7-Flash's counts) at widths
# the interpreter walks quickly: a decode step of 1-8 rows has fewer
# assignments than experts, where the packed buffer is sized by the
# assignments (`packed_rows`).
FEW_ROWS_CFG = CFG.replace(name="tiny-moe-64", num_experts=64,
                           num_experts_per_token=4,
                           router_scoring="sigmoid",
                           routed_scaling_factor=1.8)
ROUTINGS = ("distinct", "same", "mix")


def _forced_routing_params(x2, routing: str):
    """Float32 expert-layer weights whose router sends the rows of `x2`
    [rows, H] where `routing` says.  The bias is one vector for all rows,
    so it forces what rows have in common; what sets rows apart comes from
    router columns solved from the rows themselves (x2 @ W = +-8, scores
    of nearly 1 or 0, through x2's pseudo-inverse).
    - "distinct": row i to experts 4i..4i+3 and no other row's: as many
      groups as assignments, the packed buffer's worst case;
    - "same": router 0 (every score 0.5) and a bias on experts 3, 17, 40
      and 63: every row to those four;
    - "mix": row i scores its own four and experts 62 and 63 alike, and
      a bias puts those two first: two groups of `rows` rows, and two
      groups of one row for each row.
    Returns (params, per-expert counts expected)."""
    rows, H = x2.shape
    E, F = FEW_ROWS_CFG.num_experts, CFG.intermediate_size
    keys = jax.random.split(jax.random.key(11), 3)
    p = {name: jax.random.normal(k, shape, jnp.float32) * shape[-2] ** -0.5
         for k, (name, shape) in zip(keys, {
             "w_gate": (E, H, F), "w_up": (E, H, F),
             "w_down": (E, F, H)}.items())}
    own = np.arange(rows)[:, None] * 4 + np.arange(4)       # [rows, 4]
    target = np.full((rows, E), -8.0, np.float32)
    bias = np.zeros((E,), np.float32)
    counts = np.zeros((E,), np.int64)
    if routing == "same":
        target[:] = 0.0
        bias[[3, 17, 40, 63]] = 1.0
        counts[[3, 17, 40, 63]] = rows
    else:
        np.put_along_axis(target, own, 8.0, axis=1)
        if routing == "mix":
            target[:, 62:] = 8.0
            bias[62:] = 2.0
            counts[62:] = rows
    p["router"] = jnp.asarray(
        np.linalg.pinv(np.asarray(x2, np.float32)) @ target)
    p["router_bias"] = jnp.asarray(bias)
    return p, counts, own


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("weights", ["bf16", "int8"])
@pytest.mark.parametrize("blocks_f", [1, 2])
@pytest.mark.parametrize("rows", [1, 2, 4, 8])
def test_grouped_matches_dense_with_fewer_assignments_than_experts(
        monkeypatch, rows, blocks_f, weights, routing):
    """`moe_grouped` against the dense oracle where a step has fewer
    assignments than experts (1-8 rows x 4 of 64): the packed buffer is
    `packed_rows` rows, a tile an assignment and not one an expert, with
    one F block and with two, bf16 and int8 weights, under the three
    forced routings of `_forced_routing_params`.  "distinct" fills every
    tile of the buffer with one row: were it a row short, an assignment
    would be dropped and the outputs would differ.  Tolerance as
    `test_grouped_matches_dense_bitwise` states it for bf16."""
    from dynamo_tpu.ops.pallas import moe_grouped as kernel
    from dynamo_tpu.ops.pallas import (
        dequantize_moe_params, quantize_moe_params)

    seen = {}
    real = kernel.grouped_expert_ffn

    def ffn(x_pad, tile_expert, wg, *rest, **kw):
        seen["rows"] = x_pad.shape[0]
        seen["tiles"] = tile_expert.shape[0]
        return real(x_pad, tile_expert, wg, *rest,
                    **{**kw, "block_f": wg.shape[2] // blocks_f})

    monkeypatch.setattr(kernel, "grouped_expert_ffn", ffn)
    cfg = FEW_ROWS_CFG
    x = jax.random.normal(jax.random.key(rows), (rows, 1, cfg.hidden_size),
                          jnp.float32).astype(jnp.bfloat16)
    p32, counts, own = _forced_routing_params(x[:, 0], routing)
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p32)
    p["router_bias"] = p32["router_bias"]
    oracle = p
    if weights == "int8":
        # `quantize_moe_params` keeps the router alone: the bias rides on.
        p = {**quantize_moe_params(p), "router_bias": p["router_bias"]}
        oracle = {**dequantize_moe_params(p, jnp.bfloat16),
                  "router_bias": p["router_bias"]}
    want, load_d = moe_ops.moe_dense(cfg, oracle, x)
    got, load_g = moe_ops.moe_grouped(cfg, p, x, interpret=True)

    S, E = rows * 4, cfg.num_experts
    bm = kernel.auto_block_rows(S, E)
    assert seen["rows"] == kernel.packed_rows(S, E, bm) == S * bm
    assert seen["tiles"] == S
    load = np.asarray(load_g)
    np.testing.assert_array_equal(load, np.asarray(load_d))
    assert load[-1] == 0 and load[:-1].sum() == S
    if routing == "mix":
        # Which two of its own four a row takes is the tie-break's.
        assert (load[62:64] == rows).all() and (load[:62] <= 1).all()
        assert (load[:-1].reshape(16, 4)[:rows].sum(1) == 2).all()
    else:
        if routing == "distinct":
            counts[own.reshape(-1)] = 1
        np.testing.assert_array_equal(load[:-1], counts)
    _assert_bf16_close(want, got)
    assert float(jnp.abs(want.astype(jnp.float32)).max()) > 0.05


def _tiles(counts, bm):
    return int((-(-np.asarray(counts) // bm)).sum())


@pytest.mark.parametrize("S,E,bm", [
    (4, 64, 8), (8, 64, 8), (16, 64, 8), (32, 64, 8), (32, 128, 8),
    (64, 128, 8), (63, 64, 8), (64, 64, 8), (65, 64, 8), (5, 3, 8),
    (1, 1, 8), (9, 1, 8), (2048, 64, 64), (1024, 128, 16)])
def test_packed_rows_holds_every_routing_and_one_fills_it(S, E, bm):
    """`packed_rows` is the padded total of the worst routing of `S`
    assignments over `E` experts at a tile of `bm`: one row in each group
    that can exist and the rest in one of them reaches it (below `E`
    assignments that is an expert of its own for each), and no routing
    passes it: all to one expert, spread evenly, and seeded random counts
    from near-uniform to heavily skewed."""
    from dynamo_tpu.ops.pallas.moe_grouped import packed_rows

    bound = packed_rows(S, E, bm)
    assert bound % bm == 0 and bound >= bm
    groups = min(S, E)
    worst = np.zeros(E, np.int64)
    worst[:groups] = 1
    worst[0] += S - groups
    assert _tiles(worst, bm) * bm == bound
    one = np.zeros(E, np.int64)
    one[E // 2] = S
    assert _tiles(one, bm) * bm <= bound
    assert _tiles(np.full(E, S // E) + (np.arange(E) < S % E), bm) * bm \
        <= bound
    rng = np.random.default_rng(S * 1000 + E + bm)
    for skew in (0.05, 0.3, 1.0, 10.0):
        for _ in range(25):
            counts = rng.multinomial(S, rng.dirichlet(np.full(E, skew)))
            assert _tiles(counts, bm) * bm <= bound
    if S < E:
        assert bound == S * bm <= (S + E * (bm - 1)) // bm * bm


def test_packed_rows_is_never_empty():
    from dynamo_tpu.ops.pallas.moe_grouped import packed_rows

    assert packed_rows(0, 64, 8) == 8


# (assignments, experts) of every expert-layer program of the benchmark's
# configurations with as many assignments as experts or more, at the
# worker's default buckets (rows 1-64, packed prefill of 128 and 512
# tokens): glm-4.7-flash (64 experts, 4 a token) from row bucket 16 up and
# both chunks; sdar-30b-a3b (128 experts, 8 a token, blocks of 4) from row
# bucket 4 up and both chunks.
SERVED_AT_OR_OVER_E = (
    [(r * 4, 64) for r in (16, 32, 64, 128, 512)]
    + [(r * 4 * 8, 128) for r in (4, 8, 16, 32, 64)]
    + [(t * 8, 128) for t in (128, 512)])


@pytest.mark.parametrize("S,E", SERVED_AT_OR_OVER_E)
def test_packed_rows_is_what_it_was_from_as_many_assignments_as_experts(
        S, E):
    """From `S >= E` on the packed buffer is the one `moe_grouped` had
    before `packed_rows` (`E` ragged groups' worth), so the accepted
    cells' program shapes cannot drift: pinned for every such shape the
    three configurations' buckets reach, at the tile `auto_block_rows`
    gives and at every tile of its ladder."""
    from dynamo_tpu.ops.pallas import moe_grouped as kernel

    assert S >= E
    for bm in {kernel.auto_block_rows(S, E), *kernel._BLOCK_ROW_LADDER}:
        assert kernel.packed_rows(S, E, bm) \
            == max(bm, (S + E * (bm - 1)) // bm * bm)


def test_grouped_int8_matches_dense_on_dequantized_weights():
    """int8-weight grouped (dequant-in-VMEM) == dense oracle run on the
    host-dequantized weights, byte for byte — the same static-structure
    discipline as kv_quant: quantization changes the weights once, not
    the compute path's numerics."""
    from dynamo_tpu.ops.pallas import (
        dequantize_moe_params,
        moe_params_quantized,
        quantize_moe_params,
    )

    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), _moe_params())
    q = quantize_moe_params(p)
    assert moe_params_quantized(q) and not moe_params_quantized(p)
    x = jax.random.normal(jax.random.key(4), (2, 16, CFG.hidden_size),
                          jnp.float32).astype(jnp.bfloat16)
    want, load_d = moe_ops.moe_dense(
        CFG, dequantize_moe_params(q, jnp.bfloat16), x)
    got, load_g = moe_ops.moe_grouped(CFG, q, x, interpret=True)
    _assert_bf16_close(want, got)
    np.testing.assert_array_equal(np.asarray(load_g), np.asarray(load_d))


def test_dispatch_stats_tail_counts_drops():
    """[E+1] stats contract: slots [:E] are the PRE-drop routing counts,
    the tail is the dropped-assignment count — zero at the exact default,
    honest (nonzero) under a bounding capacity."""
    p = _moe_params()
    x = jax.random.normal(jax.random.key(2), (2, 8, CFG.hidden_size),
                          jnp.float32)
    N = 2 * 8
    k = CFG.num_experts_per_token
    _, exact = moe_ops.moe_dispatch(CFG, p, x)
    assert exact.shape == (CFG.num_experts + 1,)
    assert int(exact[-1]) == 0
    assert int(exact[:-1].sum()) == N * k
    _, bounded = moe_ops.moe_dispatch(CFG, p, x, capacity=1)
    assert int(bounded[-1]) > 0
    # Routing is capacity-independent: same pre-drop counts either way.
    np.testing.assert_array_equal(np.asarray(bounded[:-1]),
                                  np.asarray(exact[:-1]))


def test_resolve_moe_mode_ladder():
    """The mode ladder's resolution rules and pointed errors."""
    from dynamo_tpu.parallel.sharding import resolve_moe_mode

    # Meshless auto on CPU → dense (grouped needs TPU + geometry).
    assert resolve_moe_mode(CFG, None) == "dense"
    assert resolve_moe_mode(CFG, None, "grouped") == "grouped"
    with pytest.raises(ValueError, match="needs a mesh with an ep axis"):
        resolve_moe_mode(CFG, None, "dispatch")
    with pytest.raises(ValueError, match="not in"):
        resolve_moe_mode(CFG, None, "bogus")
    mesh = make_mesh(MeshConfig(dp=4, ep=2), jax.devices())
    with pytest.raises(ValueError, match="meshless fast path"):
        resolve_moe_mode(CFG, mesh, "grouped")
    assert resolve_moe_mode(CFG, mesh) == "dispatch"
    mesh_d = make_mesh(MeshConfig(dp=8), jax.devices())
    assert resolve_moe_mode(CFG, mesh_d) == "dense"
    # Dense models short-circuit whatever the mesh looks like.
    assert resolve_moe_mode(mcfg.get_config("tiny-test"), mesh) == "dense"


def test_moe_decode_windows_match_single_step():
    """MoE decode windows (r5): the fused window threads the expert-load
    aux through its loop carry, so MoE serving gets the fast decode path
    — greedy output must match the single-step engine, and the telemetry
    must account for every windowed token."""
    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.models import config as mcfg

    cfg = mcfg.get_config("tiny-moe")

    def run(window):
        core = EngineCore(EngineConfig(
            model=cfg, num_blocks=64, decode_window=window,
            enable_prefix_cache=False,
            scheduler=SchedulerConfig(
                max_seqs=4, block_size=8, max_pages_per_seq=8,
                max_prefill_chunk=16,
                decode_buckets=(1, 2, 4), prefill_buckets=(8, 16))))
        core.add_request("a", [5, 6, 7, 8, 9, 10],
                         SamplingParams(max_tokens=10))
        core.add_request("b", list(range(20, 29)),
                         SamplingParams(max_tokens=10))
        out = {}
        for _ in range(300):
            for d in core.step():
                out.setdefault(d.request_id, []).extend(d.token_ids)
            if not core._requests:
                break
        assert not core._requests
        return out, core.snapshot_expert_load()

    single, load1 = run(window=1)
    windowed, loadw = run(window=4)
    assert windowed == single, "MoE window diverged from single-step"
    # Load telemetry accounts every processed token x top-k x layers.
    # (Window overshoot may process a few discarded tokens; the count
    # must be at least the single-step total and divisible by k*L.)
    kL = cfg.num_experts_per_token * cfg.num_layers
    assert int(load1.sum()) % kL == 0
    assert int(loadw.sum()) % kL == 0
    assert int(loadw.sum()) >= int(load1.sum()) > 0


def test_moe_sharded_window_over_ep_mesh():
    """The sharded MoE window compiles and serves over a dp x ep mesh
    with load telemetry flowing."""
    import jax

    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.models import config as mcfg
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    cfg = mcfg.get_config("tiny-moe")
    mesh = make_mesh(MeshConfig(dp=2, ep=2, tp=2), jax.devices())
    core = EngineCore(EngineConfig(
        model=cfg, num_blocks=64, mesh=mesh, decode_window=4,
        enable_prefix_cache=False,
        scheduler=SchedulerConfig(
            max_seqs=4, block_size=8, max_pages_per_seq=8,
            max_prefill_chunk=16,
            decode_buckets=(2, 4), prefill_buckets=(8, 16))))
    core.add_request("a", [5, 6, 7, 8, 9, 10],
                     SamplingParams(max_tokens=8))
    core.add_request("b", list(range(20, 29)),
                     SamplingParams(max_tokens=8))
    out = {}
    for _ in range(300):
        for d in core.step():
            out.setdefault(d.request_id, []).extend(d.token_ids)
        if not core._requests:
            break
    assert not core._requests
    assert len(out["a"]) == 8 and len(out["b"]) == 8
    load = core.snapshot_expert_load()
    assert load is not None and int(load.sum()) > 0


def test_moe_dispatch_window_over_ep_mesh():
    """The DISPATCH-mode (shard_map all-to-all) window path: ep>1, tp=1
    resolves moe_mode='dispatch', and the window must still serve with
    correct telemetry."""
    import jax

    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import SchedulerConfig
    from dynamo_tpu.models import config as mcfg
    from dynamo_tpu.parallel import MeshConfig, make_mesh
    from dynamo_tpu.parallel.sharding import resolve_moe_mode

    cfg = mcfg.get_config("tiny-moe")
    mesh = make_mesh(MeshConfig(dp=4, ep=2), jax.devices())
    assert resolve_moe_mode(cfg, mesh) == "dispatch"
    core = EngineCore(EngineConfig(
        model=cfg, num_blocks=128, mesh=mesh, decode_window=4,
        enable_prefix_cache=False,
        scheduler=SchedulerConfig(
            max_seqs=8, block_size=8, max_pages_per_seq=8,
            max_prefill_chunk=16,
            decode_buckets=(4, 8), prefill_buckets=(8, 16))))
    for i in range(4):
        core.add_request(f"r{i}", list(range(5 + i, 12 + i)),
                         SamplingParams(max_tokens=8))
    out = {}
    for _ in range(300):
        for d in core.step():
            out.setdefault(d.request_id, []).extend(d.token_ids)
        if not core._requests:
            break
    assert not core._requests
    assert all(len(v) == 8 for v in out.values())
    load = core.snapshot_expert_load()
    kL = cfg.num_experts_per_token * cfg.num_layers
    assert int(load.sum()) > 0 and int(load.sum()) % kL == 0


def _serve_moe_engine(**over):
    """One meshless tiny-moe engine run with the file's shared geometry
    (compile-cache reuse): two short greedy requests, returns (tokens,
    engine)."""
    from dynamo_tpu.engine.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import SchedulerConfig

    cfg = dict(model=CFG, num_blocks=64, enable_prefix_cache=False,
               scheduler=SchedulerConfig(
                   max_seqs=4, block_size=8, max_pages_per_seq=8,
                   max_prefill_chunk=16,
                   decode_buckets=(1, 2, 4), prefill_buckets=(8, 16)))
    cfg.update(over)
    core = EngineCore(EngineConfig(**cfg))
    core.add_request("a", [5, 6, 7, 8, 9, 10], SamplingParams(max_tokens=8))
    core.add_request("b", list(range(20, 29)), SamplingParams(max_tokens=8))
    out = {}
    for _ in range(300):
        for d in core.step():
            out.setdefault(d.request_id, []).extend(d.token_ids)
        if not core._requests:
            break
    assert not core._requests
    return out, core


def test_engine_grouped_mode_matches_dense():
    """A meshless engine serving with moe_mode='grouped' (interpret mode
    on CPU) emits the SAME greedy tokens as the dense oracle engine —
    the ops-level byte-identity surviving the full serving stack — and
    the expert-load telemetry flows either way."""
    dense_out, dense_core = _serve_moe_engine(moe_mode="dense")
    grp_out, grp_core = _serve_moe_engine(moe_mode="grouped")
    assert grp_out == dense_out, "grouped engine diverged from dense"
    for core in (dense_core, grp_core):
        load = core.snapshot_expert_load()
        assert load is not None and int(load.sum()) > 0
        assert core.moe_dropped_tokens == 0


def test_packed_prefill_serves_moe():
    """packed_prefill=True on a MoE model (the exclusion this PR kills):
    token parity with the padded plane, the packed plane actually used,
    and prefill assignments landing in the expert-load telemetry."""
    padded_out, _ = _serve_moe_engine(packed_prefill=False)
    packed_out, core = _serve_moe_engine(packed_prefill=True)
    assert packed_out == padded_out, "packed MoE prefill diverged"
    assert core.counters.packed_prefill_dispatches > 0
    load = core.snapshot_expert_load()
    assert load is not None and int(load.sum()) > 0
    assert core.moe_dropped_tokens == 0


def test_short_burst_publishes_expert_load_in_metrics():
    """Drain-edge telemetry publish: a burst that finishes in < 32 steps
    must still land its expert load in ForwardPassMetrics (what the
    worker's /metrics route reads).  The periodic step_count % 32 sync
    alone left short-lived traffic dark — the live worker served a chat
    completion and exported no dynamo_moe_expert_load series."""
    _, core = _serve_moe_engine(moe_mode="grouped")
    assert core.step_count < 32  # the repro precondition: no periodic sync
    m = core.metrics
    assert m.expert_load is not None and sum(m.expert_load) > 0
    assert m.moe_dropped_tokens == 0


def _log_dispatches(core) -> list:
    """Every program dispatch of `core` from now on, as (tag, *shape)."""
    log, real = [], core.counters.note_dispatch

    def note(tag, *sig):
        log.append((tag,) + sig)
        return real(tag, *sig)

    core.counters.note_dispatch = note
    return log


def _packed_rows_of(cfg, tokens: int) -> int:
    from dynamo_tpu.ops.pallas.moe_grouped import auto_block_rows, packed_rows

    S = tokens * cfg.num_experts_per_token
    return packed_rows(S, cfg.num_experts,
                       auto_block_rows(S, cfg.num_experts))


@pytest.mark.parametrize("model", [CFG, FEW_ROWS_CFG], ids=lambda c: c.name)
def test_short_burst_publishes_packed_rows_in_metrics(monkeypatch, model):
    """`dynamo_worker_moe_packed_rows_total` beside the other `moe_*`
    series: the rows of the packed buffers the grouped kernel was handed,
    equal to `packed_rows` summed over the expert layer-forwards of the
    burst's dispatches (prefill calls, decode windows of 8 steps, single
    steps), each known from its program's static shape.  At 64 experts
    the decode steps (2 rows) have fewer assignments than experts, and
    the tokens are the dense engine's all the same.  An engine whose
    expert path does not pack counts no row."""
    from dynamo_tpu.engine.engine import EngineCore

    logs = []
    real_init = EngineCore.__init__

    def init(self, *a, **kw):
        real_init(self, *a, **kw)
        logs.append(_log_dispatches(self))

    monkeypatch.setattr(EngineCore, "__init__", init)
    dense_out, dense_core = _serve_moe_engine(model=model, moe_mode="dense")
    out, core = _serve_moe_engine(model=model, moe_mode="grouped")
    assert out == dense_out
    core.snapshot_expert_load()
    dense_core.snapshot_expert_load()
    c = core.counters
    L, K = model.num_layers, core.config.decode_window
    tokens_of = {"prefill": lambda R, T, *_: [R * T],
                 "prefill_packed": lambda T, *_: [T],
                 "window": lambda _greedy, bucket, *_: [bucket] * K,
                 "decode1g": lambda bucket, *_: [bucket],
                 "decode1": lambda bucket, *_: [bucket]}
    forwards = [t for tag, *sig in logs[1] if tag in tokens_of
                for t in tokens_of[tag](*sig)]
    assert c.moe_layer_forwards == L * len(forwards)
    assert c.moe_packed_rows == sum(
        L * _packed_rows_of(model, t) for t in forwards) > 0
    assert c.moe_packed_rows >= c.moe_assignments
    if model is FEW_ROWS_CFG:
        assert any(t * 4 < 64 for t in forwards)
    assert f"dynamo_worker_moe_packed_rows_total {c.moe_packed_rows}" \
        in c.block_metrics_lines()
    assert dense_core.counters.moe_packed_rows == 0
    assert dense_core.counters.moe_assignments == c.moe_assignments
