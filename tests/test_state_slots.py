"""Recurrent state in per-sequence slots beside the paged cache (the
`falcon_h1` block: a Mamba-2 state-space mixer beside grouped-query attention
in every layer), at tiny widths on the CPU with seeded weights: the state's
life in the engine (a slot from admission to the end, zeroed at a sequence's
first chunk, kept off padding rows), chunked and packed prefill against one
chunk, recompute-preemption, the no-reuse block source, the kernel against
the plain form, the loader, the counters, and every combination the state is
refused."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import kv_cache as kvc
from dynamo_tpu.engine.engine import (
    STATE_NO_TRANSFER, EngineConfig, EngineCore)
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import SchedulerConfig
from dynamo_tpu.models import llama, loader
from dynamo_tpu.models.config import (
    STATE_MESHLESS, STATE_NO_DIFFUSION, TINY, TINY_H1)
from dynamo_tpu.ops import ssm as ssm_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HF = {"model_type": "falcon_h1", "hidden_size": 64, "intermediate_size": 128,
      "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 16,
      "vocab_size": 256, "num_hidden_layers": 2, "rms_norm_eps": 1e-5,
      "rope_theta": 10000.0, "max_position_embeddings": 512,
      "tie_word_embeddings": False, "mamba_d_ssm": 64, "mamba_n_heads": 4,
      "mamba_d_head": 16, "mamba_d_state": 8, "mamba_n_groups": 2,
      "mamba_d_conv": 4, "mamba_chunk_size": 8, "mamba_expand": 2,
      "mamba_conv_bias": True, "mamba_proj_bias": False,
      "mamba_rms_norm": True, "mamba_norm_before_gate": False,
      "embedding_multiplier": 1.7, "lm_head_multiplier": 0.3,
      "attention_in_multiplier": 0.9, "attention_out_multiplier": 0.6,
      "key_multiplier": 0.5, "ssm_in_multiplier": 0.8,
      "ssm_out_multiplier": 0.7, "mlp_multipliers": [0.9, 0.8],
      "ssm_multipliers": [0.9, 0.7, 0.8, 1.1, 0.6]}
BS = 8


def _engine(cfg=TINY_H1, max_seqs=4, window=4, blocks=64, **kw):
    return EngineCore(EngineConfig(
        model=cfg, num_blocks=blocks, decode_window=window,
        scheduler=SchedulerConfig(block_size=BS, max_seqs=max_seqs,
                                  max_prefill_chunk=16,
                                  prefill_buckets=(8, 16)), **kw))


def _generate(core, prompts, max_tokens=11):
    for i, p in enumerate(prompts):
        core.add_request(f"r{i}", p, SamplingParams(max_tokens=max_tokens))
    out = {f"r{i}": [] for i in range(len(prompts))}
    while core.has_work:
        for d in core.step():
            out[d.request_id].extend(d.token_ids)
    return [out[f"r{i}"] for i in range(len(prompts))]


def _prompts(*lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, size=n).tolist() for n in lengths]


@pytest.fixture(scope="module")
def alone():
    """Each of three prompts served alone on the padded plane, one token a
    step: what every other way of serving them must give."""
    prompts = _prompts(5, 19, 40)
    return prompts, [_generate(_engine(window=1), [p])[0] for p in prompts]


def test_config_from_hf_maps_the_block():
    cfg = loader.config_from_hf(HF, "t")
    assert cfg == TINY_H1.replace(name="t", dtype=cfg.dtype, max_context=512)
    assert cfg.has_ssm and cfg.mamba_conv_dim == 96 \
        and cfg.mamba_proj_size == 164
    # `mamba_d_ssm` sets the mixer's width; `mamba_expand` only where it is
    # not given.
    assert loader.config_from_hf(dict(HF, mamba_expand=7), "t") == cfg
    with open(os.path.join(
            ROOT, "chipbench/configs/falcon-h1-34b-instruct-d6.json")) as f:
        real = loader.config_from_hf(json.load(f), "h1")
    real.validate()
    # The issue's count: six layers of 430.12 M and the vocabulary twice.
    assert real.param_count() == pytest.approx(5.2546e9, rel=1e-4)
    assert (real.mamba_proj_size, real.mamba_conv_dim) == (9248, 5120)
    cache_cfg = kvc.KvCacheConfig.for_model(real, 1536, 64, state_slots=64)
    assert cache_cfg.state_bytes_per_slot == 25_165_824 + 184_320
    assert cache_cfg.bytes_per_context_token == 12_288


@pytest.mark.parametrize("hf,message", [
    (dict(HF, model_type="mamba2_hybrid"), "states state-space keys"),
    ({k: v for k, v in HF.items() if not k.startswith("mamba_")
      or k == "mamba_d_state"} | {"model_type": "llama"}, "mamba_d_state"),
    (dict(HF, attn_layer_indices=[0]), "attn_layer_indices"),
    (dict(HF, mamba_proj_bias=True), "mamba_proj_bias"),
    (dict(HF, mamba_norm_before_gate=True), "mamba_norm_before_gate"),
])
def test_loader_refuses_what_it_does_not_map(hf, message):
    """A config that states `mamba_*` keys under a type this loader does not
    map is refused (it was served as a plain dense decoder), and so is what
    the falcon_h1 block is not built for."""
    with pytest.raises(ValueError, match=message):
        loader.config_from_hf(hf, "t").validate()


def test_cache_gains_two_leaves_a_layer_and_a_dense_model_none():
    cache = kvc.init_cache(kvc.KvCacheConfig.for_model(
        TINY_H1, 16, BS, state_slots=4))
    assert set(cache) == {"k", "v", "ssm", "conv"}
    assert [a.shape for a in cache["ssm"]] == [(5, 4, 16, 8)] * 2
    assert cache["ssm"][0].dtype == jnp.float32
    assert [a.shape for a in cache["conv"]] == [(5, 3, 96)] * 2
    dense = kvc.KvCacheConfig.for_model(TINY, 16, BS, state_slots=4)
    assert set(kvc.init_cache(dense)) == {"k", "v"}
    assert dense.state_bytes_per_slot == 0 and not dense.has_state


def test_dense_step_programs_take_no_new_argument():
    """The accepted configurations' programs are what they were: a model
    without state layers gets no state argument and no leaf."""
    core = _engine(TINY, enable_prefix_cache=False)
    assert set(core.cache) == {"k", "v"}
    toks = _generate(core, _prompts(5, 19))
    assert all(len(t) == 11 for t in toks)
    # The engine handed the programs seven and eleven arguments, as before.
    assert "slots" not in core._window_state
    assert core._state_args(4) == ()
    assert not any("ssm" in line
                   for line in core.counters.block_metrics_lines())


def test_bucket_row_steps_count_the_rows_the_programs_have():
    """Beside the live row-steps the counters keep the row-steps of the
    decode programs' buckets (host integers at the dispatch): one prompt
    decodes on a bucket of one, so both move alike; three decode on a bucket
    of four, so a quarter of the bucket's rows are padding the update kernel
    moves no state for.  Both are on `/metrics`."""
    core = _engine()
    c = core.counters
    _generate(core, _prompts(9))
    assert c.ssm_decode_bucket_row_steps == c.ssm_decode_row_steps > 0
    before = c.snapshot()
    _generate(core, _prompts(5, 6, 4))   # one chunk: they decode as three
    live = c.ssm_decode_row_steps - before.ssm_decode_row_steps
    bucket = c.ssm_decode_bucket_row_steps \
        - before.ssm_decode_bucket_row_steps
    assert live >= 30 and live < bucket <= 4 * live
    lines = c.block_metrics_lines()
    assert f"dynamo_worker_ssm_decode_row_steps_total " \
        f"{c.ssm_decode_row_steps}" in lines
    assert f"dynamo_worker_ssm_decode_bucket_row_steps_total " \
        f"{c.ssm_decode_bucket_row_steps}" in lines


def test_capture_tallies_count_what_is_dispatched_inside_a_capture():
    """A device capture's shares divide its device time by the work of its
    own seconds: the `ssm_capture_*` tallies move only while
    `DeviceProfiler.capture` has the phase clocks traced, by what the
    all-time tallies move then, and count the steps and calls beside."""
    core = _engine()
    c = core.counters
    _generate(core, _prompts(19))
    assert c.ssm_decode_row_steps > 0 and c.ssm_prefill_tokens == 19
    assert (c.ssm_capture_decode_row_steps, c.ssm_capture_decode_steps,
            c.ssm_capture_prefill_tokens, c.ssm_capture_prefill_calls) \
        == (0, 0, 0, 0)
    before = c.snapshot()
    core.profiler._trace_phases(True)         # what capture() does around
    try:                                      # its start_trace / stop_trace
        _generate(core, _prompts(5, 40, seed=4))
    finally:
        core.profiler._trace_phases(False)
    inside = c.snapshot()
    _generate(core, _prompts(9, seed=5))
    assert c.ssm_decode_row_steps > inside.ssm_decode_row_steps
    assert c.ssm_capture_decode_row_steps \
        == inside.ssm_decode_row_steps - before.ssm_decode_row_steps
    assert c.ssm_capture_prefill_tokens == 45
    # 45 tokens in chunks of at most 16; two rows, then one, a decode step.
    assert 3 <= c.ssm_capture_prefill_calls <= 5
    assert c.ssm_capture_decode_steps <= c.ssm_capture_decode_row_steps \
        <= 2 * c.ssm_capture_decode_steps
    steps = (inside.window_dispatches - before.window_dispatches) * 4 \
        + inside.single_step_dispatches - before.single_step_dispatches
    assert c.ssm_capture_decode_steps == steps
    assert "dynamo_worker_ssm_capture_decode_steps_total " \
        f"{c.ssm_capture_decode_steps}" in c.block_metrics_lines()


def test_a_prompt_over_three_chunks_equals_one_chunk():
    """The forward step itself: 40 tokens as 16 + 16 + 8 (the scan and the
    convolution start from the slot where the prompt continues) against one
    chunk, logits and the state left behind."""
    cfg = TINY_H1
    params = llama.init_params(cfg, jax.random.key(0))
    step = jax.jit(llama.make_forward_step(cfg, BS))
    toks = np.asarray(_prompts(40)[0], np.int32)
    bt = jnp.asarray([[1, 2, 3, 4, 5]], jnp.int32)

    def run(chunks):
        cache = kvc.init_cache(kvc.KvCacheConfig.for_model(
            cfg, 16, BS, state_slots=4))
        # Whatever the slot's last occupant left must not matter.
        cache["ssm"] = [a + 5.0 for a in cache["ssm"]]
        cache["conv"] = [a + 5.0 for a in cache["conv"]]
        out, pos = [], 0
        for n in chunks:
            t = np.zeros((1, 40), np.int32)
            p = np.full((1, 40), 10_000, np.int32)
            t[0, :n] = toks[pos:pos + n]
            p[0, :n] = np.arange(pos, pos + n)
            logits, cache = step(params, cache, jnp.asarray(t),
                                 jnp.asarray(p), jnp.asarray([pos + n]), bt,
                                 None, state_slots=jnp.asarray([2]))
            out.append(np.asarray(logits[0, :n]))
            pos += n
        return np.concatenate(out), cache

    whole, c1 = run([40])
    parts, c3 = run([16, 16, 8])
    np.testing.assert_allclose(parts, whole, atol=2e-6)
    np.testing.assert_allclose(c3["ssm"][1][2], c1["ssm"][1][2], atol=1e-6)
    np.testing.assert_allclose(c3["conv"][1][2], c1["conv"][1][2], atol=1e-6)
    # No other slot was touched, the scratch slot aside.
    for s in (0, 1, 3):
        assert float(jnp.abs(c3["ssm"][0][s] - 5.0).max()) == 0.0


def test_a_reused_slot_starts_from_zero_with_windows_in_flight(alone):
    """One slot: the second sequence takes it while windows of the first
    (which ended on a stop token inside a window) are still in flight on the
    device; its first chunk starts from zero all the same."""
    prompts, want = alone
    core = _engine(max_seqs=1, window=4, window_pipeline_depth=4)
    first = want[2]
    core.add_request("a", prompts[2], SamplingParams(
        max_tokens=64, stop_token_ids=[first[5]]))
    core.add_request("b", prompts[1], SamplingParams(max_tokens=11))
    out = {"a": [], "b": []}
    slots = set()
    while core.has_work:
        for d in core.step():
            out[d.request_id].extend(d.token_ids)
        slots |= {r.slot for r in core.scheduler.running}
    assert slots == {0}
    assert out["a"] == first[:6]
    assert out["b"] == want[1]


def test_padding_rows_never_touch_a_live_slot():
    """A decode bucket wider than its live rows: the padding rows read and
    write the scratch slot, and an idle slot's state stays what it was."""
    core = _engine(max_seqs=4)
    mark = [a.at[3].set(9.0) for a in core.cache["ssm"]]
    core.cache = dict(core.cache, ssm=mark)
    # Three live sequences in slots 0-2 of a bucket of 4: one padding row.
    toks = _generate(core, _prompts(5, 6, 7))
    assert all(len(t) == 11 for t in toks)
    for leaf in core.cache["ssm"]:
        assert float(jnp.abs(leaf[3] - 9.0).max()) == 0.0
    assert core._slot_rows(4, [], None).tolist() == [4, 4, 4, 4]


def test_a_recompute_preempted_sequence_resumes_to_the_same_tokens(alone):
    """Preempted mid-decode (its pages and its slot given up), a sequence
    prefills prompt + generated from token 0 into a fresh state and goes on
    as if nothing had happened."""
    prompts, want = alone
    core = _engine(max_seqs=2)
    core.add_request("a", prompts[1], SamplingParams(max_tokens=11))
    got, preempted = [], False
    while core.has_work:
        for d in core.step():
            got.extend(d.token_ids)
        req = core._requests.get("a")
        if not preempted and req is not None and len(got) >= 5:
            core._drain_inflight([])
            got = list(req.prompt_tokens[len(prompts[1]):]) \
                + list(req.output_tokens)
            core._hash_seqs.pop("a", None)
            core._published_blocks.pop("a", None)
            core.scheduler.preempt(req)
            preempted = True
    assert preempted and got == want[1]


def test_a_prefix_that_would_hit_on_a_dense_model_is_prefilled_whole():
    """The engine gives a model with state layers the no-reuse block source
    by itself: the same prompt twice is prefilled twice, where a dense model
    skips the second one's cached blocks."""
    prompt = _prompts(40)[0]
    for cfg, hit in ((TINY, True), (TINY_H1, False)):
        core = _engine(cfg)
        first = _generate(core, [prompt], max_tokens=3)[0]
        before = core.counters.prefill_tokens_dispatched
        core.add_request("again", prompt, SamplingParams(max_tokens=3))
        again = []
        while core.has_work:
            for d in core.step():
                again.extend(d.token_ids)
        prefilled = core.counters.prefill_tokens_dispatched - before
        assert again == first
        assert (prefilled < 40) == hit, (cfg.name, prefilled)
        assert core._managed_cache == hit
        assert (core.scheduler.prefix_hit_tokens > 0) == hit


@pytest.mark.parametrize("build,message", [
    (lambda: _engine(kv_quant="int8"), "no int8 KV form"),
    (lambda: _engine(speculative_tokens=2), "speculative decoding"),
    (lambda: _engine(host_blocks=8), "no tier offload"),
    (lambda: _engine(disk_blocks=8), "no tier offload"),
    (lambda: _engine(mesh=object()), STATE_MESHLESS[:40]),
    (lambda: llama.make_forward_step(TINY_H1, BS, mesh=object()),
     "serves meshless"),
    (lambda: llama.make_forward_step(TINY_H1, BS, sp_ring=True),
     "ring/sequence-parallel"),
    (lambda: TINY_H1.replace(diffusion_block_length=4, denoising_steps=4,
                             mask_token_id=255).validate(),
     STATE_NO_DIFFUSION[:40]),
    (lambda: kvc.KvCacheConfig.for_model(TINY_H1, 16, BS, kv_quant="int8",
                                         state_slots=4), "no int8 KV form"),
    (lambda: _engine().export_blocks([1]), "disaggregated transfer"),
    (lambda: _engine().export_blocks_device([1]), "drain migration"),
    (lambda: _engine().import_blocks({}), "tier offload"),
    (lambda: TINY.replace(lm_head_multiplier=0.5).validate(),
     "muP multipliers"),
], ids=["int8", "speculative", "host-tier", "disk-tier", "mesh",
        "mesh-step", "ring", "block-diffusion", "int8-cache", "export",
        "export-device", "import", "multipliers-without-mixer"])
def test_each_refused_combination_raises_by_name(build, message):
    with pytest.raises(ValueError, match=message):
        build()
    assert "disaggregated transfer" in STATE_NO_TRANSFER \
        and "drain migration" in STATE_NO_TRANSFER \
        and "tier offload" in STATE_NO_TRANSFER


def test_state_update_kernel_equals_the_plain_form():
    """The Pallas kernel in interpret mode against the gather-update-scatter
    form: outputs, the stepped slots, and every other slot untouched."""
    from dynamo_tpu.ops.pallas import ssm as kernel

    k = jax.random.split(jax.random.key(0), 6)
    S, H, P, N, G, R = 6, 16, 8, 128, 2, 4
    assert kernel.state_update_geometry_ok(H, P, N, G)
    assert kernel.state_update_geometry_ok(32, 128, 256, 2)
    assert not kernel.state_update_geometry_ok(4, 16, 8, 2)      # tiny-h1
    ssm = jax.random.normal(k[0], (S, H, P, N))
    slots = jnp.asarray([3, 0, 5, 5])          # two rows on the scratch slot
    x = jax.random.normal(k[1], (R, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[2], (R, H)))
    a = -jnp.exp(jax.random.normal(k[3], (H,)))
    b = jax.random.normal(k[4], (R, G, N))
    c = jax.random.normal(k[5], (R, G, N))
    y, out = ssm_ops.ssm_state_update(ssm, slots, x, dt, a, b, c,
                                      interpret=True)
    rep = H // G
    s = ssm[slots] * jnp.exp(dt * a)[..., None, None] \
        + (dt[..., None] * x)[..., None] \
        * jnp.repeat(b, rep, axis=1)[:, :, None, :]
    want = jnp.sum(s * jnp.repeat(c, rep, axis=1)[:, :, None, :], axis=-1)
    np.testing.assert_allclose(y[:2], want[:2], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out[3], s[0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(out[0], s[1], rtol=1e-6, atol=1e-6)
    for untouched in (1, 2, 4):
        assert float(jnp.abs(out[untouched] - ssm[untouched]).max()) == 0.0


def _parent_update_kernel(slots_ref, da_ref, dx_ref, b_ref, c_ref, s_ref,
                          y_ref, s_out_ref):
    """The state-update kernel as it stood before PR 49 (8 heads a grid
    step, a masked lane reduction for each of a head's two scalars and one
    for the read-out, every row of the bucket stepped), kept here as the
    oracle of the state's arithmetic: `s * da + dx * b` has to come out bit
    for bit the same."""
    from jax.experimental import pallas as pl

    del slots_ref
    j = pl.program_id(1)
    da_t, dx_t = da_ref[0], dx_ref[0]                # [P, H]
    b, c = b_ref[0], c_ref[0]                        # [1, N]
    lane = jax.lax.broadcasted_iota(jnp.int32, da_t.shape, 1)

    @pl.when(j == 0)
    def _():
        y_ref[0] = jnp.zeros_like(y_ref[0])

    acc = y_ref[0]
    for k in range(8):
        sel = lane == j * 8 + k
        da = jnp.sum(jnp.where(sel, da_t, 0.0), axis=-1, keepdims=True)
        dx = jnp.sum(jnp.where(sel, dx_t, 0.0), axis=-1, keepdims=True)
        s = s_ref[0, k].astype(jnp.float32) * da + dx * b
        s_out_ref[0, k] = s.astype(s_out_ref.dtype)
        acc = jnp.where(sel, jnp.sum(s * c, axis=-1, keepdims=True), acc)
    y_ref[0] = acc


@jax.jit
def _parent_state_update(ssm, slots, x, dt, a, b, c):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, H, P = x.shape
    N, G = ssm.shape[-1], b.shape[1]
    per_group = H // G // 8
    da_t = jnp.broadcast_to(jnp.exp(dt * a)[:, None, :], (R, P, H))
    dx_t = (dt[..., None] * x).transpose(0, 2, 1)
    row = pl.BlockSpec((1, P, H), lambda r, j, sl: (r, 0, 0))
    group = pl.BlockSpec((1, 1, N),
                         lambda r, j, sl: (r * G + j // per_group, 0, 0))
    state = pl.BlockSpec((1, 8, P, N), lambda r, j, sl: (sl[r], j, 0, 0))
    y_t, ssm = pl.pallas_call(
        _parent_update_kernel,
        out_shape=(jax.ShapeDtypeStruct((R, P, H), jnp.float32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R, H // 8),
            in_specs=[row, row, group, group, state],
            out_specs=(row, state)),
        input_output_aliases={5: 1}, interpret=True,
    )(slots.astype(jnp.int32), da_t, dx_t, b.reshape(R * G, 1, N),
      c.reshape(R * G, 1, N), ssm)
    return y_t.transpose(0, 2, 1), ssm


# Both published geometries (heads, head dimension, state, groups) on a leaf
# cut to six slots and the scratch slot, which is slot 6.
GEOMETRIES = {"nemotron-3-super": (128, 64, 128, 8),
              "falcon-h1": (32, 128, 256, 2)}
ROWS = {"scattered": [6, 3, 6, 0, 5, 6, 6, 1],
        "one-live-of-16": [6] * 9 + [2] + [6] * 6,
        "every-row-live": [4, 0, 5, 2],
        "one-row": [3],
        "24-rows": [6] * 4 + [1, 6, 6, 4] + [6] * 9 + [0, 6, 5, 6, 6, 2, 6]}


@pytest.mark.parametrize("rows", list(ROWS))
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_state_update_kernel_at_the_published_geometries(geometry, rows):
    """The kernel in interpret mode on a leaf filled with noise: live rows'
    `y` equals the plain form's, their stepped slots are bit for bit what
    the plain form and the kernel before PR 49 write, a padding row reads
    zeros, and every slot no live row names is bit for bit what it was, the
    scratch slot too (a padding row moves no state).

    The noise of the leaf and of B is signed powers of two, so that both
    products of `s * da + dx * b` are exact and the sum is rounded once
    however a compiler fuses it: XLA:CPU contracts the multiply-add of one
    interpreted program and not of another, which moves a last bit on
    Gaussian noise and says nothing of the kernel (on the chip,
    `tools/state_update_chip_check.py` holds Gaussian noise to the plain
    form)."""
    from dynamo_tpu.ops.pallas import ssm as kernel

    H, P, N, G = GEOMETRIES[geometry]
    slots = np.asarray(ROWS[rows], np.int32)
    S, R = 7, len(slots)
    assert kernel.state_update_geometry_ok(H, P, N, G)
    hb = kernel.state_update_head_block(H, P, N, G)
    assert hb * P * N * 4 == kernel.STATE_BLOCK_BYTES == 2 << 20
    k = jax.random.split(jax.random.key(R), 8)

    def powers_of_two(key, sign_key, shape):
        return jnp.exp2(jax.random.randint(key, shape, -3, 4).astype(
            jnp.float32)) * jnp.where(jax.random.bernoulli(sign_key, 0.5,
                                                           shape), 1.0, -1.0)

    ssm = powers_of_two(k[0], k[6], (S, H, P, N))
    x = jax.random.normal(k[1], (R, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[2], (R, H)))
    a = -jnp.exp(jax.random.normal(k[3], (H,)))
    b = powers_of_two(k[4], k[7], (R, G, N))
    c = jax.random.normal(k[5], (R, G, N))
    y, out = ssm_ops.ssm_state_update(ssm, jnp.asarray(slots), x, dt, a, b,
                                      c, interpret=True)
    want_y, want = ssm_ops.state_update_plain(ssm, jnp.asarray(slots), x, dt,
                                              a, b, c)
    _, parent = _parent_state_update(ssm, jnp.asarray(slots), x, dt, a, b, c)
    live = slots != S - 1
    np.testing.assert_allclose(y[live], want_y[live], rtol=1e-5, atol=2e-4)
    assert float(jnp.abs(y[~live]).max(initial=0.0)) == 0.0
    for slot in range(S):
        if slot in slots[live]:
            assert bool(jnp.array_equal(out[slot], want[slot])), slot
            assert bool(jnp.array_equal(out[slot], parent[slot])), slot
        else:
            assert bool(jnp.array_equal(out[slot], ssm[slot])), slot


def test_one_state_update_program_a_bucket_whatever_is_live():
    """The live count reaches the kernel as a value on the device, never as
    a shape or a static argument: a bucket with three live rows, with seven,
    with none and with all has one traced program, and each of those calls
    still equals the plain form."""
    H, P, N, G, S, R = 16, 8, 128, 2, 9, 8
    k = jax.random.split(jax.random.key(1), 6)
    ssm = jax.random.normal(k[0], (S, H, P, N))
    x = jax.random.normal(k[1], (R, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[2], (R, H)))
    a = -jnp.exp(jax.random.normal(k[3], (H,)))
    b = jax.random.normal(k[4], (R, G, N))
    c = jax.random.normal(k[5], (R, G, N))
    step = jax.jit(lambda *args: ssm_ops.ssm_state_update(*args,
                                                          interpret=True))
    for slots in ([8, 2, 8, 8, 5, 8, 0, 8], [1, 8, 0, 3, 2, 7, 6, 4],
                  [8] * 8, [7, 6, 5, 4, 3, 2, 1, 0]):
        slots = jnp.asarray(slots)
        y, out = step(ssm, slots, x, dt, a, b, c)
        want_y, want = ssm_ops.state_update_plain(ssm, slots, x, dt, a, b, c)
        live = np.asarray(slots) != S - 1
        np.testing.assert_allclose(y[live], want_y[live], rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(out[:S - 1], want[:S - 1], rtol=1e-6,
                                   atol=1e-6)
    assert step._cache_size() == 1


def test_chunk_scan_kernel_equals_the_plain_form():
    """The scan kernel in interpret mode against the plain chunked form:
    two segments of two scan chunks (one from its slot's state, one fresh),
    a scan chunk that belongs to none, every output and both last states."""
    from dynamo_tpu.ops.pallas import ssm as kernel

    NC, Q, H, P, G, N, R = 5, 128, 16, 128, 2, 128, 3
    assert kernel.chunk_scan_geometry_ok(H, P, N, G, Q)
    assert kernel.chunk_scan_geometry_ok(32, 128, 256, 2, 128)
    assert not kernel.chunk_scan_geometry_ok(4, 16, 8, 2, 8)     # tiny-h1
    k = jax.random.split(jax.random.key(0), 6)
    x = jax.random.normal(k[0], (NC, Q, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (NC, Q, H)) - 2)
    a = -jnp.exp(jax.random.normal(k[2], (H,)))
    b = 0.3 * jax.random.normal(k[3], (NC, Q, G, N))
    c = 0.3 * jax.random.normal(k[4], (NC, Q, G, N))
    first = jnp.asarray([1, 0, 1, 0, 0])
    seg = jnp.asarray([0, 0, 2, 2, 3])
    init = jax.random.normal(k[5], (R + 1, H, P, N)).at[2].set(0.0)
    y, fin = ssm_ops.ssm_chunk_scan(x, dt, a, b, c, first, seg, init,
                                    interpret=True)
    # The plain form of the same jit (off the TPU and not asked to
    # interpret, it is what runs).
    want_y, want_fin = ssm_ops.ssm_chunk_scan(x, dt, a, b, c, first, seg,
                                              init)
    np.testing.assert_allclose(y[:4], want_y[:4], rtol=1e-4, atol=1e-4)
    for r in (0, 2):
        np.testing.assert_allclose(fin[r], want_fin[r], rtol=1e-5,
                                   atol=1e-5)


def test_chunk_scan_restarts_at_every_segment():
    """`mamba_prefill` over three segments packed on one axis (one of them a
    continuation from its slot) against each run alone from the same
    state."""
    cfg = TINY_H1
    p = llama.init_params(cfg, jax.random.key(1))["layers"][0]["ssm"]
    key = jax.random.split(jax.random.key(2), 4)
    lens, starts = [5, 19, 11], [0, 8, 32]
    hs = [jax.random.normal(key[i], (n, cfg.hidden_size))
          for i, n in enumerate(lens)]
    shape = (6,) + (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state)
    ssm = jax.random.normal(key[3], shape)
    conv = jnp.ones((6, 3, cfg.mamba_conv_dim))
    slots = jnp.asarray([2, 0, 4, 5])
    fresh = jnp.asarray([True, False, True, False])
    flat = jnp.zeros((48, cfg.hidden_size))
    seg = np.zeros(48, np.int32)
    for i, (s0, h) in enumerate(zip(starts, hs)):
        flat = flat.at[s0:s0 + h.shape[0]].set(h)
        seg[s0:s0 + h.shape[0]] = i
    out, ssm2, conv2 = ssm_ops.mamba_prefill(
        cfg, p, flat, ssm, conv, slots, jnp.asarray(seg),
        jnp.asarray(starts + [0]), jnp.asarray(lens + [0]), fresh)
    for i, h in enumerate(hs):
        n = h.shape[0]
        one, s1, c1 = ssm_ops.mamba_prefill(
            cfg, p, jnp.zeros((24, cfg.hidden_size)).at[:n].set(h), ssm,
            conv, slots[i:i + 1], jnp.zeros((24,), jnp.int32),
            jnp.asarray([0]), jnp.asarray([n]), fresh[i:i + 1])
        np.testing.assert_allclose(out[starts[i]:starts[i] + n], one[:n],
                                   atol=2e-6)
        np.testing.assert_allclose(ssm2[slots[i]], s1[slots[i]], atol=1e-6)
        np.testing.assert_allclose(conv2[slots[i]], c1[slots[i]], atol=1e-6)
    # Slots 1 and 3 belong to nobody here.
    assert float(jnp.abs(ssm2[1] - ssm[1]).max()) == 0.0
    assert float(jnp.abs(ssm2[3] - ssm[3]).max()) == 0.0


def test_embeddings_leave_every_live_slot_alone():
    core = _engine()
    mark = [a.at[:4].set(3.0) for a in core.cache["ssm"]]
    core.cache = dict(core.cache, ssm=mark)
    out = core.embed_tokens(_prompts(5, 9))
    assert out.shape == (2, 64) and np.isfinite(out).all()
    for leaf in core.cache["ssm"]:
        assert float(jnp.abs(leaf[:4] - 3.0).max()) == 0.0
