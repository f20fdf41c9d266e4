"""One walk over layer kinds (models/llama.walk_layers) under every step
builder: a tiny model of each kind, seeded weights, on the CPU.  The packed
prefill step and the forward step are the same layers met by different
mixers, so the same prompt through either gives the same logits and leaves
the same cache, and a decode window continues the same from both; and the
engine serves three prompts together, on the padded and on the packed
plane, as it serves each alone."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import kv_cache as kvc
from dynamo_tpu.engine.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import SchedulerConfig
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import (
    TINY, TINY_GEMMA, TINY_H1, TINY_MLA, TINY_MOE, TINY_PATTERN, TINY_SDAR,
    TINY_WINDOW)

BS = 8
# kind -> (model, the cache's kv_quant): the classic layer with each of its
# forks taken once, the pattern's three kinds ("ME*ME"), and the parallel
# kind (attention and experts on one norm; three window layers, whose pages
# are a group of their own, beside a full one).
KINDS = {
    "dense": (TINY, "none"),
    "int8": (TINY, "int8"),
    "post-norms": (TINY_GEMMA, "none"),
    "experts": (TINY_MOE, "none"),
    "block-mask": (TINY_SDAR, "none"),
    "latent": (TINY_MLA, "none"),
    "state-beside-attention": (TINY_H1, "none"),
    "pattern": (TINY_PATTERN, "none"),
    "parallel-window": (TINY_WINDOW, "none"),
}


def _cases(kinds, ways):
    """(kind, way) pairs: the ways through the grouped expert path for the
    kinds with an expert layer only."""
    return [(k, way) for k in kinds for way in ways
            if way not in ("grouped", "packed-grouped") or KINDS[k][0].is_moe]


@pytest.mark.parametrize("kind,moe_mode",
                         _cases(KINDS, ("dense", "grouped")))
def test_packed_prefill_and_forward_step_are_the_same_layers(kind, moe_mode):
    """A 21-token prompt through the packed prefill step (one segment of a
    32-row pack beside a pad segment) and through the forward step (one
    padded row): the same logits at the last token, the same cache leaves
    outside the null block and the scratch slot, and four greedy window
    steps from either cache give the same tokens.  A kind that one builder
    serves and the other does not (a mixer left out, a leaf not written)
    fails here."""
    cfg, quant = KINDS[kind]
    moe = cfg.is_moe
    n, T = 21, 32
    params = llama.init_params(cfg, jax.random.key(0))
    cache = kvc.init_cache(kvc.KvCacheConfig.for_model(
        cfg, 16, BS, kv_quant=quant, state_slots=2 if cfg.has_ssm else 0,
        window_blocks=12))
    tokens = np.random.default_rng(5).integers(1, 250, size=n).astype(np.int32)
    pages = np.array([[3, 9, 4, 7]], np.int32)
    state = (np.zeros((1,), np.int32),) if cfg.has_ssm else ()
    # What a step takes beside its pages, by name: a row's (a segment's)
    # state slot, or its table of window-group pages (other pages than the
    # full group's, of another pool).
    extra, extra_packed = {}, {}
    if cfg.has_ssm:
        extra = {"state_slots": state[0]}
        extra_packed = {"state_slots": np.zeros((2,), np.int32)}
    if cfg.has_window:
        extra = {"window_tables": np.array([[5, 2, 8, 1]], np.int32)}
        extra_packed = {"window_tables": np.array(
            [[5, 2, 8, 1], [0, 0, 0, 0]], np.int32)}

    step = jax.jit(llama.make_forward_step(
        cfg, BS, moe_mode=moe_mode, with_expert_load=moe))
    out_f = step(params, cache, tokens[None],
                 np.arange(n, dtype=np.int32)[None], np.array([n], np.int32),
                 pages, np.array([n - 1], np.int32), **extra)

    t = np.zeros((T,), np.int32)
    p = np.full((T,), 10 ** 6, np.int32)      # pad rows: the null block
    t[:n], p[:n] = tokens, np.arange(n)
    bts = np.zeros((2, 4), np.int32)
    bts[0] = pages[0]
    packed = jax.jit(llama.make_packed_prefill_step(
        cfg, BS, moe_mode=moe_mode))
    out_p = packed(params, cache, t, p, np.zeros((T,), np.int32), bts,
                   np.zeros((2,), np.int32), np.array([n, 0], np.int32),
                   np.array([n, 0], np.int32), np.array([n - 1, 0], np.int32),
                   **extra_packed)

    assert len(out_f) == len(out_p) == (3 if moe else 2)
    np.testing.assert_allclose(np.asarray(out_p[0][0]),
                               np.asarray(out_f[0][0]), atol=1e-4)
    assert int(jnp.argmax(out_p[0][0])) == int(jnp.argmax(out_f[0][0]))
    if moe:      # the pack's 11 pad rows are routed too
        assert int(out_f[2][:-1].sum()) == n * cfg.num_experts_per_token \
            * cfg.num_moe_layers
    assert set(out_f[1]) == set(out_p[1]) == set(cache)
    for name, bufs in out_f[1].items():
        for got, want in zip(out_p[1][name], bufs):
            keep = slice(None, -1) if name in ("ssm", "conv") \
                else slice(BS, None)
            np.testing.assert_allclose(
                np.asarray(got[keep], np.float32),
                np.asarray(want[keep], np.float32), atol=1e-4, err_msg=name)
        assert float(jnp.abs(bufs[0].astype(jnp.float32)).max()) > 0, name

    window = jax.jit(llama.make_decode_window(
        cfg, BS, 4, greedy_only=True, moe_mode=moe_mode))
    first = np.asarray(jnp.argmax(out_f[0], axis=-1), np.int32)
    z = np.zeros((1,), np.float32)
    runs = [window(params, c, first, np.array([n], np.int32),
                   np.array([n + 1], np.int32), pages, z,
                   np.zeros((1,), np.int32), z,
                   np.zeros((1, 2), np.uint32), np.zeros((1,), np.int32),
                   **extra)[1] for c in (out_f[1], out_p[1])]
    np.testing.assert_array_equal(np.asarray(runs[0]), np.asarray(runs[1]))


# ---------------------------------------------------------------------------
# Through the engine


def _engine(cfg, quant="none", max_seqs=4, window=4, **kw):
    return EngineCore(EngineConfig(
        model=cfg, num_blocks=64, decode_window=window, kv_quant=quant,
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


PROMPTS = [np.random.default_rng(3).integers(1, 256, size=n).tolist()
           for n in (5, 19, 40)]
ENGINE_KINDS = [k for k in KINDS if k != "block-mask"]   # blocks: no window


@pytest.fixture(scope="module")
def alone():
    """Each of three prompts served alone on the padded plane, one token a
    step: what every other way of serving them must give.  A model a kind,
    made when first asked for."""
    made = {}

    def of(kind):
        if kind not in made:
            cfg, quant = KINDS[kind]
            one = _engine(cfg, quant, max_seqs=1, window=1,
                          packed_prefill=False, use_pallas_decode=False)
            made[kind] = [_generate(one, [p])[0] for p in PROMPTS]
        return made[kind]

    return of


PLANES = {
    "padded": dict(packed_prefill=False, use_pallas_decode=False),
    "packed": dict(packed_prefill=True, use_pallas_decode=True),
    "packed-grouped": dict(packed_prefill=True, moe_mode="grouped"),
}
# What a slot of recurrent state holds: the state-space layers' f32 state
# and their convolutions' tails.
STATE_BYTES = {"state-beside-attention": 2 * (4 * 16 * 8 * 4 + 3 * 96 * 4)}


@pytest.mark.parametrize("kind,plane", _cases(ENGINE_KINDS, PLANES))
def test_three_prompts_together_equal_each_alone(alone, kind, plane):
    """Three prompts packed in one chunk (19 and 40 also split over two and
    three chunks of 16) decode, through windows and single steps, what each
    gives alone in one token a step."""
    cfg, quant = KINDS[kind]
    core = _engine(cfg, quant, **PLANES[plane])
    assert _generate(core, PROMPTS) == alone(kind)
    c = core.counters
    assert c.window_dispatches > 0 and c.single_step_dispatches > 0
    if cfg.has_ssm:
        # 3 prompts x 10 decoded tokens; 64 prompt tokens in 6 chunks.
        assert c.ssm_prefill_tokens == 64 and c.ssm_prefill_segments == 6
        assert c.ssm_decode_row_steps >= 30
        lines = c.block_metrics_lines()
        assert 'dynamo_ssm_state_slots{state="capacity"} 4' in lines
        assert "dynamo_ssm_state_bytes_per_slot " \
            f"{STATE_BYTES.get(kind, core.cache_cfg.state_bytes_per_slot)}" \
            in lines
