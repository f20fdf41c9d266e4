"""The grouped expert kernel's fetch ring (ops/pallas/moe_grouped.py, PR 54)
against the kernel that stood before it (`grouped_ffn_before_ring.py`: the
weight blocks through the grid's own two buffers), in interpret mode, bit for
bit: the tiles, the matmuls and their order are the same, only the way a
block reaches VMEM changed, so any difference is a fault of the ring.  The
routings walk its edges: one live tile, fewer live tiles than slots, an expert
spilling into a second and a third tile, every tile dead, 1 / 2 / 8 F blocks,
a share's extra group behind the held experts.  What the chip adds to this
(timings, real DMAs) is `tools/expert_kernel_chip_check.py`."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import grouped_ffn_before_ring as before                      # noqa: E402
from dynamo_tpu.ops import moe as moe_ops                     # noqa: E402
from dynamo_tpu.ops.pallas import moe_grouped as ring         # noqa: E402

E, H, F, BM = 6, 128, 1024, 8

# name: (tile -> expert, live tiles)
ROUTINGS = {
    "one_live_tile": ([4, 4, 4, 4], 1),
    "two_live_under_three_slots": ([1, 5, 5, 5, 5], 2),
    "an_expert_in_three_tiles": ([0, 2, 2, 2, 3, 5, 5, 5], 6),
    "every_tile_dead": ([0, 0, 0], 0),
    "every_tile_live_each_an_expert": ([0, 1, 2, 3, 4, 5], 6),
    "more_experts_than_slots_then_dead": ([0, 1, 1, 3, 4, 4, 5, 5, 5, 5], 7),
    "the_first_tiles_expert_again_later": ([2, 2, 0, 2, 2], 4),
}


def _weights(seed: int, dtype=jnp.float32):
    k = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(k[0], (E, H, F), dtype) * H ** -0.5,
            jax.random.normal(k[1], (E, H, F), dtype) * H ** -0.5,
            jax.random.normal(k[2], (E, F, H), dtype) * F ** -0.5)


def _run(module, form: str, tile_expert, live: int, block_f: int, seed: int,
         traced_anew: bool = False):
    """The live tiles' rows of `module`'s kernel; `traced_anew` goes past the
    jit's cache (for a ring whose depth a test has since patched)."""
    te = jnp.asarray(tile_expert, jnp.int32)
    x = jax.random.normal(jax.random.key(seed + 7), (len(tile_expert) * BM, H),
                          jnp.float32)
    wg, wu, wd = _weights(seed)
    kw = dict(live_tiles=jnp.asarray([live], jnp.int32), block_rows=BM,
              block_f=block_f, interpret=True)
    gated, two = module.grouped_expert_ffn, module.grouped_expert_ffn_relu2
    if traced_anew:
        gated, two = gated.__wrapped__, two.__wrapped__
    if form == "relu2":
        out = two(x, te, wu, wd, **kw)
    elif form == "int8":
        q = ring.quantize_moe_params(
            {"router": None, "w_gate": wg, "w_up": wu, "w_down": wd})
        out = gated(
            x, te, q["w_gate"], q["w_up"], q["w_down"],
            w_gate_scale=q["w_gate_scale"], w_up_scale=q["w_up_scale"],
            w_down_scale=q["w_down_scale"], **kw)
    else:
        out = gated(x, te, wg, wu, wd, **kw)
    return np.asarray(out)[:live * BM]       # a dead tile's rows are undefined


@pytest.mark.parametrize("f_blocks", [1, 2, 8])
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
@pytest.mark.parametrize("form", ["gated", "relu2", "int8"])
def test_the_ring_equals_the_grid_pipeline_bit_for_bit(form, routing,
                                                       f_blocks):
    tile_expert, live = ROUTINGS[routing]
    seed = sorted(ROUTINGS).index(routing)
    want = _run(before, form, tile_expert, live, F // f_blocks, seed)
    got = _run(ring, form, tile_expert, live, F // f_blocks, seed)
    assert got.shape == want.shape == (live * BM, H)
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    if live:
        assert float(np.abs(want).max()) > 0.05


@pytest.mark.parametrize("depth", [2, 4, 5])
def test_every_depth_of_the_ring_gives_the_same_bits(depth, monkeypatch):
    """The depth is the ring's own choice (`ring_depth`, from the block's
    bytes): whatever it picks, the blocks land in the grid's order."""
    tile_expert, live = ROUTINGS["more_experts_than_slots_then_dead"]
    want = _run(before, "gated", tile_expert, live, F // 2, 3)
    monkeypatch.setattr(ring, "ring_depth", lambda block_bytes: depth)
    got = _run(ring, "gated", tile_expert, live, F // 2, 3, traced_anew=True)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("form", ["gated", "relu2"])
def test_a_share_behind_the_ring_equals_the_one_behind_the_pipeline(
        form, monkeypatch):
    """`moe_grouped` told a share (4 of 16 held): the other chips' rows are
    one more group behind the held ones, whose tiles are dead.  The layer's
    output through the ring is the one through the pipeline, bit for bit."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import TINY_PATTERN

    cfg = TINY_PATTERN.replace(dtype=jnp.float32)
    moe = llama.init_params(cfg, jax.random.key(0))["layers"][1]["moe"]
    routed = {k: v for k, v in moe.items()
              if k not in ("shared", "latent_in", "latent_out")}
    if form == "gated":
        routed["w_gate"] = routed["w_up"][:, :, ::-1]
    x = jax.random.normal(jax.random.key(1), (1, 37, 64), jnp.float32)
    u = x @ moe["latent_in"]
    got, load = moe_ops.moe_grouped(cfg, routed, x, x_expert=u,
                                    interpret=True)
    for name in ("grouped_expert_ffn", "grouped_expert_ffn_relu2"):
        monkeypatch.setattr(ring, name, getattr(before, name))
    want, load_w = moe_ops.moe_grouped(cfg, routed, x, x_expert=u,
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(load), np.asarray(load_w))
    held = int(load[4:8].sum())
    assert 0 < held < 37 * 6 and float(jnp.abs(want).max()) > 0.05


def test_the_ring_counts_its_slots_and_keeps_the_f_block():
    """The F block is the one the pipeline took (the bytes a call moves stay
    as they were); the ring's slots are what the kernel asks VMEM for."""
    assert ring.auto_block_f(2048, 768, 2) == 768              # SDAR
    assert ring.auto_block_f(2048, 1536, 2) == 768             # GLM-4.7-Flash
    assert ring.auto_block_f(4096, 4096, 2) == 512             # Command A+
    assert ring.auto_block_f(1024, 2688, 2, matrices=2) == 2688   # Nemotron
    for block_bytes in (3 * 2048 * 768 * 2, 2 * 1024 * 2688 * 2,
                        3 * 4096 * 512 * 2):
        depth = ring.ring_depth(block_bytes)
        assert depth >= 3 and depth * block_bytes <= 64 << 20
