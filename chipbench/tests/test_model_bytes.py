"""`model_bytes` counts the dense block and refuses any other: a share of a
roofline read against bytes the block does not stream would be wrong, and
could read over 100 %."""

import pytest

from chipbench import model_bytes

DENSE = {"hidden_size": 4096, "intermediate_size": 14336,
         "num_attention_heads": 32, "num_key_value_heads": 8,
         "num_hidden_layers": 16, "vocab_size": 32768}


def test_dense_block_is_counted_from_its_published_sizes():
    attn = 4096 * 32 * 128 * 2 + 4096 * 8 * 128 * 2
    layer = attn + 3 * 4096 * 14336 + 2 * 4096
    want = 16 * layer + 4096 + 4096 * 32768
    assert model_bytes.weight_params(DENSE) == want
    assert model_bytes.weight_bytes_per_step(DENSE) == 2 * want
    # A key that is there and says "none" is no routed expert.
    assert model_bytes.weight_params(dict(DENSE, num_local_experts=0)) == want
    assert model_bytes.weight_params(dict(DENSE, num_experts=None)) == want


@pytest.mark.parametrize("key,value", [
    ("num_experts", 128), ("num_local_experts", 8),
    ("n_routed_experts", 256), ("kv_lora_rank", 512)])
def test_a_block_it_does_not_count_raises_with_the_key(key, value):
    with pytest.raises(ValueError, match=key):
        model_bytes.weight_bytes_per_step(dict(DENSE, **{key: value}))


def test_an_unknown_device_has_no_peaks():
    assert model_bytes.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        model_bytes.peaks_for("TPU v9")
