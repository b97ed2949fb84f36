"""bench/flops.py against counts made by hand for both configurations."""
import json
from pathlib import Path

import pytest

from bench import flops as F
from bench import weights as W
from bench.peaks import peaks

CONFIGS = Path(__file__).resolve().parents[2] / "bench" / "configs"


def model(name):
    return W.model_from_config(json.loads((CONFIGS / f"{name}.json")
                                          .read_text()))


# qwen3-0.6b: d 1024, 16 x 128 query heads, 8 x 128 kv heads, gated ff 3072
#   q 1024*2048 + k, v 2 * 1024*1024 + o 2048*1024 + 3 * 1024*3072
# nemotron stage: d 6144, 48 x 128 query heads, 8 x 128 kv heads, ff 24576
#   q 6144*6144 + k, v 2 * 6144*1024 + o 6144*6144 + 2 * 6144*24576
HAND = {
    "qwen3-0.6b": dict(layer=2_097_152 + 2_097_152 + 2_097_152 + 9_437_184,
                       layers=28, d=1024, vocab=151_936, kv_pos=28 * 2 * 8
                       * 128 * 2, heads_dim=16 * 128),
    "nemotron-4-15b-pp4": dict(layer=37_748_736 + 12_582_912 + 37_748_736
                               + 301_989_888, layers=8, d=6144,
                               vocab=64_000, kv_pos=8 * 2 * 8 * 128 * 2,
                               heads_dim=48 * 128),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_layer_and_weight_counts(name):
    m, h = model(name), HAND[name]
    assert F.layer_params(m) == h["layer"]
    assert F.weight_bytes(m) == 2 * (h["layers"] * h["layer"]
                                     + h["d"] * h["vocab"])
    assert F.kv_bytes_per_position(m) == h["kv_pos"]


@pytest.mark.parametrize("name", sorted(HAND))
def test_decode_tick_counts(name):
    m, h = model(name), HAND[name]
    ctxs = [1, 100, 2047]
    fl, by = F.decode_tick(m, ctxs)
    per_token = 2 * h["layers"] * h["layer"] + 2 * h["d"] * h["vocab"]
    attn = sum(4 * h["heads_dim"] * c * h["layers"] for c in ctxs)
    assert fl == 3 * per_token + attn
    assert by == F.weight_bytes(m) + h["kv_pos"] * sum(ctxs)


def test_prefill_chunk_counts():
    m, h = model("qwen3-0.6b"), HAND["qwen3-0.6b"]
    fl, by = F.prefill_chunk(m, start=64, n=64, last=False)
    ctx_sum = sum(range(65, 129))          # positions 64..127 attend 65..128
    assert fl == 64 * 2 * h["layers"] * h["layer"] + \
        4 * h["heads_dim"] * h["layers"] * ctx_sum
    fl_last, _ = F.prefill_chunk(m, start=64, n=64, last=True)
    assert fl_last - fl == 2 * h["d"] * h["vocab"]
    assert by == F.weight_bytes(m) + h["kv_pos"] * 128


def test_roofline_takes_the_longer_bound():
    p = peaks("TPU v5 lite")
    assert F.least_seconds(197e12, 1, p) == pytest.approx(1.0)
    assert F.least_seconds(1, 819e9, p) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
