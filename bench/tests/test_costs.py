"""Operation and byte counts against hand counts from the published
sizes."""

import json

import pytest

from benchlib.costs import Model
from benchlib.spec import BENCH_DIR


def model(name, layers=None):
    conf = json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())
    if layers is not None:
        conf["num_hidden_layers"] = layers
    return Model.from_config(conf)


def test_phi3_counts():
    m = model("phi3-mini-3.8b-4l", 32)
    assert m.kv_bytes_per_token == 196_608
    assert m.step_weight_bytes == pytest.approx(7.45e9, rel=0.005)
    # 32 layers x (4 x 3072^2 + 3 x 3072 x 8192) = 3.62 B matmul weights
    assert m.layers * m.layer_matmul_weights == 3_623_878_656


def test_qwen2_14l_counts():
    m = model("qwen2-7b-4l", 14)
    assert m.kv_bytes_per_token == 14_336
    assert m.step_weight_bytes == pytest.approx(7.62e9, rel=0.005)
    layers = m.layers * m.layer_weights
    assert layers == pytest.approx(3.26e9, rel=0.005)
    total = layers + m.head_weights + m.vocab * m.d      # + embedding
    assert total == pytest.approx(4.35e9, rel=0.005)


def test_stage_counts():
    phi3, qwen2 = model("phi3-mini-3.8b-4l"), model("qwen2-7b-4l")
    assert phi3.kv_bytes_per_token == 196_608 // 8
    assert qwen2.kv_bytes_per_token == 4 * 2 * 4 * 128
    # 4 layers plus the LM head, in bf16
    assert phi3.step_weight_bytes == pytest.approx(
        2 * (4 * 113_252_352 + 3072 * 32064), rel=1e-4)


def test_attention_counts_follow_the_causal_triangle():
    m = model("phi3-mini-3.8b-4l", 32)
    # one decode token against 1000 keys: 4 * H * hd * 1000 per layer
    assert m.attn_ops([1], [1000]) == 4 * 32 * 96 * 1000 * 32
    # a 32-token chunk ending at 100 sees 69+1 ... 100 keys
    assert m.visible_keys(32, 100) == sum(range(69, 101))
    # int8 q and output, K and V of the context, every layer
    assert m.attn_bytes([1], [1000]) == 32 * (2 * 32 * 96 + 2 * 1000 * 32 * 96)
