import json
from pathlib import Path

import pytest

from chipbench import work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


# granite-3.0-8b's published widths (ibm-granite/granite-3.0-8b-base), one
# layer: a second shape for the hand arithmetic
GRANITE = work.Shape(d_model=4096, n_heads=32, n_kv_heads=8, head_dim=128,
                     d_ff=12800, vocab=49155, n_layers=1)


def shape(name):
    if name == "granite-3-8b":
        return GRANITE
    return work.Shape.from_config(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_qwen_paged_decode_by_hand():
    s = shape("qwen2-1.5b")         # 12 heads, 2 KV heads, head_dim 128, f32
    flops, nbytes = work.paged_decode(s, [10, 30])
    # 4 * H * hd * L summed: 4 * 12 * 128 * 40
    assert flops == 245760.0
    # live K and V: 2 * 40 rows * 2 heads * 128 * 4 B = 81920;
    # q and out: 2 * 2 rows * 12 * 128 * 4 B = 24576
    assert nbytes == 81920.0 + 24576.0


def test_granite_prefill_attention_by_hand():
    s = shape("granite-3-8b")       # 32 heads, 8 KV heads, head_dim 128
    flops, nbytes = work.prefill_attention(s, [(4, 4)])
    # causal over 4 tokens: 1+2+3+4 = 10 query-key pairs, 4*32*128 each
    assert flops == 10 * 4 * 32 * 128
    # q and out: 2*4*32*128*4 B; K and V: 2*4*8*128*4 B
    assert nbytes == 2 * 4 * 32 * 128 * 4 + 2 * 4 * 8 * 128 * 4
    # a fork's suffix: 2 new tokens after 3 resident attend to 4 and 5 keys
    f2, b2 = work.prefill_attention(s, [(2, 5)])
    assert f2 == 9 * 4 * 32 * 128
    assert b2 == 2 * 2 * 32 * 128 * 4 + 2 * 5 * 8 * 128 * 4


def test_qwen_forward_flops_by_hand():
    s = shape("qwen2-1.5b")
    d, f, v = 1536, 8960, 151936
    layer = d * 1536 + 2 * d * 256 + 1536 * d + 3 * d * f
    assert s.layer_matmul_params == layer
    expect = 2.0 * (4 * layer + d * v) + 4.0 * 4 * 12 * 128 * 7
    assert work.forward_flops(s, 7) == expect


def _old_forward_flops(conf, context):
    """The count before expert models: one dense SwiGLU of
    ``intermediate_size`` a layer."""
    d, h, hkv = (conf[k] for k in ("hidden_size", "num_attention_heads",
                                   "num_key_value_heads"))
    hd, f, n = conf["head_dim"], conf["intermediate_size"], conf["num_hidden_layers"]
    layer = d * (h * hd) + 2 * d * (hkv * hd) + (h * hd) * d + 3 * d * f
    return 2.0 * (n * layer + d * conf["vocab_size"]) + 4.0 * n * h * hd * context


def test_qwen_flops_equal_the_dense_formula_exactly():
    conf = json.loads((CONFIGS / "qwen2-1.5b.json").read_text())
    s = work.Shape.from_config(conf)
    for c in (1, 12, 57, 128):
        assert work.forward_flops(s, c) == _old_forward_flops(conf, c)
    p, rs = 12, [1, 22, 116]
    fwd = sum(_old_forward_flops(conf, c) for c in range(1, p + 1))
    dec = sum(_old_forward_flops(conf, p + j) for r in rs for j in range(1, r))
    train = 3.0 * sum(fwd + sum(_old_forward_flops(conf, p + j)
                                for j in range(1, r + 1)) for r in rs)
    assert work.job_flops(s, p, rs) == fwd + dec + train


def test_expert_forward_flops_by_hand():
    """4 of 16 experts held, top-2: a token's 2 slots land here 2 * 4 / 16
    = 0.5 times under uniform routing, beside the router over all 16."""
    conf = {
        "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "intermediate_size": 6144, "moe_intermediate_size": 32,
        "num_experts": 4, "num_experts_per_tok": 2, "vocab_size": 1000,
        "num_hidden_layers": 2,
        "reduced": {"num_experts": {"published": 16, "here": 4}},
    }
    s = work.Shape.from_config(conf)
    assert (s.d_ff, s.n_experts, s.experts_total, s.top_k) == (32, 4, 16, 2)
    # attention 64*64 + 2*64*32 + 64*64 = 12288; router 64*16 = 1024;
    # experts 0.5 * 3*64*32 = 3072
    assert s.layer_matmul_params == 16384
    # 2 * (2 layers * 16384 + head 64*1000) + attention 4*2*4*16*5
    assert work.forward_flops(s, 5) == 196096.0
    # held experts alone, uncut: every slot lands here
    del conf["reduced"]
    whole = work.Shape.from_config(conf)
    assert whole.layer_matmul_params == 12288 + 64 * 4 + 2 * 3 * 64 * 32


def test_job_flops_counts_prompt_once_and_each_token_once():
    s = shape("granite-3-8b")
    one = work.forward_flops
    p, rs = 3, [2, 1]
    fwd = one(s, 1) + one(s, 2) + one(s, 3)
    dec = one(s, 4)                  # only the 2-token answer decodes once
    train = 3 * ((fwd + one(s, 4) + one(s, 5)) + (fwd + one(s, 4)))
    assert work.job_flops(s, p, rs) == pytest.approx(fwd + dec + train)


def test_peak_table_and_unknown_device():
    p = work.peak("TPU v5 lite")
    assert p.flops_per_s == 197e12 and p.bytes_per_s == 819e9
    assert p.least_s(197e12, 0) == 1.0 and p.least_s(0, 819e9) == 1.0
    with pytest.raises(KeyError):
        work.peak("cpu")
    assert "Google Cloud" in json.loads(work.PEAKS.read_text())["source"]
