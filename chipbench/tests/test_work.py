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
