"""The qwen2 family's work counts against hand counts for both Qwen
configurations, and the shared table of peaks and roofline.  No chip."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import arch, work  # noqa: E402


# the GQA case: Qwen2.5-1.5B's published sizes on the benchmark's file
GQA = dict(hidden_size=1536, intermediate_size=8960, num_attention_heads=12,
           num_key_value_heads=2, num_hidden_layers=28)


def sizes(name):
    c = json.loads((ROOT / "bench" / "configs" / "qwen1.5-0.5b.json")
                   .read_text())
    if name == "qwen2.5-1.5b":
        c.update(GQA)
    return arch.family(c["model_type"]).work(c)


# Hand counts.  qwen1.5-0.5b: per layer Q, K, V, O are 1024 x 1024 each
# (16 heads of 64, MHA) = 4,194,304, the Q/K/V biases 3 x 1024, the MLP
# 3 x 1024 x 2816 = 8,650,752, two norms 2 x 1024: 12,850,176; times 24,
# plus the final norm.  qwen2.5-1.5b: Q and O 1536 x 1536, K and V
# 1536 x 256 (2 heads of 128): 5,505,024, biases 1536 + 2 x 256, MLP
# 3 x 1536 x 8960 = 41,287,680, norms 3,072: 46,797,824; times 28, plus
# the final norm.  (Qwen publishes 1.31B non-embedding parameters.)
PARAMS = {"qwen1.5-0.5b": 24 * 12_850_176 + 1024,
          "qwen2.5-1.5b": 28 * 46_797_824 + 1536}
# bytes of K and V per token over all layers, bf16
KV_PER_TOKEN = {"qwen1.5-0.5b": 24 * 2 * 16 * 64 * 2,     # 98,304
                "qwen2.5-1.5b": 28 * 2 * 2 * 128 * 2}     # 28,672


@pytest.mark.parametrize("name", sorted(PARAMS))
def test_nonembed_params_match_hand_counts(name):
    assert sizes(name).nonembed_params == PARAMS[name]


def test_published_kv_bytes_per_token():
    assert KV_PER_TOKEN == {"qwen1.5-0.5b": 98_304, "qwen2.5-1.5b": 28_672}
    assert {n: sizes(n).kv_bytes_per_token for n in KV_PER_TOKEN} == \
        KV_PER_TOKEN


@pytest.mark.parametrize("name", sorted(PARAMS))
def test_decode_kv_bytes_at_a_position(name):
    s = sizes(name)
    # position 999 reads 1000 cached tokens, plus its query and output
    qo = s.layers * 2 * s.heads * s.head_dim * 2
    assert s.decode_kv_bytes(999) == 1000 * KV_PER_TOKEN[name] + qo
    # one decode row's attention: QK^T and PV over 1000 keys, 4 FLOPs per
    # head dimension per key per layer: 4 x 24 x 16 x 64 and 4 x 28 x 12 x 128
    per_key = {"qwen1.5-0.5b": 98_304, "qwen2.5-1.5b": 172_032}[name]
    assert s.decode_attn_flops(999) == 1000 * per_key


@pytest.mark.parametrize("name", sorted(PARAMS))
def test_token_and_prompt_flops(name):
    s = sizes(name)
    attn_per_key = 4 * s.layers * s.heads * s.head_dim
    head = 2 * s.d_model * 151_936
    assert s.vocab == 151_936
    assert s.token_flops(0, False) == 2 * PARAMS[name] + attn_per_key
    assert s.token_flops(9, True) == (2 * PARAMS[name]
                                      + 10 * attn_per_key + head)
    # a 3-token prompt: positions 0, 1, 2 attend to 1 + 2 + 3 keys, and
    # only the last samples
    assert s.prompt_flops(3) == (3 * 2 * PARAMS[name]
                                 + 6 * attn_per_key + head)
    assert s.prompt_attn_flops(3) == 6 * attn_per_key
    assert s.prompt_attn_flops(4096) == attn_per_key * 4096 * 4097 // 2
    # q, k, v and the output of each real token, bf16, every layer
    per_tok = (2 * s.heads + 2 * s.kv_heads) * s.head_dim * 2
    assert s.prompt_attn_bytes(3) == s.layers * 3 * per_tok


def test_prompt_flops_sum_token_flops():
    s = sizes("qwen2.5-1.5b")
    n = 37
    by_token = sum(s.token_flops(p, p == n - 1) for p in range(n))
    assert s.prompt_flops(n) == by_token


def test_decode_position_follows_the_engine():
    # output token 1 is computed from output token 0, written right after
    # the prompt's last position
    assert work.decode_position(100, 1) == 100
    assert work.decode_position(100, 5) == 104


def test_peaks_by_device_kind():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_roofline_share_names_its_bound():
    p = work.peaks("TPU v5 lite")
    share, bound = work.roofline_share(197e12, 1.0, 2.0, p)
    assert bound == "compute" and share == pytest.approx(50.0)
    share, bound = work.roofline_share(1.0, 819e9, 4.0, p)
    assert bound == "memory" and share == pytest.approx(25.0)
