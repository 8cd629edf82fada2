"""FLOP and byte counters against hand counts at tiny shapes, and the peak
table."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]

import pytest  # noqa: E402

import counts  # noqa: E402
import peaks  # noqa: E402

# D=4, H=2 query heads over KV=1, Dh=2, F=3, V=5, 2 layers
TINY = {"d_model": 4, "n_heads": 2, "n_kv_heads": 1, "head_dim": 2,
        "d_ff": 3, "vocab_size": 5, "n_layers": 2, "qkv_bias": False,
        "tie_embeddings": True}


def test_layer_params_by_hand():
    # wq 4*2*2=16, wk 4*1*2=8, wv 8, wo 16; MLP 3*4*3=36; norms 2*4=8
    assert counts.layer_params(TINY) == 16 + 8 + 8 + 16 + 36 + 8
    # biases: bq 2*2, bk 2, bv 2
    assert counts.layer_params(dict(TINY, qkv_bias=True)) == 92 + 8


def test_attention_and_step_flops_by_hand():
    # L=3 causal: query i sees i+1 keys -> 1+2+3 = 6 scores per head;
    # QK^T and PV are 2 FLOPs per MAC over Dh=2: 2*2*Dh*6 per head
    assert counts.attn_flops(TINY, 3, 0) == 2 * (4 * 2 * 2 * 6)
    # one token after 4 cached ones sees 5 keys
    assert counts.attn_flops(TINY, 1, 4) == 2 * (4 * 2 * 2 * 5)
    assert counts.flash_flops(TINY, 3) == 4 * 2 * 2 * 6
    P = 92 * 2
    assert counts.prefill_flops(TINY, 3) == 2 * P * 3 + 2 * 96 + 2 * 4 * 5
    assert counts.decode_flops(TINY, 4) == 2 * P + 2 * 80 + 2 * 4 * 5


def test_bytes_by_hand():
    # q and o: 2*L*H*Dh, k and v: 2*L*KV*Dh, bf16
    assert counts.flash_bytes(TINY, 3) == (12 + 6) * 2 * 2
    assert counts.kv_bytes_per_token(TINY) == 2 * 2 * 1 * 2 * 2
    mats = 2 * (92 - 8) + 5 * 4                  # layers + tied embedding
    norms = 2 * 2 * 4 + 4
    assert counts.param_bytes(TINY) == mats * 2 + norms * 4
    # two slots with 3 and 5 cached tokens: weights + 8 read + 2 written
    assert counts.decode_step_bytes(TINY, [3, 5]) == \
        counts.param_bytes(TINY) + 10 * 16


def test_untied_head_reads_only_the_looked_up_embedding_rows():
    m = dict(TINY, tie_embeddings=False)
    assert counts.param_bytes(m) == counts.param_bytes(TINY) + 5 * 4 * 2
    assert counts.decode_step_bytes(m, [3]) == (
        counts.param_bytes(m) - (5 - 1) * 4 * 2 + 4 * 16)


@pytest.mark.parametrize("name", ["qwen2-1.5b"])
def test_param_bytes_match_the_served_tree(name):
    import json
    import jax
    import run
    from repro.models import transformer as T
    m = json.loads((HERE / "configs" / f"{name}.json").read_text())["model"]
    tree = T.abstract_params(run.model_config(m))
    served = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    assert counts.param_bytes(m) == served


def test_peak_table_refuses_an_unknown_device():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("TPU v99 imaginary")
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == pytest.approx(197e12)
    assert v5e["hbm_bytes_per_s"] == pytest.approx(819e9)
    assert "Google Cloud" in v5e["source"]
