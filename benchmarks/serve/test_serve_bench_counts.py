"""FLOP and byte counters against hand counts at tiny shapes, and the peak
table."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]

import pytest  # noqa: E402

import counts  # noqa: E402
import peaks  # noqa: E402

DENSE, LOCAL, MOE = (("attn", "global", "dense"), ("attn", "local", "dense"),
                     ("attn", "global", "moe"))


def spec(layer):
    return dict(zip(("kind", "attn_type", "mlp"), layer))


# D=4, H=2 query heads over KV=1, Dh=2, F=3, V=5, 2 layers
TINY = {"d_model": 4, "n_heads": 2, "n_kv_heads": 1, "head_dim": 2,
        "d_ff": 3, "vocab_size": 5, "n_layers": 2, "qkv_bias": False,
        "tie_embeddings": True, "period": [spec(DENSE)]}
# the same, a windowed layer of 3 keys then a full one
MIXED = dict(TINY, period=[spec(LOCAL), spec(DENSE)], sliding_window=3)
# one expert layer that holds 8 of 64 experts of width 3, 8 to a token
MOE1 = dict(TINY, n_layers=1, period=[spec(MOE)], n_experts=8, moe_top_k=8,
            moe_d_ff=3, published={"n_experts": 64})


def test_layer_params_by_hand():
    # wq 4*2*2=16, wk 4*1*2=8, wv 8, wo 16; MLP 3*4*3=36; norms 2*4=8
    assert counts.layer_params(TINY, DENSE) == 16 + 8 + 8 + 16 + 36 + 8
    # biases: bq 2*2, bk 2, bv 2
    assert counts.layer_params(dict(TINY, qkv_bias=True), DENSE) == 92 + 8
    assert counts.layers(TINY) == (DENSE, DENSE)
    assert counts.layers(MIXED) == (LOCAL, DENSE)


def test_the_pattern_as_groups_or_as_one_period():
    m = dict(TINY, groups=[{"period": [spec(DENSE)], "repeat": 1},
                           {"period": [spec(LOCAL), spec(DENSE)],
                            "repeat": 2}])
    del m["period"], m["n_layers"]
    assert counts.layers(m) == (DENSE, LOCAL, DENSE, LOCAL, DENSE)
    assert counts.windows(dict(m, sliding_window=3)) == (None, 3, None, 3,
                                                         None)
    with pytest.raises(ValueError, match="either groups or a period"):
        counts.layers(dict(m, period=[spec(DENSE)]))
    with pytest.raises(ValueError, match="whole periods"):
        counts.layers(dict(MIXED, n_layers=3))


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


def test_windowed_layer_by_hand():
    # queries at 0..4 in a window of 3 see 1, 2, 3, 3, 3 keys; full: 1..5
    per_key = 4 * 2 * 2
    assert counts.attn_flops(MIXED, 2, 0) == per_key * (3 + 3)    # below
    assert counts.attn_flops(MIXED, 3, 0) == per_key * (6 + 6)    # at
    assert counts.attn_flops(MIXED, 5, 0) == per_key * (12 + 15)  # past
    # one token at position 7: 3 keys in the window, 8 without
    assert counts.attn_flops(MIXED, 1, 7) == per_key * (3 + 8)
    assert counts.flash_flops(MIXED, 5, 3) == per_key * 12
    assert counts.flash_flops(MIXED, 3, 3) == counts.flash_flops(MIXED, 3)
    assert counts.flash_calls(MIXED) == {3: 1, None: 1}
    # all positions are cached; a step reads the windowed layer's last
    # 2 (= 3 - 1) positions at most: slots at 1 and 7 read 1 + 2 there,
    # 1 + 7 in the full layer, and each writes its new token in both
    assert counts.kv_bytes_per_token(MIXED) == 2 * 8
    assert counts.decode_step_bytes(MIXED, [1, 7]) == (
        counts.param_bytes(MIXED) + ((1 + 2 + 2) + (1 + 7 + 2)) * 8)


def test_expert_layer_at_a_share_of_8_of_64_by_hand():
    # attention 48, norms 8, router 4*64 (float32), 8 experts of 3*4*3
    assert counts.experts(MOE1) == (8, 64, 8)
    assert counts.layer_params(MOE1, MOE) == 48 + 8 + 256 + 8 * 36
    # 2 a weight: attention, norms and the router over all 64; of the 8
    # experts a token goes to, 8 * 8 / 64 = 1 is held here
    assert counts.layer_flops(MOE1, MOE) == 2 * (48 + 8 + 256) + 2 * 36
    assert counts.decode_flops(MOE1, 4) == (
        2 * (48 + 8 + 256) + 2 * 36 + 4 * 2 * 2 * 5 + 2 * 4 * 5)
    # final norm, tied embedding, bf16 matrices, float32 router and norms
    pb = 4 * 4 + 5 * 4 * 2 + (48 + 8 * 36) * 2 + (256 + 8) * 4
    assert counts.param_bytes(MOE1) == pb
    # one token: 8 * (1 - (1 - 8/64)) = 1 held expert expected, 7 unread;
    # two tokens: 8 * (1 - (7/8)**2) = 1.875
    assert counts.experts_hit(MOE1, 1) == pytest.approx(1.0)
    assert counts.experts_hit(MOE1, 2) == pytest.approx(1.875)
    assert counts.decode_step_bytes(MOE1, [3]) == pb - 7 * 36 * 2 + 4 * 8
    assert counts.decode_step_bytes(MOE1, [3, 3]) == (
        pb - round(6.125 * 36 * 2) + 8 * 8)
    # no published count: every expert is held here
    whole = dict(MOE1, published={})
    assert counts.experts(whole) == (8, 8, 8)
    assert counts.layer_flops(whole, MOE) == (
        2 * (48 + 8 + 32) + 2 * 36 * 8)


def test_qwen2_counts_are_pinned():
    import json
    m = json.loads((HERE / "configs" / "qwen2-1.5b.json").read_text())["model"]
    assert {counts.layer_params(m, x) for x in counts.layers(m)} == {
        46_797_824}
    assert counts.param_bytes(m) == 3_087_603_712
    assert counts.kv_bytes_per_token(m) == 28_672
    assert counts.prefill_flops(m, 90) == 237_032_251_392
    assert counts.decode_flops(m, 300) == 3_139_207_168
    assert counts.decode_step_bytes(m, [300] * 9) == 3_165_276_160
    assert counts.flash_flops(m, 512) == 806_879_232
    assert counts.flash_bytes(m, 512) == 3_670_016
    assert counts.flash_calls(m) == {None: 28}


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


def _served_model(name):
    import json
    import test_serve_bench_rehearsal as rehearsal
    if name == "mixed":
        return rehearsal.tiny_model(rehearsal.MIXED)
    if name == "moe":
        return dict(rehearsal.tiny_model(), family="moe", n_experts=8,
                    moe_top_k=2, moe_d_ff=32,
                    period=[{"kind": "attn", "attn_type": "global",
                             "mlp": "moe"}, spec(DENSE)], n_layers=4)
    return json.loads((HERE / "configs" / f"{name}.json").read_text())["model"]


@pytest.mark.parametrize("name", ["qwen2-1.5b", "mixed", "moe"])
def test_param_bytes_match_the_served_tree(name):
    import jax
    import run
    from repro.models import transformer as T
    m = _served_model(name)
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
