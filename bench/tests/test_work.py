"""bench/work.py against numbers worked out by hand: for the Qwen3
configuration, for GLM-4-9B's widths (2 layers, half its vocabulary),
which give an untied head, 16 query heads per KV head and an FFN that is
no power of two, and for the sparse-expert counts at Qwen1.5-MoE's
widths."""
import dataclasses
import json
from fractions import Fraction

import pytest
from conftest import BENCH

import modelcfg
import work


GLM4 = {"family": "dense", "hidden_size": 4096, "ffn_hidden_size": 13696,
        "kv_channels": 128, "num_attention_heads": 32,
        "multi_query_group_num": 2, "num_layers": 2,
        "padded_vocab_size": 75776, "rope_ratio": 500,
        "layernorm_epsilon": 1e-06, "tie_word_embeddings": False}


def dims(name):
    if name == "glm4-9b":
        return modelcfg.dims(GLM4)
    return modelcfg.dims(json.loads(
        (BENCH / "configs" / f"{name}.json").read_text()))


def test_qwen3_counts():
    d = dims("qwen3-1.7b")
    # per layer: q 2048*2048 + k,v 2*2048*1024 + o 2048*2048 + mlp 3*2048*6144
    #          = 4,194,304 * 3 + 37,748,736 = 50,331,648 multiply-adds
    assert work.matmul_flops(d) == 2 * 4 * 50_331_648 == 402_653_184
    # QK and PV: 2 * 2 * 16 heads * 128 per key, 4 layers
    assert work.attention_flops(d, 1) == 32_768
    # tied head: 2 * 2048 * 151,936
    assert work.head_flops(d) == 622_329_856
    # 402,653,184 + 1000 keys * 32,768 + 622,329,856
    assert work.decode_flops(d, 1000) == 1_057_751_040
    # 256 * 402,653,184 + 32,768 * (256 * 257 / 2) + 622,329,856
    assert work.prefill_flops(d, 256) == 104_779_481_088
    # 1000 rows * 4 layers * (K, V) * 8 KV heads * 128 * 2 bytes
    assert work.kv_bytes(d, 1000) == 16_384_000


def test_glm4_counts():
    d = dims("glm4-9b")
    # per layer: q 4096*4096 + k,v 2*4096*256 + o 4096*4096 + mlp 3*4096*13696
    #          = 16,777,216 * 2 + 2,097,152 + 168,296,448 = 203,948,032
    assert work.matmul_flops(d) == 2 * 2 * 203_948_032 == 815_792_128
    # 2 * 2 * 32 heads * 128 per key, 2 layers
    assert work.attention_flops(d, 1) == 32_768
    # separate head over half the vocabulary: 2 * 4096 * 75,776
    assert work.head_flops(d) == 620_756_992
    assert work.decode_flops(d, 3000) == 1_534_853_120
    # 2048 * 815,792,128 + 32,768 * (2048 * 2049 / 2) + 620,756,992
    assert work.prefill_flops(d, 2048) == 1_740_116_066_304
    # 1000 rows * 2 layers * (K, V) * 2 KV heads * 128 * 2 bytes
    assert work.kv_bytes(d, 1000) == 2_048_000


def test_decode_weight_bytes():
    stored = {"embed": 1000, "lm_head": 700, "final_norm": 40,
              "layers/wq": 300, "layers/attn_norm": 8}
    untied = dims("glm4-9b")
    # layers + final norm + head, and 3 gathered rows of the embedding
    assert work.decode_weight_bytes(untied, stored, 3) == \
        300 + 8 + 40 + 700 + 3 * 1000 // untied.vocab
    tied = dims("qwen3-1.7b")
    stored.pop("lm_head")
    # a tied head reads the whole embedding
    assert work.decode_weight_bytes(tied, stored, 3) == 300 + 8 + 40 + 1000


# Qwen1.5-MoE-A2.7B's block at its published widths (MHA, 16 heads of 128,
# routed experts 1,408 wide, top-4, a gated shared expert 5,632 wide), 4
# layers, an eighth of the vocabulary, 15 of its 60 routed experts held
QWEN15_MOE = {"family": "moe", "hidden_size": 2048, "num_attention_heads": 16,
              "num_key_value_heads": 16, "num_hidden_layers": 4,
              "intermediate_size": 5632, "moe_intermediate_size": 1408,
              "vocab_size": 18992, "rope_theta": 1000000,
              "rms_norm_eps": 1e-06, "tie_word_embeddings": False}


def qwen15_moe():
    d = modelcfg.dims(QWEN15_MOE)
    return dataclasses.replace(d, d_ff=1408, family_sizes=modelcfg.family_sizes(
        {"experts": 60, "experts_held": 15, "top_k": 4, "expert_d_ff": 1408,
         "shared_d_ff": 5632, "shared_gate": 1, "norm_topk_prob": False}))


def test_sparse_expert_counts():
    d = qwen15_moe()
    # per layer: attention 4 * 2048 * 2048 = 16,777,216; router 2048 * 60
    # = 122,880; routed 3 * 2048 * 1408 * (4 * 15 / 60 = 1) = 8,650,752;
    # shared 3 * 2048 * 5632 = 34,603,008 and its gate 2048: 60,155,904
    assert work.sparse_expert_matmul_flops(d) == 2 * 4 * 60_155_904 \
        == 481_247_232
    # 2 * 2 * 16 heads * 128 per key, 4 layers: as the dense block's
    assert work.attention_flops(d, 1) == 32_768
    stored = {"layers/moe/w_gate": 15_000, "layers/moe/w_up": 15_000,
              "layers/moe/w_down": 15_000, "layers/moe/router": 60,
              "layers/moe/shared_w_up": 500, "layers/wq": 300,
              "final_norm": 40, "lm_head": 700, "embed": 18_992_000}
    rest = 60 + 500 + 300 + 40 + 700        # read whole every step
    # the 15 held experts, 3,000 bytes each; n tokens reach an expected
    # 15 * (1 - (56/60)**n) of them
    share = work.expected_experts_reached
    assert share(d, 1) == Fraction(1, 15)                   # one expert
    assert share(d, 8) == Fraction(1_087_101_569, 2_562_890_625)
    assert round(15 * float(share(d, 8)), 4) == 6.3626
    assert round(15 * float(share(d, 32)), 2) == 13.35
    for n, experts in [(1, 3_000),
                       (8, 19_087),    # 45,000 * 1,087,101,569 / 15**8
                       (32, 40_052)]:  # 45,000 * (1 - (14/15)**32), down
        assert work.sparse_expert_decode_weight_bytes(d, stored, n) == \
            rest + experts + n * 1000       # and n embedding rows
    # through the dispatch, as an adapter that names them gives them
    adapter = type("Adapter", (), {
        "matmul_flops": staticmethod(work.sparse_expert_matmul_flops),
        "decode_weight_bytes": staticmethod(
            work.sparse_expert_decode_weight_bytes)})
    assert work.decode_flops(d, 100, adapter) == 481_247_232 + 3_276_800 \
        + 2 * 2048 * 18992
    assert work.prefill_flops(d, 2, adapter) == 2 * 481_247_232 + \
        32_768 * 3 + 2 * 2048 * 18992
    assert work.decode_weight_bytes(d, stored, 8, adapter) == \
        rest + 19_087 + 8_000


@pytest.mark.parametrize("workload, matmul, key, head, untied_head", [
    # 4 layers * 2 * 50,331,648 (test_qwen3_counts); 2 * 2 * 16 heads * 128
    # a key, 4 layers; tied head
    ("qwen3-1.7b.chat", 402_653_184, 32_768, 622_329_856, False),
    # 4 layers * 2 * 203,948,032 (test_glm4_counts); 2 * 2 * 32 * 128 * 4;
    # 2 * 4096 * 18,944
    ("glm4-9b.longctx", 1_631_584_256, 65_536, 155_189_248, True),
])
def test_existing_cells_count_as_before(workload, matmul, key, head,
                                        untied_head):
    """The cells' own configurations, through the adapter dispatch: no
    adapter, so the dense counts, to the operation and the byte."""
    import cell as cells
    c = cells.load_cell(workload)
    d, a = c.dims, c.adapter
    assert a is None
    assert work.matmul_flops(d, a) == work.matmul_flops(d) == matmul
    assert work.head_flops(d) == head
    assert work.attention_flops(d, 1) == key
    assert work.decode_flops(d, 1000, a) == matmul + key * 1000 + head
    assert work.prefill_flops(d, 256, a) == \
        256 * matmul + key * (256 * 257 // 2) + head
    stored = {"embed": d.vocab * 10, "lm_head": 700, "final_norm": 40,
              "layers/wq": 300}
    if not untied_head:
        stored.pop("lm_head")
    assert work.decode_weight_bytes(d, stored, 3, a) == \
        work.decode_weight_bytes(d, stored, 3) == 300 + 40 + (
            700 + 30 if untied_head else d.vocab * 10)
