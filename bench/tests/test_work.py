"""bench/work.py against numbers worked out by hand: for the Qwen3
configuration, and for GLM-4-9B's widths (2 layers, half its vocabulary),
which give an untied head, 16 query heads per KV head and an FFN that is
no power of two."""
import json

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
