"""bench/work/ against counts made by hand for one small layer."""
from bench.work import dense, rwkv6

DENSE = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
         "head_dim": 2, "intermediate_size": 3, "vocab_size": 5,
         "num_hidden_layers": 1, "use_qkv_bias": True}
RWKV = {"hidden_size": 4, "head_size": 2, "attention_hidden_size": 4,
        "time_decay_extra_dim": 1, "intermediate_size": 3, "vocab_size": 5,
        "num_hidden_layers": 1}


def test_dense_one_layer_by_hand():
    # wq 4x2x2=16, wk 4x1x2=8, wv 8, wo 2x2x4=16, gate/up/down 3x4x3=36
    matrices = 16 + 8 + 8 + 16 + 36
    head = 4 * 5
    # one token at position 3: it attends to 3 cached rows and itself, with
    # 2 heads of size 2, scores and values each 2 FLOPs a product
    attn = 2 * 2 * 2 * 2 * (3 + 1)
    assert dense.step_flops(DENSE, 1, 3) == 2 * matrices + 2 * head + attn
    biases = (2 + 1 + 1) * 2
    weights = 2 * (matrices + biases + head) + 4 * (4 + 4 + 4)  # 3 norms, f32
    embed_row = 4 * 2
    kv_row = 2 * 1 * 2 * 2  # k and v, 1 head of 2, bf16
    assert dense.step_bytes(DENSE, 1, 3) == (weights + embed_row
                                             + (3 + 1) * kv_row)
    # two slots at positions 3 and 5: weights once, rows add up
    assert dense.step_bytes(DENSE, 2, 8) == (weights + 2 * embed_row
                                             + (8 + 2) * kv_row)


def test_rwkv6_one_layer_by_hand():
    # r, k, v, g, o 4x4 each; decay lora 4x1 and 1x4; c_k 4x3, c_v 3x4,
    # c_r 4x4
    matrices = 5 * 16 + 4 + 4 + 12 + 12 + 16
    wkv = 7 * 2 * 2 * 2  # 2 heads of size 2: 7 hs^2 each
    head = 4 * 5
    assert rwkv6.step_flops(RWKV, 1, 0) == 2 * matrices + wkv + 2 * head
    assert rwkv6.step_flops(RWKV, 3, 99) == 3 * (2 * matrices + wkv + 2 * head)
    vectors_bf16 = 5 * 4 + 2 * 4  # mu, mu_c
    vectors_f32 = 4 + 2 * 2 + 2 * 2 + 2 * 4 + 4  # decay, u, head norm, 3 norms
    weights = 2 * (matrices + vectors_bf16 + head) + 4 * vectors_f32
    state = 2 * 2 * 2 * 4 + 2 * 4 * 2  # WKV f32, two shift vectors bf16
    assert rwkv6.step_bytes(RWKV, 1, 0) == weights + 4 * 2 + 2 * state
    assert rwkv6.step_bytes(RWKV, 2, 7) == weights + 2 * (4 * 2 + 2 * state)
