"""Operations and least bytes against counts by hand at a small shape."""
from chipbench import flops

CFG = dict(hidden_size=8, intermediate_size=16, num_attention_heads=2,
           num_key_value_heads=1, head_dim=4, vocab_size=10,
           num_hidden_layers=3)


def test_matmul_params_by_hand():
    # q 8x8, k 8x4, v 8x4, o 8x8, gate+up 8x32, down 16x8 -> 128+64+256+128
    per_layer = 64 + 32 + 32 + 64 + 256 + 128
    assert flops.matmul_params(CFG) == 3 * per_layer + 8 * 10


def test_token_and_prefill_flops_by_hand():
    mm = 2 * flops.matmul_params(CFG)
    # attention: 3 layers x 2 heads x head_dim 4 x 4 per context token
    assert flops.token_flops(CFG, 5) == mm + 3 * 2 * 4 * 4 * 5
    # prompt of 3: contexts 1, 2, 3
    assert flops.prefill_flops(CFG, 3) == 3 * mm + 3 * 2 * 4 * 4 * 6
    assert flops.decode_step_flops(CFG, [5, 3]) == (
        flops.token_flops(CFG, 5) + flops.token_flops(CFG, 3))


def test_decode_bytes_by_hand():
    per_layer = 64 + 32 + 32 + 64 + 256 + 128 + 16
    weights = 2 * (3 * per_layer + 8 + 80)
    assert flops.weight_bytes(CFG) == weights
    kv = 3 * 2 * 1 * 4 * 2            # layers x (k, v) x kv heads x hd x 2 B
    assert flops.kv_bytes_per_token(CFG) == kv
    assert flops.decode_step_bytes(CFG, [5, 3]) == weights + 2 * 8 * 2 + 8 * kv
