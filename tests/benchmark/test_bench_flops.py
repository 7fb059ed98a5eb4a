"""Operation and byte counts of the dense GQA kind against hand counts."""
import types

from benchmarks.chip import spec

# L=2, d=8, H=2 heads of hd=4, KV=1, ff=16, V=10
M = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
     "num_key_value_heads": 1, "intermediate_size": 16, "vocab_size": 10}
C = {"kind": "dense_gqa", "model": M}
K = spec.arch_kind(types.SimpleNamespace(config=C, base=spec.HERE))


def test_layer_weights_by_hand():
    # q 8x8, k 8x4, v 8x4, o 8x8, gate/up 8x16 each, down 16x8
    assert K.layer_weights(C) == 64 + 32 + 32 + 64 + 3 * 128


def test_token_flops_by_hand():
    per_layer = 2 * 576 + 4 * 2 * 4 * (5 + 1)     # position 5: 6 keys
    assert K.token_flops(C, 5, logits=False) == 2 * per_layer
    assert K.token_flops(C, 5, logits=True) == 2 * per_layer + 2 * 80


def test_prefill_is_the_sum_of_its_tokens_with_one_logits_row():
    start, end = 3, 9
    by_token = sum(K.token_flops(C, p, logits=False)
                   for p in range(start, end))
    assert K.prefill_flops(C, start, end) == by_token + 2 * 8 * 10
    assert K.prefill_flops(C, 4, 4) == 0


def test_decode_step_bytes_by_hand():
    weights = 2 * 576 + 10 * 8                     # layers + tied head
    kv_row = 2 * 2 * 1 * 4 * 2                     # L x (k,v) x KV x hd x 2B
    want = weights * 2 + kv_row * (4 + 8) + kv_row * 2
    assert K.decode_step_bytes(C, iter([3, 7])) == want
