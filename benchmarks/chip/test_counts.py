"""Operation and byte counts against hand counts at one small shape."""
import pytest

import harness

olmo = harness.load_module("counts", "olmo")
rwkv6 = harness.load_module("counts", "rwkv6")

# d=4, 2 heads of 2 (MHA), d_ff=8, vocab 10, one layer
OLMO = dict(hidden_size=4, num_attention_heads=2, num_key_value_heads=2, head_dim=2,
            intermediate_size=8, vocab_size=10, num_hidden_layers=1)
RWKV = dict(hidden_size=4, num_attention_heads=2, head_size=2, intermediate_size=8, vocab_size=10,
            num_hidden_layers=1, decay_lora_rank=3)


def test_olmo_layer_weights():
    # q, k, v, o: 4x4 each; gate, up: 4x8; down: 8x4
    assert olmo.layer_weights(OLMO) == 4 * 16 + 3 * 32


def test_olmo_causal_attention():
    # positions 1..3 see 1, 2, 3 keys; QK^T and PV are 2 * dh flops per
    # key and head each: 2 heads * 2 matmuls * 2 * 2 * (1 + 2 + 3)
    assert olmo.attention_flops(OLMO, 3, 1) == 2 * 2 * 2 * 2 * 6
    # three decode positions after a prompt of 5: 6, 7, 8 keys
    assert olmo.attention_flops(OLMO, 3, 6) == 2 * 2 * 2 * 2 * (6 + 7 + 8)


def test_olmo_serve_batch():
    # prompt 3, 2 new tokens: 3 prompt positions through the blocks, logits
    # at the last; one decode step (position 3, 4 keys) with its logits
    per_pos = 2 * 160
    head = 2 * 4 * 10
    attn_prefill = 2 * 2 * 2 * 2 * 6
    attn_decode = 2 * 2 * 2 * 2 * 4
    want = 3 * per_pos + attn_prefill + head + per_pos + head + attn_decode
    assert olmo.serve_flops(OLMO, 1, 3, 2) == want
    assert olmo.serve_flops(OLMO, 5, 3, 2) == 5 * want


def test_olmo_train_step():
    # forward 2 * (blocks + head) per position plus causal attention, times 3
    fwd = 4 * 2 * (160 + 40) + 2 * 2 * 2 * 2 * (1 + 2 + 3 + 4)
    assert olmo.train_flops(OLMO, 1, 4) == 3 * fwd
    assert olmo.train_flops(OLMO, 2, 4) == 6 * fwd


def test_flash_attention_kernel():
    k = olmo.kernels(OLMO, 3, 4)["flash_attention"]
    assert k["calls"] == 1
    assert k["flops"] == 3 * 2 * 2 * 2 * 2 * (1 + 2 + 3 + 4)
    assert k["bytes"] == 2 * (4 * 3 * 4 * 2 * 2)  # q, k, v, o: B*S*heads*dh bf16 each


def test_wkv6():
    # 2 heads of K=V=2: per token and head 5*K*V + 4*K = 28
    assert rwkv6.wkv_flops_per_token(RWKV) == 2 * 28
    k = rwkv6.kernels(RWKV, 1, 3)["wkv6"]
    assert k["flops"] == 3 * 2 * 28
    # r, k, v, y bf16 and log w f32 per channel and position; state f32
    assert k["bytes"] == 3 * 4 * (2 + 2 + 2 + 4 + 2) + 2 * 2 * 2 * 4
    # time mix 5 d^2 and channel mix r d^2, k and v d*ff, decay LoRA 2*d*rank
    assert rwkv6.layer_weights(RWKV) == 6 * 16 + 2 * 32 + 2 * 4 * 3


def test_peaks_by_device_kind():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")
