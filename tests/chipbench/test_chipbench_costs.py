"""The benchmark's counts of operations and bytes, against sums worked
out by hand at small shapes."""
from __future__ import annotations

from chipbench import costs

LM = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
      "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
      "vocab_size": 10, "rope_theta": 1e4, "rms_norm_eps": 1e-6}


def test_lm_forward_flops():
    # per layer: qkv 8*(2+2)*4=128, out 8*8=64, mlp 3*8*16=384 -> 576;
    # two layers + head 8*10 = 1232 matmul weights
    assert costs.lm_matmul_params(LM) == 1232
    # 2 flops per weight per token (3 x 5 tokens) plus, per layer,
    # 2*2*B*H*hd*S(S+1)/2 = 4*3*2*4*15 = 1440
    assert costs.lm_forward_flops(LM, 3, 5) == 2 * 15 * 1232 + 2 * 1440


def test_fake_quant_tensors_lm():
    t = costs.fake_quant_tensors_lm(LM, 3, 5)
    assert t[0] == (10, 8, True) and t[-1] == (8, 10, True)
    # 7 weights and 4 distinct matmul inputs per layer
    assert len(t) == 2 + 2 * 11
    assert t[8:12] == [(15, 8, False), (15, 8, False), (15, 8, False),
                       (15, 16, False)]


def test_fake_quant_bytes_read_shared_inputs_once():
    # shared: 1 read + 4 writes; per policy: 4 reads + 4 writes
    assert costs.fake_quant_bytes([(3, 5, True)], 4) == 4 * 15 * 5
    assert costs.fake_quant_bytes([(3, 5, False)], 4) == 4 * 15 * 8


def test_mlp3_counts():
    dims = (3, 4, 5, 1)
    assert costs.mlp3_flops(dims, 2) == 2 * 2 * (12 + 20 + 5)
    a = costs.mlp3_flops((3, 4, 5, 2), 2)
    c = costs.mlp3_flops((5, 4, 5, 1), 2)
    assert costs.ddpg_update_flops(3, 2, (4, 5), 2) == 4 * a + 6 * c
