"""Operations and bytes the measured work needs, computed from shapes.

Counts are of what the algorithm needs, not of what a compiled program
happens to do: a pruned channel is still computed (the search masks, it
does not slice), so validation counts the dense forward; causal
attention counts the lower triangle with its diagonal.
"""
from __future__ import annotations

from chipbench.reference import lm

F32 = 4


def lm_matmul_params(cfg: dict) -> int:
    a = lm.arch(cfg)
    d, ff, H, KV, hd = a["d"], a["ff"], a["heads"], a["kv"], a["hd"]
    per_layer = d * (H + 2 * KV) * hd + H * hd * d + 3 * d * ff
    return a["layers"] * per_layer + d * a["vocab"]


def lm_forward_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward FLOPs of ``batch`` sequences of ``seq`` tokens."""
    a = lm.arch(cfg)
    tokens = batch * seq
    attn = 2 * 2 * batch * a["heads"] * a["hd"] * seq * (seq + 1) / 2
    return 2.0 * tokens * lm_matmul_params(cfg) + a["layers"] * attn


def mlp3_dims(state_dim: int, action_dim: int, hidden) -> dict:
    h1, h2 = hidden
    return {"actor": (state_dim, h1, h2, action_dim),
            "critic": (state_dim + action_dim, h1, h2, 1)}


def mlp3_flops(dims, batch: int) -> float:
    d0, d1, d2, d3 = dims
    return 2.0 * batch * (d0 * d1 + d1 * d2 + d2 * d3)


# An update step runs the fused trunk five times: the target actor and
# target critic for the TD target, the critic on the sampled actions,
# and the actor and then the critic on the actor's actions.
MLP3_CALLS_PER_UPDATE = {"actor": 2, "critic": 3}


def ddpg_update_flops(state_dim, action_dim, hidden, batch) -> float:
    """The five trunk forwards, plus backward passes: the critic's
    (weights and inputs), through the critic to the actions, and the
    actor's (weights and inputs): about 4 actor and 6 critic forwards."""
    dims = mlp3_dims(state_dim, action_dim, hidden)
    return (4 * mlp3_flops(dims["actor"], batch)
            + 6 * mlp3_flops(dims["critic"], batch))


def fake_quant_tensors_lm(cfg: dict, batch: int, seq: int) -> list:
    """(rows, channels, shared) of every distinct tensor one validation
    of a batch of policies fake-quantizes: each weight (shared by the
    policies), and each distinct matmul input of a layer (the q/k/v
    input, the attention output, the up/gate input, the down input),
    which differs per policy."""
    a = lm.arch(cfg)
    d, ff, H, KV, hd, V = (a["d"], a["ff"], a["heads"], a["kv"], a["hd"],
                           a["vocab"])
    t = batch * seq
    weights = [(d, H * hd), (d, KV * hd), (d, KV * hd), (H * hd, d),
               (d, ff), (d, ff), (ff, d)]
    acts = [(t, d), (t, H * hd), (t, d), (t, ff)]
    layer = [(r, c, True) for r, c in weights] + \
        [(r, c, False) for r, c in acts]
    return [(V, d, True)] + layer * a["layers"] + [(d, V, True)]


def fake_quant_bytes(tensors: list, policies: int) -> float:
    """Least HBM bytes to fake-quantize ``tensors`` for ``policies``
    policies: one float32 read of each input (once where the policies
    share it) and one write of each policy's output."""
    return sum(F32 * r * c * ((1 if shared else policies) + policies)
               for r, c, shared in tensors)
