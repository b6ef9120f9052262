"""The seeded weights and validation tokens of the language-model
reference, at a small size: the massive channel of the head's input
leaves float logits as they are and ruins per-token int8 ones, and the
validation rows hold their greedy shares."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness
from chipbench.reference import lm

import chipbench_tiny

CFG = chipbench_tiny.tiny_cell("qwen2-0.5b.search-pq")["config"]


def _params():
    return jax.jit(lambda k: lm.make_params(CFG, k))(
        jax.random.PRNGKey(harness.sub_seeds(2 ** 31 + 3)["weights"]))


def test_massive_channel_moves_only_int8_logits():
    p = _params()
    assert float(jnp.max(jnp.abs(p["embed"][:, 0]))) == 0.0
    plain = jax.tree.map(lambda x: x, p)
    plain["final_norm"]["scale"] = p["final_norm"]["scale"].at[0].set(1.0)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              CFG["vocab_size"])
    for prec in ("f32", "bf16"):
        a = lm.logits(CFG, p, toks, None, prec)
        b = lm.logits(CFG, plain, toks, None, prec)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    a8 = np.asarray(lm.logits(CFG, p, toks, None, "int8"))
    b8 = np.asarray(lm.logits(CFG, plain, toks, None, "int8"))
    ref = np.asarray(lm.logits(CFG, p, toks, None, "f32"))
    agree = lambda x: np.mean(np.argmax(x, -1) == np.argmax(ref, -1))
    assert agree(b8) > 0.9 and agree(a8) < agree(b8) - 0.2


def test_validation_rows_hold_their_greedy_shares():
    p = _params()
    shares = [1.0, 0.0]
    toks = lm.greedy_tokens(CFG, p, jax.random.PRNGKey(5), 32, shares)
    lg = lm.logits(CFG, p, toks, None)[:, :-1]
    hit = np.asarray(jnp.argmax(lg, -1) == toks[:, 1:]).mean(-1)
    assert hit[0] == 1.0 and hit[1] < 0.2
