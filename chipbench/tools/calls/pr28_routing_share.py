"""PR 28: how much of ``mean_logit_gap`` of the Mellum cell is an expert
chosen differently in bfloat16 and in float32? A CPU experiment at small
widths (no device metric comes of it): the program serves seeded prompts in
bfloat16, and the reference scores the served tokens two ways:

- as the cell's comparison does (float32 throughout): the whole gap;
- a float32 reference whose ROUTER alone is fed its input rounded to
  bfloat16 picks its own best tokens, and the plain reference scores THEM:
  the gap that near-ties at the k-th expert cause with not one other
  rounding in play. A lower bound on the flips' share: in the program the
  router's input also carries the layers' accumulated rounding.

    JAX_PLATFORMS=cpu python3 chipbench/tools/calls/pr28_routing_share.py
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.getcwd())

import numpy as np
import jax
import jax.numpy as jnp

from chipbench.archs import mellum as arch
from chipbench.reference import mellum as ref
from paddle_tpu.inference.predictor import ContinuousBatchingEngine

with open("chipbench/configs/mellum2-12b-a2.5b.json") as f:
    C = json.load(f)
C.update(hidden_size=256, moe_intermediate_size=64, num_hidden_layers=4,
         num_attention_heads=4, num_key_value_heads=2, head_dim=64,
         vocab_size=1024, num_experts=32, num_experts_per_tok=8,
         sliding_window=32)


def gaps(params, seq, start, tokens, hidden=ref.hidden):
    with jax.default_matmul_precision("highest"):
        x = hidden(params, jnp.asarray(seq, jnp.int32), C, q_block=64)
        lg = np.asarray(ref.logits(params, x, C))[start - 1:-1]
    return lg, lg.max(-1) - lg[np.arange(tokens.size), tokens]


def rounded_router_hidden(params, tokens, c, q_block):
    """``ref.hidden`` with the experts CHOSEN from the router's input rounded
    to bfloat16, and weighed and applied in float32 as ever."""
    plain = ref.experts

    def experts(m, gate, wg, wu, wd, top_k):
        rounded = m.astype(jnp.bfloat16).astype(ref.F32)
        p = jax.nn.softmax(ref._mm(rounded, gate), -1)
        order = jnp.argsort(-p, axis=-1, stable=True)[:, :top_k]
        chosen = jnp.zeros(p.shape, bool).at[
            jnp.arange(p.shape[0])[:, None], order].set(True)
        w = jnp.where(chosen, jax.nn.softmax(ref._mm(m, gate), -1), 0.0)
        w = w / jnp.sum(w, -1, keepdims=True)

        def one(acc, xs):
            g, u, d, we = xs
            y = ref._mm(jax.nn.silu(ref._mm(m, g.astype(ref.F32)))
                        * ref._mm(m, u.astype(ref.F32)), d.astype(ref.F32))
            return acc + we[:, None] * y, None
        acc, _ = jax.lax.scan(one, jnp.zeros_like(m), (wg, wu, wd, w.T))
        return acc
    ref.experts = experts
    try:
        return ref.hidden(params, tokens, c, q_block)
    finally:
        ref.experts = plain


def main():
    rng = np.random.default_rng(0)
    whole, flips, n = 0.0, 0.0, 0
    for seed in range(4):
        params = arch.weights(jax.random.key(seed), C)
        cfg = arch.program_config(C, 256, remat=False)
        eng = ContinuousBatchingEngine(params, cfg, max_batch=4, page_size=16,
                                       max_len=256, prefill_chunk=32)
        prompts = [rng.integers(3, 1024, (k,)).astype(np.int32)
                   for k in (70, 90, 120, 150)]
        hs = [eng.submit(p, max_new_tokens=64) for p in prompts]
        eng.run()
        for p, h in zip(prompts, hs):
            t = np.asarray(h.tokens)
            seq = np.concatenate([p, t])
            lg, g = gaps(params, seq, p.size, t)
            whole += float(g.sum())
            lg2, _ = gaps(params, seq, p.size, t, hidden=rounded_router_hidden)
            best2 = lg2.argmax(-1)
            flips += float((lg.max(-1) - lg[np.arange(t.size), best2]).sum())
            n += t.size
    print(f"tokens {n}: mean_logit_gap of the bf16 program {whole / n:.5f}; "
          f"of the float32 reference with only its router's input rounded "
          f"to bf16 {flips / n:.5f}")


if __name__ == "__main__":
    main()
