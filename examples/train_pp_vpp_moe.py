"""Pipeline-parallel training with the hand-written VPP (interleaved
1F1B) schedule, plus the pp × MoE composition. No schedule has run on
a chip yet (ROADMAP D5).

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     python examples/train_pp_vpp_moe.py
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.models import llama, moe, train, train_pp

# ---- dense Llama under VPP (dp × pp × tp) ------------------------------
# dp=1 here: with dp AND tp sharded XLA:CPU aborts the VPP step
# (tests/test_pp_moe.py::test_interleave_1f1b_on_ep2_tp2); on chips the
# recipe is dp × pp × tp
mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(1, 2, 4),
            ("dp", "pp", "tp"))
cfg = llama.LlamaConfig.tiny(num_layers=4, hidden_size=64, num_heads=4,
                             num_kv_heads=4, intermediate_size=128,
                             vocab_size=256)
chunks = 2
step = train_pp.make_train_step_pp(cfg, mesh, num_microbatches=4,
                                   schedule="interleave_1f1b",
                                   num_chunks=chunks)
state = jax.jit(lambda k: train.init_train_state(k, cfg),
                out_shardings=train_pp.state_shardings_pp(mesh, cfg))(
    jax.random.key(0))
# interleaved schedules hold each device's chunks contiguously; the
# helper permutes into round-robin storage order (checkpoints store
# canonical order — from_interleave_storage inverts at save time)
state = train_pp.to_interleave_storage(state, cfg, mesh, chunks)
tokens = jax.device_put(
    jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, 64)), jnp.int32),
    NamedSharding(mesh, P("dp")))
for i in range(3):
    state, metrics = step(state, tokens)
    print(f"[vpp ] step {i}: loss={float(metrics['loss']):.4f}")

# ---- MoE under the pipeline (dp × pp × ep × tp) ------------------------
# the load-balance aux loss rides the pipeline carry; experts shard
# over the ep axis (GSPMD lowers the dispatch einsums to all-to-alls)
mesh4 = Mesh(np.asarray(jax.devices()[:8]).reshape(1, 2, 2, 2),
             ("dp", "pp", "ep", "tp"))
cfg_moe = llama.LlamaConfig.tiny(
    num_layers=4, hidden_size=32, num_heads=2, num_kv_heads=2,
    intermediate_size=64, vocab_size=64,
    moe=moe.MoEConfig(num_experts=4, top_k=2, capacity_factor=2.0))
step_m = train_pp.make_train_step_pp(cfg_moe, mesh4, num_microbatches=2,
                                     schedule="1f1b")
st_m = jax.jit(lambda k: train.init_train_state(k, cfg_moe),
               out_shardings=train_pp.state_shardings_pp(mesh4, cfg_moe))(
    jax.random.key(1))
toks_m = jax.device_put(
    jnp.asarray(np.random.default_rng(1).integers(
        0, cfg_moe.vocab_size, (4, 32)), jnp.int32),
    NamedSharding(mesh4, P("dp")))
for i in range(3):
    st_m, metrics = step_m(st_m, toks_m)
    print(f"[moe ] step {i}: loss={float(metrics['loss']):.4f}")
print("pp VPP + pp MoE example OK")
