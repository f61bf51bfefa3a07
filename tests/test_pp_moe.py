"""Pipeline parallelism × MoE composition (round 5).

The reference trains MoE models under its hybrid pipeline engine
(reference: python/paddle/distributed/fleet/meta_parallel/
pipeline_parallel.py + incubate MoE layers; pp×ep hybrid_configs). The
TPU formulation carries the MoE load-balance aux loss through the
pipeline ring as one extra sequence position of the static carry
(train_pp.make_train_step_pp), so it reaches the final loss AND
backprops into every stage's router under every schedule.

Pins:
- loss agreement across gpipe / 1F1B / zero-bubble / hand-written VPP
  (same per-microbatch aux accounting);
- router (gate) gradients are NONZERO — the aux path is live;
- training steps reduce the loss;
- the aux really contributes: zeroing the aux row changes the loss.
"""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from paddle_tpu.models import llama, moe, train, train_pp


def _cfg():
    return llama.LlamaConfig.tiny(
        num_layers=4, hidden_size=32, num_heads=2, num_kv_heads=2,
        intermediate_size=64, vocab_size=64,
        moe=moe.MoEConfig(num_experts=4, top_k=2, capacity_factor=2.0))


def _mesh(tp=2):
    devs = jax.devices()[:4 * tp]
    return Mesh(np.asarray(devs).reshape(1, 2, 2, tp),
                ("dp", "pp", "ep", "tp"))


def _mesh_for(schedule):
    """interleave_1f1b runs at tp=1 here: with TWO sharded axes beside
    pp XLA:CPU aborts the step (test_interleave_1f1b_on_ep2_tp2 below
    says why and skips by name)."""
    return _mesh(tp=1 if schedule == "interleave_1f1b" else 2)


def _tokens(cfg, b=4, s=32):
    return jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (b, s)), jnp.int32)


def _state(cfg, mesh, permuted_chunks=None):
    st = jax.jit(lambda k: train.init_train_state(k, cfg),
                 out_shardings=train_pp.state_shardings_pp(mesh, cfg))(
        jax.random.key(0))
    if permuted_chunks:
        perm = train_pp.interleave_layer_perm(
            cfg, mesh.shape["pp"], permuted_chunks)
        reorder = lambda tr: {
            **tr, "layers": jax.tree.map(lambda a: a[perm],
                                         tr["layers"])}
        st = train.TrainState(st.step, reorder(st.params),
                              reorder(st.master), reorder(st.m),
                              reorder(st.v))
        st = jax.device_put(st, train_pp.state_shardings_pp(mesh, cfg))
    return st


def test_pp_moe_schedules_agree_and_router_gets_grads():
    cfg = _cfg()
    toks = _tokens(cfg)

    results = {}
    for sched, chunks, permuted in (("gpipe", 1, None),
                                    ("1f1b", 1, None),
                                    ("zero_bubble", 1, None),
                                    ("interleave_1f1b", 2, 2)):
        mesh = _mesh_for(sched)
        step = train_pp.make_train_step_pp(
            cfg, mesh, num_microbatches=2, schedule=sched,
            num_chunks=chunks)
        st = _state(cfg, mesh, permuted_chunks=permuted)
        # the step donates its input state: snapshot BEFORE stepping
        gate0 = np.asarray(st.master["layers"]["moe_gate"], np.float32)
        st2, m = step(st, toks)
        results[sched] = (float(m["loss"]), float(m["grad_norm"]))
        # router gradients are live: the updated gate differs
        dg = np.abs(np.asarray(
            st2.master["layers"]["moe_gate"], np.float32) - gate0)
        assert dg.max() > 0, f"{sched}: router gate never updated"

    l_ref, g_ref = results["gpipe"]
    assert np.isfinite(l_ref)
    for sched, (l, g) in results.items():
        # bf16 aux transport: ~0.4% relative on the aux term
        np.testing.assert_allclose(l, l_ref, rtol=1e-3, err_msg=sched)
        np.testing.assert_allclose(g, g_ref, rtol=2e-2, err_msg=sched)


def test_pp_moe_aux_actually_contributes():
    """The pipeline loss must include the load-balance aux: it exceeds
    the pure-CE head loss computed from the same final activations."""
    cfg = _cfg()
    mesh = _mesh()
    toks = _tokens(cfg)
    step = train_pp.make_train_step_pp(cfg, mesh, num_microbatches=2,
                                       schedule="1f1b")
    st = _state(cfg, mesh)
    # the step donates its input state: compute references BEFORE stepping
    full = llama.loss_fn(st.params, toks, cfg)
    h, aux = llama._trunk(st.params, toks, cfg, None)
    full, aux = jax.block_until_ready((full, aux))
    _, m = step(st, toks)
    assert float(aux) > 0
    assert float(m["loss"]) > float(full) - float(aux) + 1e-6


def test_pp_moe_trains():
    cfg = _cfg()
    mesh = _mesh_for("interleave_1f1b")
    toks = _tokens(cfg, b=4, s=32)
    step = train_pp.make_train_step_pp(cfg, mesh, num_microbatches=2,
                                       schedule="interleave_1f1b",
                                       num_chunks=2, lr=3e-3)
    st = _state(cfg, mesh, permuted_chunks=2)
    losses = []
    for _ in range(8):
        st, m = step(st, toks)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.2, losses


_EP2_TP2_CHILD = """
import numpy as np
from tests import test_pp_moe as t
cfg = t._cfg()
mesh = t._mesh()
step = t.train_pp.make_train_step_pp(
    cfg, mesh, num_microbatches=2, schedule="interleave_1f1b",
    num_chunks=2)
_, m = step(t._state(cfg, mesh, permuted_chunks=2), t._tokens(cfg))
print("LOSS", float(m["loss"]))
"""


def test_interleave_1f1b_on_ep2_tp2():
    """The hand-written VPP step with two sharded axes beside pp (here
    ep=2 x tp=2; dp=2 x tp=2 in ``__graft_entry__``'s dry run), run in a
    child because XLA:CPU aborts it: ``_interleave_1f1b_core`` evaluates
    the head under ``lax.cond(on_last, ...)``, GSPMD puts the reshard
    between the two axes inside that branch as ONE collective-permute
    naming all eight devices, and XLA:CPU's in-process rendezvous waits
    for all eight where only the last stage's four enter the branch
    (the pairs never leave a stage, so a chip needs no such wait). One
    sharded axis beside pp has no such reshard and runs (above). When
    XLA:CPU stops aborting, this test checks the loss and the tests
    above can go back to tp=2."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=root, JAX_PLATFORMS="cpu")
    # the abort comes after this long a wait for the missing four
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        "--xla_cpu_collective_call_terminate_timeout_seconds=5")
    proc = subprocess.run([sys.executable, "-c", _EP2_TP2_CHILD],
                          env=env, cwd=root, text=True, timeout=300,
                          stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    if proc.returncode == -6 and "collective permute" in proc.stdout \
            and "Termination timeout" in proc.stdout:
        pytest.skip("XLA:CPU aborts interleave_1f1b on ep=2 x tp=2: a "
                    "collective-permute inside the head's lax.cond "
                    "branch waits for all 8 devices, 4 enter it "
                    "(ROADMAP D5)")
    assert proc.returncode == 0, proc.stdout[-1500:]
    assert np.isfinite(float(proc.stdout.rsplit("LOSS", 1)[1]))


# ---------------- fleet engine (PipelineLayer) tier ----------------

def _engine_setup(schedule):
    """Shared fleet init for the engine-tier tests; returns
    (LayerDesc, PipelineLayer, loss_fn)."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.fleet.meta_parallel import (LayerDesc,
                                                            PipelineLayer)
    loss_fn = lambda o, l: ((o - l) ** 2).mean()
    strategy = dist.fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 1,
                               "pp_degree": 4}
    strategy.pipeline_configs = {"accumulate_steps": 4,
                                 "micro_batch_size": 2,
                                 "schedule_mode": schedule}
    dist.fleet.init(strategy=strategy)
    return LayerDesc, PipelineLayer, loss_fn


def _engine_aux_ref(pipe, loss_fn, x, y, m=4):
    """Eager PER-MICROBATCH reference (the pipeline's accounting, same
    as the reference engine's): for each microbatch, loss_fn + that
    microbatch's MoE aux (aux is nonlinear in batch statistics, so
    full-batch aux would NOT match a microbatched pipeline); mean over
    microbatches. Returns (loss, grads) and clears."""
    import paddle_tpu as paddle
    sz = x.shape[0] // m
    total = None
    for i in range(m):
        xi = paddle.to_tensor(x.numpy()[i * sz:(i + 1) * sz])
        yi = paddle.to_tensor(y.numpy()[i * sz:(i + 1) * sz])
        out = pipe(xi)
        loss = loss_fn(out, yi)
        for layer in pipe.sublayers(include_self=True):
            a = getattr(layer, "_last_aux_loss", None)
            if a is not None:
                loss = loss + a
        total = loss if total is None else total + loss
    total = total / m
    total.backward()
    g = {n: p.grad.numpy().copy() for n, p in pipe.named_parameters()}
    for p in pipe.parameters():
        p.clear_grad()
    return float(total.numpy()), g


@pytest.mark.slow
@pytest.mark.parametrize("schedule", ["1F1B", "VPP"])
def test_engine_pp_moe_matches_eager(schedule):
    """Fleet PipelineLayer with MoE layers in every stage: the SPMD
    pipeline loss and grads equal eager loss+aux (the engine carries the
    aux in the carry's extra last-axis slot)."""
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.incubate.distributed.models.moe import MoELayer
    LayerDesc, PipelineLayer, loss_fn = _engine_setup(schedule)
    np.random.seed(5)
    chunks = 2 if schedule == "VPP" else 1
    descs = [LayerDesc(MoELayer, 8, 16, 4, gate="gshard", top_k=2,
                       capacity_factor=2.0)
             for _ in range(4 * chunks)]
    kw = ({"num_virtual_pipeline_stages": 2} if chunks == 2 else {})
    pipe = PipelineLayer(layers=descs, num_stages=4, loss_fn=loss_fn,
                         **kw)
    model = dist.fleet.distributed_model(pipe)
    x = paddle.to_tensor(np.random.rand(8, 8).astype("float32"))
    y = paddle.to_tensor(np.random.rand(8, 8).astype("float32"))
    ref_loss, ref_g = _engine_aux_ref(pipe, loss_fn, x, y)

    import warnings
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        loss = model.forward_backward_pipeline([x, y])
        assert not any("NO pipeline" in str(m.message) for m in w), \
            "pp x MoE fell back to accumulation"
    np.testing.assert_allclose(float(loss.numpy()), ref_loss, rtol=2e-3)
    for n, p in pipe.named_parameters():
        np.testing.assert_allclose(p.grad.numpy(), ref_g[n], atol=2e-3,
                                   err_msg=f"{schedule}: {n}")


@pytest.mark.slow
def test_engine_pp_moe_hetero_matches_eager():
    """Hetero stages (embed != MoE blocks != head) under the hetero SPMD
    path with the aux slot on the carry."""
    import warnings
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.incubate.distributed.models.moe import MoELayer
    LayerDesc, PipelineLayer, loss_fn = _engine_setup("1F1B")
    np.random.seed(6)
    descs = [
        LayerDesc(paddle.nn.Embedding, 16, 8),               # stage 0
        LayerDesc(MoELayer, 8, 16, 4, gate="gshard", top_k=2,
                  capacity_factor=2.0),                      # stage 1
        LayerDesc(paddle.nn.Linear, 8, 8),                   # stage 2
        LayerDesc(MoELayer, 8, 16, 4, gate="gshard", top_k=2,
                  capacity_factor=2.0),                      # stage 3
    ]
    pipe = PipelineLayer(layers=descs, num_stages=4, loss_fn=loss_fn)
    model = dist.fleet.distributed_model(pipe)
    x = paddle.to_tensor(np.random.randint(0, 16, (8,)).astype("int64"))
    y = paddle.to_tensor(np.random.rand(8, 8).astype("float32"))
    ref_loss, ref_g = _engine_aux_ref(pipe, loss_fn, x, y)

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        loss = model.forward_backward_pipeline([x, y])
        assert not any("NO pipeline" in str(m.message) for m in w)
    np.testing.assert_allclose(float(loss.numpy()), ref_loss, rtol=2e-3)
    for n, p in pipe.named_parameters():
        np.testing.assert_allclose(p.grad.numpy(), ref_g[n], atol=2e-3,
                                   err_msg=n)


@pytest.mark.slow
def test_engine_pp_moe_fallback_keeps_aux():
    """The accumulation FALLBACK must include MoE aux too — otherwise the
    engine's loss (and the routers' gradients) would be path-dependent.
    Trigger the fallback with a shape-changing mid-ring stage."""
    import warnings
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.incubate.distributed.models.moe import MoELayer
    LayerDesc, PipelineLayer, loss_fn = _engine_setup("1F1B")
    np.random.seed(7)
    descs = [
        LayerDesc(MoELayer, 8, 16, 4, gate="gshard", top_k=2,
                  capacity_factor=2.0),
        LayerDesc(paddle.nn.Linear, 8, 12),   # widens mid-ring: fallback
        LayerDesc(paddle.nn.Linear, 12, 8),
        LayerDesc(paddle.nn.Linear, 8, 8),
    ]
    pipe = PipelineLayer(layers=descs, num_stages=4, loss_fn=loss_fn)
    model = dist.fleet.distributed_model(pipe)
    x = paddle.to_tensor(np.random.rand(8, 8).astype("float32"))
    y = paddle.to_tensor(np.random.rand(8, 8).astype("float32"))
    ref_loss, ref_g = _engine_aux_ref(pipe, loss_fn, x, y)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        loss = model.forward_backward_pipeline([x, y])
        assert any("NO pipeline" in str(m.message) for m in w)
    np.testing.assert_allclose(float(loss.numpy()), ref_loss, rtol=1e-4)
    for n, p in pipe.named_parameters():
        np.testing.assert_allclose(p.grad.numpy(), ref_g[n], atol=1e-3,
                                   err_msg=n)


@pytest.mark.slow
def test_engine_pp_moe_in_pre_peel():
    """An MoE layer peeled into the PRE segment (stage 0 = [MoELayer,
    Linear(8->16)], carry 16-wide): its aux is computed per MICROBATCH
    under the vmap (the vmap maps over microbatches, not examples) and
    must match the per-microbatch eager reference."""
    import warnings
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.incubate.distributed.models.moe import MoELayer
    LayerDesc, PipelineLayer, loss_fn = _engine_setup("1F1B")
    np.random.seed(8)
    descs = [
        LayerDesc(MoELayer, 8, 16, 4, gate="gshard", top_k=2,
                  capacity_factor=2.0),
        LayerDesc(paddle.nn.Linear, 8, 16),                  # stage 0
        LayerDesc(paddle.nn.Linear, 16, 16),                 # stage 1
        LayerDesc(paddle.nn.Linear, 16, 16),                 # stage 2
        LayerDesc(paddle.nn.Linear, 16, 16),                 # stage 3
    ]
    pipe = PipelineLayer(layers=descs, num_stages=4, loss_fn=loss_fn)
    model = dist.fleet.distributed_model(pipe)
    x = paddle.to_tensor(np.random.rand(8, 8).astype("float32"))
    y = paddle.to_tensor(np.random.rand(8, 16).astype("float32"))
    ref_loss, ref_g = _engine_aux_ref(pipe, loss_fn, x, y)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        loss = model.forward_backward_pipeline([x, y])
        assert not any("NO pipeline" in str(m.message) for m in w)
    np.testing.assert_allclose(float(loss.numpy()), ref_loss, rtol=2e-3)
    for n, p in pipe.named_parameters():
        np.testing.assert_allclose(p.grad.numpy(), ref_g[n], atol=2e-3,
                                   err_msg=n)
