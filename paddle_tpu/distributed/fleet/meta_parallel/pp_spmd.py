"""SPMD pipeline parallelism: stacked stages + ppermute rotation.

This is the TPU-native execution of pipeline parallelism — the counterpart
of the reference's multi-process 1F1B engine
(reference: python/paddle/distributed/fleet/meta_parallel/
pipeline_parallel.py:575 forward_backward_pipeline + pp_utils/
p2p_communication.py eager NCCL p2p). The reference pipelines across
*processes*; XLA pipelines across *mesh coordinates inside one program*:

- Each coordinate of the ``pp`` mesh axis holds ONE stage's weights: every
  homogeneous-stage parameter is stacked with a leading ``[num_stages]``
  axis sharded over ``pp``.
- A ``lax.scan`` runs M + P - 1 ticks. Per tick each stage applies its
  layer block, then activations rotate one hop along the pp ring via
  ``lax.ppermute`` (ICI neighbour traffic only). Stage 0 feeds a fresh
  microbatch each tick; stage P-1 emits a finished microbatch from tick
  P-1 on — the classic GPipe wavefront.
- Differentiating through the scan + ppermute gives the reverse wavefront
  (ppermute transposes to the opposite rotation, scan reverses time): the
  backward pipeline the reference hand-schedules falls out of AD.
- Other mesh axes (dp/mp/...) are listed outside ``axis_names`` so GSPMD
  keeps auto-sharding them inside the manual pp program (jax.shard_map
  partial-manual mode).

Four schedules, mirroring the reference's set (reference:
meta_parallel/pipeline_parallel.py:575 1F1B, :1174 interleaved VPP, :2256
FThenB; passes/pipeline_scheduler_pass/pipeline_zero_bubble.py:62):

- ``gpipe`` (``pipeline_spmd``): forward wavefront scan; AD reverses it.
  Bubble (P-1)/(M+P-1); activation residency grows with M (all in-flight
  microbatch residuals live until the backward wavefront).
- ``interleave`` (``pipeline_interleave``): each pp coordinate holds
  ``num_chunks`` non-adjacent virtual stages (Megatron VPP); microbatches
  lap the ring num_chunks times. Bubble shrinks to
  (P-1)/(M*num_chunks + P-1) at GPipe-like residency.
- ``1f1b`` (``pipeline_1f1b``): ONE combined scan runs the forward and the
  hand-written backward concurrently; stage inputs live in a (2P-1)-slot
  ring carried through the scan, so activation residency is bounded by the
  pipeline depth — NOT by M. This is the reference 1F1B's memory contract;
  under lockstep SPMD it costs ~P extra ticks vs gpipe, the price of
  in-scan backward. Backward recomputes the stage forward from the saved
  input (remat), the same tradeoff the big configs already take.
- ``zero_bubble`` (``pipeline_1f1b(defer_dw=True)``): 1F1B structure but
  the per-tick backward computes only dX (the serial dependency); dW
  matmuls are hoisted out of the scan into a scan-accumulated pass over
  the stashed (input, cotangent) pairs — the XLA translation of
  zero-bubble's "fill bubbles with W-grad work": the serialized chain per
  tick drops from fwd+dX+dW to fwd+dX, at gpipe-like stash memory. The
  dW tail accumulates via lax.scan, NOT vmap: a vmapped tail
  materializes T full dW trees at once (AOT-measured 307 GB temp on the
  13B recipe vs 27 GB for 1f1b).
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stack_stage_params(per_stage_params: Sequence[Any], mesh: Mesh,
                       pp_axis: str = "pp"):
    """Stack per-stage pytrees into leading-[P] arrays sharded over pp."""
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0),
                           *per_stage_params)

    def place(x):
        spec = [pp_axis] + [None] * (x.ndim - 1)
        try:
            return jax.device_put(x, NamedSharding(mesh, P(*spec)))
        except Exception:
            return x
    return jax.tree.map(place, stacked)


def _psum_act(x, pp_axis: str, mesh: Mesh):
    """psum for activation-sized tensors. On CPU meshes the all-reduce
    runs in f32: XLA's CPU AllReducePromotion pass CHECK-crashes cloning
    a bf16 all-reduce whose reducer carries a copy ("Invalid binary
    instruction opcode copy", hlo_instruction.cc:1585 — observed
    AOT-compiling the 13B bf16 recipe on the 16-device CPU mesh; TPU
    backends never run that pass). Native-dtype psum is kept on TPU so
    the collective rides ICI at bf16 bytes.

    THE SAME XLA BUG has two workarounds in this repo — this is the
    canonical inventory so one can be retired when upstream fixes the
    CHECK:

    1. **This f32 upcast** — covers every bf16 activation psum the
       SPMD pipeline entry points emit EXPLICITLY (``pipeline_spmd``,
       ``pipeline_spmd_grad``, ``pipeline_spmd_hetero``, and the
       interleave forward), i.e. all in-process CPU-mesh runs: tier-1
       tests, the 16-device CPU smoke meshes, eager fleet engines.
    2. **The XLA-flag disable** (``tools/aot_validate.py`` child env:
       ``--xla_disable_hlo_passes=all-reduce-promotion``) — needed
       because the interleave-schedule AD graph also contains
       GSPMD-INSERTED bf16 all-reduces that never route through this
       helper, so the upcast can't reach them; bf16 all-reduces compile
       and run correctly on CPU with the pass off.

    Retirement order once the upstream CHECK is fixed: drop (1) first
    (native bf16 everywhere, this helper becomes plain ``lax.psum``),
    then (2); keep them in lockstep with this docstring. Set
    ``PADDLE_TPU_NATIVE_BF16_PSUM=1`` to bypass the upcast early and
    probe whether the installed XLA still crashes."""
    import os
    if mesh.devices.flat[0].platform == "cpu" and \
            x.dtype == jnp.bfloat16 and \
            not os.environ.get("PADDLE_TPU_NATIVE_BF16_PSUM"):
        return lax.psum(x.astype(jnp.float32), pp_axis).astype(x.dtype)
    return lax.psum(x, pp_axis)


def pipeline_spmd(stage_fn: Callable, stacked_params, microbatches,
                  mesh: Mesh, pp_axis: str = "pp",
                  last_fn: Optional[Callable] = None):
    """Run the GPipe wavefront over the pp axis.

    stage_fn(stage_params, x) -> y         (uniform across stages)
    stacked_params: pytree, leading dim [P] sharded over pp_axis
    microbatches:   [M, mb, ...] input activations for stage 0
    last_fn(y) -> z (optional): applied to finished microbatches
    returns [M, ...] outputs of the last stage.
    """
    num_stages = mesh.shape[pp_axis]
    M = microbatches.shape[0]
    T = M + num_stages - 1
    manual = frozenset({pp_axis})

    def per_device(params_local, mb_local):
        # params_local: my stage's params (leading dim 1) ; squeeze it
        params_me = jax.tree.map(lambda x: x[0], params_local)
        stage_id = lax.axis_index(pp_axis)
        perm_fwd = [(i, (i + 1) % num_stages) for i in range(num_stages)]

        x0 = jnp.zeros_like(mb_local[0])

        def tick(carry, t):
            recv = carry
            feed = mb_local[jnp.minimum(t, M - 1)]
            x_in = jnp.where(stage_id == 0, feed, recv)
            y = stage_fn(params_me, x_in)
            nxt = lax.ppermute(y, pp_axis, perm_fwd)
            return nxt, y

        _, ys = lax.scan(tick, x0, jnp.arange(T))
        # finished microbatches leave the last stage at ticks [P-1, T-1]
        outs = lax.dynamic_slice_in_dim(ys, num_stages - 1, M, axis=0)
        # broadcast last-stage outputs to all pp coords so the result is
        # replicated over pp (callers compute loss once)
        mask = (stage_id == num_stages - 1).astype(outs.dtype)
        outs = _psum_act(outs * mask, pp_axis, mesh)
        return outs

    fn = jax.shard_map(
        per_device, mesh=mesh, axis_names=manual,
        in_specs=(jax.tree.map(lambda _: P(pp_axis), stacked_params), P()),
        out_specs=P(), check_vma=False)
    outs = fn(stacked_params, microbatches)
    if last_fn is not None:
        outs = jax.vmap(last_fn)(outs)
    return outs


def pipeline_loss_spmd(stage_fn: Callable, loss_fn: Callable,
                       stacked_params, head_params, microbatches, labels,
                       mesh: Mesh, pp_axis: str = "pp"):
    """Pipeline + per-microbatch loss, averaged — the training objective.

    loss_fn(head_params, y, label) -> scalar loss for one microbatch.
    Returns mean loss over microbatches; differentiable w.r.t. both
    stacked_params and head_params.
    """
    outs = pipeline_spmd(stage_fn, stacked_params, microbatches, mesh,
                         pp_axis)
    losses = jax.vmap(lambda y, l: loss_fn(head_params, y, l))(outs, labels)
    return jnp.mean(losses)


def stack_stage_params_interleaved(per_stage_params: Sequence[Any],
                                   mesh: Mesh, num_chunks: int,
                                   pp_axis: str = "pp"):
    """Stack V = P*num_chunks virtual-stage pytrees into [P, num_chunks, ...]
    arrays (virtual stage s lives on device s % P as chunk s // P — the
    Megatron round-robin layout), dim 0 sharded over pp."""
    P_ = mesh.shape[pp_axis]
    V = P_ * num_chunks
    assert len(per_stage_params) == V
    rows = []
    for d in range(P_):
        chunks = [per_stage_params[c * P_ + d] for c in range(num_chunks)]
        rows.append(jax.tree.map(lambda *xs: jnp.stack(xs, 0), *chunks))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs, 0), *rows)

    def place(x):
        spec = [pp_axis] + [None] * (x.ndim - 1)
        try:
            return jax.device_put(x, NamedSharding(mesh, P(*spec)))
        except Exception:
            return x
    return jax.tree.map(place, stacked)


def pipeline_interleave(stage_fn: Callable, stacked_params, microbatches,
                        mesh: Mesh, num_chunks: int, pp_axis: str = "pp"):
    """Interleaved (VPP) wavefront: V = P*num_chunks virtual stages laid
    out round-robin; the Megatron interleaved schedule in closed form.

    Device d at tick t serves coordinate u = t - d, decomposed
    u = g*(v*P) + c*P + r  ->  chunk c, microbatch m = g*P + r.
    This is a per-device bijection (each device busy every steady tick) and
    every virtual stage's output is consumed by the next ring device exactly
    one tick later — so a single ppermute carries all traffic and the
    wavefront finishes in T = M*num_chunks + P - 1 ticks: bubble
    (P-1)/(M*v + P-1), the VPP contract. Requires M % P == 0 (Megatron's
    constraint, reference: meta_parallel/pipeline_parallel.py:1174).

    stage_fn(chunk_params, x) -> y        (uniform across virtual stages)
    stacked_params: pytree [P, num_chunks, ...], dim 0 sharded over pp
    microbatches:   [M, mb, ...] stage-0 inputs
    returns [M, ...] outputs of the last virtual stage. Differentiable.
    """
    num_stages = mesh.shape[pp_axis]
    M = microbatches.shape[0]
    assert M % num_stages == 0, (
        f"interleaved schedule needs microbatches ({M}) % pp stages "
        f"({num_stages}) == 0")
    vP = num_stages * num_chunks
    T = M * num_chunks + num_stages - 1
    manual = frozenset({pp_axis})

    def per_device(params_local, mb_local):
        params_me = jax.tree.map(lambda x: x[0], params_local)  # [v, ...]
        stage = lax.axis_index(pp_axis)
        perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
        x0 = jnp.zeros_like(mb_local[0])
        out0 = jnp.zeros((M,) + mb_local.shape[1:], mb_local.dtype)

        def tick(carry, t):
            x_rc, out_buf = carry
            u = t - stage
            g = jnp.where(u >= 0, u // vP, 0)
            rem = jnp.clip(u - g * vP, 0, vP - 1)
            c = rem // num_stages
            m = jnp.clip(g * num_stages + rem % num_stages, 0, M - 1)
            active = (u >= 0) & (u < M * num_chunks)

            feed = lax.dynamic_index_in_dim(mb_local, m, 0, keepdims=False)
            x_in = jnp.where((stage == 0) & (c == 0), feed, x_rc)
            p_c = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, c, 0, keepdims=False),
                params_me)
            y = stage_fn(p_c, x_in)
            y = jnp.where(active, y, x_in)

            emit = active & (stage == num_stages - 1) & (c == num_chunks - 1)
            upd = lax.dynamic_update_index_in_dim(
                out_buf, y.astype(out_buf.dtype), m, 0)
            out_buf = jnp.where(emit, upd, out_buf)

            x_nx = lax.ppermute(y, pp_axis, perm)
            return (x_nx, out_buf), None

        (_, outs), _ = lax.scan(tick, (x0, out0), jnp.arange(T))
        # out_buf is populated only on the last stage; replicate over pp
        mask = (stage == num_stages - 1).astype(outs.dtype)
        return _psum_act(outs * mask, pp_axis, mesh)

    fn = jax.shard_map(
        per_device, mesh=mesh, axis_names=manual,
        in_specs=(jax.tree.map(lambda _: P(pp_axis), stacked_params), P()),
        out_specs=P(), check_vma=False)
    return fn(stacked_params, microbatches)


def _interleave_1f1b_core(apply_chunk, stacked_vec, head_params,
                          microbatches, labels, mesh: Mesh,
                          num_chunks: int, pp_axis: str, loss_fn,
                          vec_spec, defer_dw: bool = False):
    """Shared combined fwd+bwd scan for the interleaved (VPP) 1F1B
    schedule — the closed forms documented on pipeline_interleave_1f1b.
    ``apply_chunk(params_me, c, x, d)`` applies this device's virtual
    stage of chunk ``c``; ``vec_spec`` is the shard_map pytree-prefix
    spec for the stacked carrier (and its gradient). ``defer_dw`` is the
    ZB-V composition: the per-tick backward emits only dX, and dW
    accumulates in a scan-accumulated tail over the stashed (input,
    cotangent, chunk) triples — the zero-bubble contract at the VPP
    bubble, with O(1) dW memory like pipeline_1f1b's defer_dw."""
    num_stages = mesh.shape[pp_axis]
    C = num_chunks
    V = num_stages * C
    M = microbatches.shape[0]
    assert M % num_stages == 0, (
        f"interleaved schedule needs microbatches ({M}) % pp stages "
        f"({num_stages}) == 0")
    U = M * C
    T = U + V + num_stages - 2
    R = 2 * V - 1
    manual = frozenset({pp_axis})
    inv_m = 1.0 / M

    def per_device(vec_local, head, mb_local, lab_local):
        vec_me = jax.tree.map(lambda a: a[0], vec_local)
        d = lax.axis_index(pp_axis)
        P_ = num_stages
        last = P_ - 1
        perm_f = [(i, (i + 1) % P_) for i in range(P_)]
        perm_b = [(i, (i - 1) % P_) for i in range(P_)]

        zero_x = jnp.zeros_like(mb_local[0])
        ring0 = jnp.zeros((R,) + zero_x.shape, zero_x.dtype)
        dw0 = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                           vec_me)
        dhead0 = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                              head)
        dx0 = jnp.zeros((M,) + zero_x.shape, jnp.float32)

        def tick(carry, t):
            (f_rc, b_rc, ring, dw, dhead, dx_out, loss_acc) = carry

            # ---- forward unit u = t - d ----
            u = t - d
            f_on = (u >= 0) & (u < U)
            uc = jnp.clip(u, 0, U - 1)
            g_f = uc // V
            rem_f = uc - g_f * V
            c_f = rem_f // P_
            m_f = jnp.clip(g_f * P_ + rem_f % P_, 0, M - 1)
            feed = lax.dynamic_index_in_dim(mb_local, m_f, 0,
                                            keepdims=False)
            x_in = jnp.where((d == 0) & (c_f == 0), feed, f_rc)
            y = apply_chunk(vec_me, c_f, x_in, d)
            ring = jnp.where(
                f_on,
                lax.dynamic_update_index_in_dim(ring, x_in,
                                                jnp.mod(t, R), 0),
                ring)

            # head loss + cotangent on the LAST virtual stage's output.
            # Gated behind ``on_last`` (ADVICE r5): only the last
            # device's last chunk ever uses these values — off-tick
            # lanes previously paid a full head forward+backward (a
            # vocab-sized matmul pair at LM shapes) per tick just to be
            # masked to zero. ``lax.cond`` evaluates the cheap
            # zeros branch instead on every other (device, tick).
            # Parity: the only OFF-tick consumer is ``dy_self`` via the
            # ``(d == last) & (c_b == C-1)`` select below, and at the
            # ticks where that backward is ON its unit coincides with
            # this tick's forward unit (u_b == u), which makes the
            # predicate equal to ``on_last`` — so a live path never
            # reads the zeros.
            lab = jax.tree.map(
                lambda l: lax.dynamic_index_in_dim(l, m_f, 0,
                                                   keepdims=False),
                lab_local)
            on_last = f_on & (d == last) & (c_f == C - 1)

            def _head_eval(hp, yy):
                lval, head_vjp = jax.vjp(
                    lambda h, yo: loss_fn(h, yo, lab), hp, yy)
                dhead_c, dy_self = head_vjp(
                    jnp.asarray(inv_m, jnp.float32))
                return lval, dhead_c, dy_self

            lval, dhead_c, dy_self = lax.cond(
                on_last, _head_eval,
                lambda hp, yy: jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype),
                    jax.eval_shape(_head_eval, hp, yy)),
                head, y)
            loss_acc = loss_acc + jnp.where(on_last, lval, 0.0)
            dhead = jax.tree.map(
                lambda acc, g: acc + jnp.where(on_last, g, 0.0),
                dhead, dhead_c)

            # ---- backward unit w = t - (V-1) - (P-1-d) ----
            w = t - (V - 1) - (last - d)
            b_on = (w >= 0) & (w < U)
            wc = jnp.clip(w, 0, U - 1)
            g_b = wc // V
            rem_b = wc - g_b * V
            c_b = C - 1 - rem_b // P_
            # forward of this unit ran here at tick u_b + d
            u_b = g_b * V + c_b * P_ + rem_b % P_
            slot_b = jnp.mod(u_b + d, R)
            x_sv = lax.dynamic_index_in_dim(ring, slot_b, 0,
                                            keepdims=False)
            dy_in = jnp.where((d == last) & (c_b == C - 1),
                              dy_self.astype(b_rc.dtype), b_rc)
            _, stage_vjp = jax.vjp(
                lambda vme, xx: apply_chunk(vme, c_b, xx, d), vec_me,
                x_sv)
            # vjp through dynamic_index scatters into a full-size tree
            # (zeros off-chunk), so plain accumulation lands the chunk's
            # grads without any indexed add
            dv_c, dx_c = stage_vjp(dy_in)
            if not defer_dw:
                dw = jax.tree.map(
                    lambda acc, g: acc + jnp.where(b_on,
                                                   g.astype(jnp.float32),
                                                   0.0),
                    dw, dv_c)
            m_b = jnp.clip(g_b * P_ + rem_b % P_, 0, M - 1)
            dx_out = jnp.where(
                b_on & (d == 0) & (c_b == 0),
                lax.dynamic_update_index_in_dim(
                    dx_out, dx_c.astype(jnp.float32), m_b, 0),
                dx_out)

            f_nx = lax.ppermute(y, pp_axis, perm_f)
            b_nx = lax.ppermute(dx_c.astype(b_rc.dtype), pp_axis, perm_b)
            stash = (x_sv, dy_in, b_on, c_b) if defer_dw else None
            return (f_nx, b_nx, ring, dw, dhead, dx_out, loss_acc), stash

        init = (zero_x, jnp.zeros_like(zero_x), ring0, dw0, dhead0,
                dx0, jnp.float32(0.0))
        (_, _, _, dw, dhead, dx_out, loss_acc), stash = lax.scan(
            tick, init, jnp.arange(T))

        if defer_dw:
            # scan-accumulated dW tail (NOT vmap — see pipeline_1f1b's
            # defer_dw note: a vmapped tail materializes T dW trees)
            xs, dys, mask, cs = stash

            def acc_one(acc, xdmc):
                x_sv, dy, on, c = xdmc
                _, vjp = jax.vjp(
                    lambda vme, xx: apply_chunk(vme, c, xx, d), vec_me,
                    x_sv)
                dv = vjp(dy)[0]
                return jax.tree.map(
                    lambda a, g: a + jnp.where(on, g.astype(jnp.float32),
                                               0.0), acc, dv), None
            dw, _ = lax.scan(acc_one, dw, (xs, dys, mask, cs))

        lastf = (d == last).astype(jnp.float32)
        loss_mean = lax.psum(loss_acc * lastf, pp_axis) * inv_m
        dhead = jax.tree.map(lambda g: lax.psum(g * lastf, pp_axis), dhead)
        dx_out = lax.psum(
            dx_out * (d == 0).astype(jnp.float32), pp_axis)
        return loss_mean, jax.tree.map(lambda a: a[None], dw), dhead, \
            dx_out

    fn = jax.shard_map(
        per_device, mesh=mesh, axis_names=manual,
        in_specs=(vec_spec, P(), P(), P()),
        out_specs=(P(), vec_spec, P(), P()),
        check_vma=False)
    return fn(stacked_vec, head_params, microbatches, labels)


def pipeline_interleave_1f1b(stage_fn: Callable, loss_fn: Callable,
                             stacked_params, head_params, microbatches,
                             labels, mesh: Mesh, num_chunks: int,
                             pp_axis: str = "pp",
                             defer_dw: bool = False):
    """Interleaved (VPP) schedule with a HAND-WRITTEN depth-bounded
    backward — the memory contract of ``pipeline_1f1b`` at the bubble of
    ``pipeline_interleave``.

    Motivation: AD through the interleave wavefront keeps every
    in-flight microbatch residual alive until the reverse wavefront (an
    AOT compile of a 13B recipe predicted 223 GB a chip: ROADMAP D5).
    Here the combined
    scan runs one forward AND one backward VIRTUAL-STAGE unit per tick
    (the shared ``_interleave_1f1b_core``), stashing only raw stage
    inputs in a (2V-1)-slot ring (V = P*C virtual stages), so activation
    residency is bounded by the virtual pipeline depth — NOT by M —
    while the bubble stays the VPP (P-1)/(M*C + P-1) class. This is the
    TPU lockstep translation of Megatron's interleaved 1F1B (reference:
    meta_parallel/pipeline_parallel.py:1174
    forward_backward_pipeline_with_interleaving).

    Schedule closed forms (d = device, t = tick, requires M % P == 0):
    - forward: unit u = t - d; u = g*V + c*P + r -> chunk c,
      microbatch m = g*P + r. Output ppermutes d -> d+1 (wrap P-1 -> 0
      carries chunk c's exit into chunk c+1's entry), consumed next tick.
    - backward: unit w = t - (V-1) - (P-1-d); w = g*V + q*P + r ->
      chunk c = C-1 - q, microbatch m = g*P + r. Cotangent ppermutes
      d -> d-1 (wrap 0 -> P-1 carries chunk c+1's entry-grad back to
      chunk c's exit), consumed next tick. The first backward (v = V-1)
      consumes the same-tick head-loss cotangent, as in pipeline_1f1b.
    - the stash ring holds stage INPUTS by forward tick mod (2V-1); the
      backward of a unit forward-run at tick t_f reads slot t_f mod R,
      and max(t_b - t_f) = 2V - 2 < R, so no slot is overwritten early.
      Backward recomputes the stage forward from the saved input (remat).

    stage_fn(chunk_params, x) -> y; loss_fn(head_params, y, label) ->
    scalar (per-microbatch, scaled by 1/M here).
    stacked_params: pytree [P, num_chunks, ...] round-robin layout
    (virtual stage v at [v % P, v // P]), dim 0 sharded over pp.
    Returns (mean_loss, d_stacked [P, num_chunks, ...] f32, d_head,
    d_microbatches) — gradients accumulate in f32.
    """
    def apply_chunk(vme, c, x, d):
        p_c = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, c, 0, keepdims=False),
            vme)
        return stage_fn(p_c, x)

    return _interleave_1f1b_core(
        apply_chunk, stacked_params, head_params, microbatches, labels,
        mesh, num_chunks, pp_axis, loss_fn,
        jax.tree.map(lambda _: P(pp_axis), stacked_params),
        defer_dw=defer_dw)



def pipeline_1f1b(stage_fn: Callable, loss_fn: Callable, stacked_params,
                  head_params, microbatches, labels, mesh: Mesh,
                  pp_axis: str = "pp", defer_dw: bool = False):
    """Combined forward/backward 1F1B scan with depth-bounded residency.

    stage_fn(stage_params, x) -> y           (uniform across stages)
    loss_fn(head_params, y, label) -> scalar (per-microbatch mean loss)
    stacked_params: pytree [P, ...] sharded over pp_axis
    head_params:    replicated pytree (final norm / head weights)
    microbatches:   [M, mb, ...]; labels: [M, ...]

    Returns (mean_loss, d_stacked_params, d_head_params, d_microbatches) —
    the hand-written pipeline VJP: stage i runs fwd of microbatch m at tick
    i+m and bwd at tick 2(P-1)-i+m, stage inputs parked in a (2P-1)-slot
    ring carried through the scan (activation residency ~2P, independent of
    M). With defer_dw (zero-bubble), the in-scan backward emits only dX and
    the stashed (x, dy) pairs; dW is one batched vjp after the scan.
    """
    num_stages = mesh.shape[pp_axis]
    M = microbatches.shape[0]
    T = M + 2 * num_stages - 2
    R = 2 * num_stages - 1
    manual = frozenset({pp_axis})
    inv_m = 1.0 / M

    def per_device(params_local, head, mb_local, lab_local):
        params_me = jax.tree.map(lambda x: x[0], params_local)
        stage = lax.axis_index(pp_axis)
        last = num_stages - 1
        perm_f = [(i, (i + 1) % num_stages) for i in range(num_stages)]
        perm_b = [(i, (i - 1) % num_stages) for i in range(num_stages)]

        zero_x = jnp.zeros_like(mb_local[0])
        ring0 = jnp.zeros((R,) + zero_x.shape, zero_x.dtype)
        dwsum0 = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                              params_me)
        dhead0 = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                              head)
        dx0 = jnp.zeros((M,) + zero_x.shape, jnp.float32)

        def tick(carry, t):
            (f_rc, b_rc, ring, dw, dhead, dx_out, loss_acc) = carry

            # ---- forward slot: stage i runs microbatch m_f = t - i ----
            m_f = t - stage
            f_on = (m_f >= 0) & (m_f < M)
            feed = lax.dynamic_index_in_dim(
                mb_local, jnp.clip(m_f, 0, M - 1), 0, keepdims=False)
            x_in = jnp.where(stage == 0, feed, f_rc)
            y = stage_fn(params_me, x_in)
            slot_f = jnp.mod(t, R)
            ring = jnp.where(
                f_on,
                lax.dynamic_update_index_in_dim(ring, x_in, slot_f, 0),
                ring)

            # last stage: per-microbatch loss + cotangent, scaled by 1/M
            lab = jax.tree.map(
                lambda l: lax.dynamic_index_in_dim(
                    l, jnp.clip(m_f, 0, M - 1), 0, keepdims=False),
                lab_local)
            lval, head_vjp = jax.vjp(lambda hp, yy: loss_fn(hp, yy, lab),
                                     head, y)
            dhead_c, dy_self = head_vjp(jnp.asarray(inv_m, jnp.float32))
            on_last = f_on & (stage == last)
            loss_acc = loss_acc + jnp.where(on_last, lval, 0.0)
            dhead = jax.tree.map(
                lambda acc, g: acc + jnp.where(on_last, g, 0.0),
                dhead, dhead_c)

            # ---- backward slot: stage i runs m_b = t - (2P-2-i) ----
            m_b = t - (2 * last - stage)
            b_on = (m_b >= 0) & (m_b < M)
            # fwd of m_b on this stage happened at tick stage + m_b
            slot_b = jnp.mod(stage + jnp.clip(m_b, 0, M - 1), R)
            x_sv = lax.dynamic_index_in_dim(ring, slot_b, 0, keepdims=False)
            dy_in = jnp.where(stage == last, dy_self.astype(b_rc.dtype),
                              b_rc)
            _, stage_vjp = jax.vjp(stage_fn, params_me, x_sv)
            dp_c, dx_c = stage_vjp(dy_in)
            if not defer_dw:
                dw = jax.tree.map(
                    lambda acc, g: acc + jnp.where(b_on, g, 0.0).astype(
                        jnp.float32),
                    dw, dp_c)
            dx_out = jnp.where(
                b_on & (stage == 0),
                lax.dynamic_update_index_in_dim(
                    dx_out, dx_c.astype(jnp.float32),
                    jnp.clip(m_b, 0, M - 1), 0),
                dx_out)

            f_nx = lax.ppermute(y, pp_axis, perm_f)
            b_nx = lax.ppermute(dx_c.astype(b_rc.dtype), pp_axis, perm_b)
            stash = (x_sv, dy_in, b_on) if defer_dw else None
            return (f_nx, b_nx, ring, dw, dhead, dx_out, loss_acc), stash

        init = (zero_x, jnp.zeros_like(zero_x), ring0, dwsum0, dhead0,
                dx0, jnp.float32(0.0))
        (_, _, _, dw, dhead, dx_out, loss_acc), stash = lax.scan(
            tick, init, jnp.arange(T))

        if defer_dw:
            # dW AFTER the pipeline scan (the zero-bubble point: dW work
            # leaves the serialized per-tick path) — but accumulated with
            # a scan, NOT a vmap: vmapping the per-tick vjp materializes
            # T full dW trees at once (AOT-measured 307 GB temp on the
            # 13B recipe vs 27 GB for 1f1b); the scan keeps dW at O(1)
            xs, dys, mask = stash

            def acc_one(acc, xdm):
                x_sv, dy, on = xdm
                _, vjp = jax.vjp(stage_fn, params_me, x_sv)
                dp = vjp(dy)[0]
                return jax.tree.map(
                    lambda a, g: a + jnp.where(on, g, 0.0).astype(
                        jnp.float32), acc, dp), None
            dw, _ = lax.scan(acc_one, dw, (xs, dys, mask))

        # replicate scalars / edge products over pp (mask -> psum)
        lastf = (stage == last).astype(jnp.float32)
        loss_mean = lax.psum(loss_acc * lastf, pp_axis) * inv_m
        dhead = jax.tree.map(lambda g: lax.psum(g * lastf, pp_axis), dhead)
        dx_out = lax.psum(
            dx_out * (stage == 0).astype(jnp.float32), pp_axis)
        dw = jax.tree.map(lambda g: g[None], dw)  # -> [1,...] per device
        return loss_mean, dw, dhead, dx_out

    fn = jax.shard_map(
        per_device, mesh=mesh, axis_names=manual,
        in_specs=(jax.tree.map(lambda _: P(pp_axis), stacked_params),
                  P(), P(), P()),
        out_specs=(P(), jax.tree.map(lambda _: P(pp_axis), stacked_params),
                   P(), P()),
        check_vma=False)
    return fn(stacked_params, head_params, microbatches, labels)


# --------------------------------------------------------------------------
# Heterogeneous stages (VERDICT r2 missing #4)
#
# The reference segments ARBITRARY layers into stages
# (reference: meta_parallel/parallel_layers/pp_layers.py:93 SegmentLayers,
# :258 PipelineLayer) — stage 0 (embedding) != mid (decoder blocks) != last
# (norm + head). The stacked-stage formulation above needs identical
# per-stage param structures; the heterogeneous formulation below removes
# that requirement the TPU way:
#
# - Each stage's param pytree is FLATTENED into per-dtype NATIVE vectors
#   ({dtype_name: vector}); per dtype, vectors pad to the longest stage and
#   stack into [P, Lmax_dt] sharded over pp — memory still scales ~1/P
#   (padding waste bounded by the largest stage), and bf16 params cost bf16
#   bytes in the stacked copy (VERDICT r4 weak #4: the earlier single-f32
#   carrier doubled the stacked copy's HBM for bf16 configs). Gradients
#   still ACCUMULATE in f32 regardless of storage dtype.
# - Inside the shard_map, ``lax.switch(stage_id, branches)`` dispatches to
#   the stage's own function; branch s statically knows stage s's
#   (treedef, shapes, dtypes) spec and carves its slice of the vector.
# - The activation CARRY stays one static shape (XLA requirement). Shape-
#   changing entry/exit layers (token embedding in, lm head out) run
#   outside the ring — embedding before microbatching, head inside the
#   per-microbatch loss — exactly how the flagship pp step is built
#   (models/train_pp.py).
# --------------------------------------------------------------------------
import numpy as _np


def _flatten_stage(params):
    """pytree -> ({dtype_name: native-dtype vector},
    (treedef, [(shape, dtype), ...]))."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    metas, groups = [], {}
    for l in leaves:
        dt = jnp.result_type(l)
        assert jnp.issubdtype(dt, jnp.floating), (
            f"heterogeneous stage stacking carries params through flat"
            f" per-dtype vectors; non-float leaf {dt} is not supported")
        metas.append((tuple(l.shape), dt))
        groups.setdefault(jnp.dtype(dt).name, []).append(
            jnp.asarray(l).reshape(-1))
    vecs = {k: jnp.concatenate(v) for k, v in groups.items()}
    return vecs, (treedef, metas)


def unflatten_stage(vecs, spec, cast=True):
    """Inverse of _flatten_stage given the stage's static spec. ``vecs``
    is the per-dtype vector dict; leaves are carved in flatten order with
    an independent running offset per dtype. ``cast=False`` keeps the
    vectors' own dtype (grad carving: f32 accumulators stay f32)."""
    treedef, metas = spec
    leaves, offs = [], {}
    for shape, dtype in metas:
        k = jnp.dtype(dtype).name
        n = int(_np.prod(shape)) if shape else 1
        off = offs.get(k, 0)
        leaf = vecs[k][off:off + n].reshape(shape)
        leaves.append(leaf.astype(dtype) if cast else leaf)
        offs[k] = off + n
    return jax.tree_util.tree_unflatten(treedef, leaves)


def flatten_stage_params(per_stage_params: Sequence[Any], mesh: Mesh,
                         pp_axis: str = "pp"):
    """Flatten+pad+stack P heterogeneous stage pytrees ->
    ({dtype_name: [P, Lmax_dt] NATIVE-dtype array sharded over pp},
    per-stage specs). Params stay in their own dtype in the stacked copy
    (bf16 costs bf16 bytes); a stage missing a dtype contributes a
    zero-padded row for that key."""
    pairs = [_flatten_stage(p) for p in per_stage_params]
    key_dtypes = {}
    for vecs, _ in pairs:
        for k, v in vecs.items():
            key_dtypes.setdefault(k, v.dtype)
    stacked = {}
    for k in sorted(key_dtypes):
        vs = [vecs.get(k, jnp.zeros((0,), key_dtypes[k]))
              for vecs, _ in pairs]
        L = max(v.shape[0] for v in vs)
        stacked[k] = jnp.stack([jnp.pad(v, (0, L - v.shape[0]))
                                for v in vs])
    try:
        sh = NamedSharding(mesh, P(pp_axis, None))
        stacked = {k: jax.device_put(a, sh) for k, a in stacked.items()}
    except Exception:
        pass
    return stacked, [s for _, s in pairs]


def unflatten_stage_grads(dvec, specs):
    """{dtype_name: [P, Lmax_dt]} grads -> list of per-stage pytrees
    (leaves keep the accumulators' dtype — f32 from the hand-written
    schedules — via ``unflatten_stage(cast=False)``)."""
    return [unflatten_stage({k: v[s] for k, v in dvec.items()}, spec,
                            cast=False)
            for s, spec in enumerate(specs)]


def _hetero_apply(stage_fns, specs, stage_id, vec_me, x_in):
    """lax.switch over per-stage branches; each branch statically unflattens
    its own spec. All branches must return the carry shape/dtype."""
    branches = [
        (lambda args, s=s: stage_fns[s](
            unflatten_stage(args[0], specs[s]), args[1]))
        for s in range(len(stage_fns))]
    return lax.switch(stage_id, branches, (vec_me, x_in))


def pipeline_hetero(stage_fns: Sequence[Callable], stacked_vec, specs,
                    microbatches, mesh: Mesh, pp_axis: str = "pp"):
    """GPipe wavefront over heterogeneous stages (AD gives the backward).

    stage_fns[s](stage_params, x) -> y, all sharing the carry shape;
    microbatches [M, ...] must already be carry-shaped (embed outside).
    Differentiable w.r.t. stacked_vec and microbatches.
    """
    num_stages = mesh.shape[pp_axis]
    assert len(stage_fns) == num_stages == len(specs)
    M = microbatches.shape[0]
    T = M + num_stages - 1
    manual = frozenset({pp_axis})

    def per_device(vec_local, mb_local):
        vec_me = jax.tree.map(lambda a: a[0], vec_local)
        stage_id = lax.axis_index(pp_axis)
        perm_fwd = [(i, (i + 1) % num_stages) for i in range(num_stages)]
        x0 = jnp.zeros_like(mb_local[0])

        def tick(carry, t):
            recv = carry
            feed = mb_local[jnp.minimum(t, M - 1)]
            x_in = jnp.where(stage_id == 0, feed, recv)
            y = _hetero_apply(stage_fns, specs, stage_id, vec_me, x_in)
            nxt = lax.ppermute(y, pp_axis, perm_fwd)
            return nxt, y

        _, ys = lax.scan(tick, x0, jnp.arange(T))
        outs = lax.dynamic_slice_in_dim(ys, num_stages - 1, M, axis=0)
        mask = (stage_id == num_stages - 1).astype(outs.dtype)
        return _psum_act(outs * mask, pp_axis, mesh)

    fn = jax.shard_map(
        per_device, mesh=mesh, axis_names=manual,
        in_specs=(P(pp_axis, None), P()), out_specs=P(), check_vma=False)
    return fn(stacked_vec, microbatches)


def pipeline_hetero_1f1b(stage_fns: Sequence[Callable], loss_fn: Callable,
                         stacked_vec, specs, head_params, microbatches,
                         labels, mesh: Mesh, pp_axis: str = "pp",
                         defer_dw: bool = False):
    """1F1B / zero-bubble over heterogeneous stages.

    Same schedule + memory contract as ``pipeline_1f1b`` (depth-bounded
    activation ring; defer_dw hoists dW out of the scan), with the
    stacked-pytree stage params replaced by the per-dtype flattened
    {dtype: [P, Lmax_dt]} vectors + lax.switch dispatch. Returns
    (mean_loss, d_stacked {dtype: [P, Lmax_dt] f32}, d_head_params,
    d_microbatches).
    """
    num_stages = mesh.shape[pp_axis]
    assert len(stage_fns) == num_stages == len(specs)
    M = microbatches.shape[0]
    T = M + 2 * num_stages - 2
    R = 2 * num_stages - 1
    manual = frozenset({pp_axis})
    inv_m = 1.0 / M

    def per_device(vec_local, head, mb_local, lab_local):
        vec_me = jax.tree.map(lambda a: a[0], vec_local)
        stage = lax.axis_index(pp_axis)
        last = num_stages - 1
        perm_f = [(i, (i + 1) % num_stages) for i in range(num_stages)]
        perm_b = [(i, (i - 1) % num_stages) for i in range(num_stages)]

        def apply(v, x):
            return _hetero_apply(stage_fns, specs, stage, v, x)

        zero_x = jnp.zeros_like(mb_local[0])
        ring0 = jnp.zeros((R,) + zero_x.shape, zero_x.dtype)
        dw0 = jax.tree.map(lambda v: jnp.zeros(v.shape, jnp.float32),
                           vec_me)
        dhead0 = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                              head)
        dx0 = jnp.zeros((M,) + zero_x.shape, jnp.float32)

        def tick(carry, t):
            (f_rc, b_rc, ring, dw, dhead, dx_out, loss_acc) = carry

            m_f = t - stage
            f_on = (m_f >= 0) & (m_f < M)
            feed = lax.dynamic_index_in_dim(
                mb_local, jnp.clip(m_f, 0, M - 1), 0, keepdims=False)
            x_in = jnp.where(stage == 0, feed, f_rc)
            y = apply(vec_me, x_in)
            slot_f = jnp.mod(t, R)
            ring = jnp.where(
                f_on,
                lax.dynamic_update_index_in_dim(ring, x_in, slot_f, 0),
                ring)

            lab = jax.tree.map(
                lambda l: lax.dynamic_index_in_dim(
                    l, jnp.clip(m_f, 0, M - 1), 0, keepdims=False),
                lab_local)
            lval, head_vjp = jax.vjp(lambda hp, yy: loss_fn(hp, yy, lab),
                                     head, y)
            dhead_c, dy_self = head_vjp(jnp.asarray(inv_m, jnp.float32))
            on_last = f_on & (stage == last)
            loss_acc = loss_acc + jnp.where(on_last, lval, 0.0)
            dhead = jax.tree.map(
                lambda acc, g: acc + jnp.where(on_last, g, 0.0),
                dhead, dhead_c)

            m_b = t - (2 * last - stage)
            b_on = (m_b >= 0) & (m_b < M)
            slot_b = jnp.mod(stage + jnp.clip(m_b, 0, M - 1), R)
            x_sv = lax.dynamic_index_in_dim(ring, slot_b, 0, keepdims=False)
            dy_in = jnp.where(stage == last, dy_self.astype(b_rc.dtype),
                              b_rc)
            _, stage_vjp = jax.vjp(apply, vec_me, x_sv)
            dv_c, dx_c = stage_vjp(dy_in)
            if not defer_dw:
                dw = jax.tree.map(
                    lambda acc, g: acc + jnp.where(
                        b_on, g.astype(jnp.float32), 0.0),
                    dw, dv_c)
            dx_out = jnp.where(
                b_on & (stage == 0),
                lax.dynamic_update_index_in_dim(
                    dx_out, dx_c.astype(jnp.float32),
                    jnp.clip(m_b, 0, M - 1), 0),
                dx_out)

            f_nx = lax.ppermute(y, pp_axis, perm_f)
            b_nx = lax.ppermute(dx_c.astype(b_rc.dtype), pp_axis, perm_b)
            stash = (x_sv, dy_in, b_on) if defer_dw else None
            return (f_nx, b_nx, ring, dw, dhead, dx_out, loss_acc), stash

        init = (zero_x, jnp.zeros_like(zero_x), ring0, dw0, dhead0,
                dx0, jnp.float32(0.0))
        (_, _, _, dw, dhead, dx_out, loss_acc), stash = lax.scan(
            tick, init, jnp.arange(T))

        if defer_dw:
            # scan-accumulated (not vmapped) for O(1) dW memory — see
            # pipeline_1f1b's defer_dw note
            xs, dys, mask = stash

            def acc_one(acc, xdm):
                x_sv, dy, on = xdm
                _, vjp = jax.vjp(apply, vec_me, x_sv)
                dv = vjp(dy)[0]
                return jax.tree.map(
                    lambda a, g: a + jnp.where(on, g.astype(jnp.float32),
                                               0.0), acc, dv), None
            dw, _ = lax.scan(acc_one, dw, (xs, dys, mask))

        lastf = (stage == last).astype(jnp.float32)
        loss_mean = lax.psum(loss_acc * lastf, pp_axis) * inv_m
        dhead = jax.tree.map(lambda g: lax.psum(g * lastf, pp_axis), dhead)
        dx_out = lax.psum(
            dx_out * (stage == 0).astype(jnp.float32), pp_axis)
        return loss_mean, jax.tree.map(lambda a: a[None], dw), dhead, \
            dx_out

    fn = jax.shard_map(
        per_device, mesh=mesh, axis_names=manual,
        in_specs=(P(pp_axis, None), P(), P(), P()),
        out_specs=(P(), P(pp_axis, None), P(), P()),
        check_vma=False)
    return fn(stacked_vec, head_params, microbatches, labels)


def flatten_stage_params_interleaved(per_stage_params: Sequence[Any],
                                     mesh: Mesh, num_chunks: int,
                                     pp_axis: str = "pp"):
    """Heterogeneous VPP stacking: V = P*num_chunks virtual-stage pytrees
    flatten to per-dtype vectors, pad to the longest, and stack
    {dtype: [P, num_chunks, Lmax_dt]} in the Megatron round-robin layout
    (virtual stage s = chunk s//P on device s%P). Returns (stacked, specs)
    with specs in CANONICAL virtual stage order (index s)."""
    P_ = mesh.shape[pp_axis]
    V = P_ * num_chunks
    assert len(per_stage_params) == V
    # reuse the canonical flatten/pad/stack, then fold [V, L] into the
    # round-robin [P, chunks, L] layout (canonical v -> [v % P, v // P])
    flat, specs = flatten_stage_params(per_stage_params, mesh, pp_axis)
    stacked = jax.tree.map(
        lambda a: jnp.transpose(
            a.reshape(num_chunks, P_, a.shape[-1]), (1, 0, 2)), flat)
    try:
        sh = NamedSharding(mesh, P(pp_axis, None, None))
        stacked = jax.tree.map(lambda a: jax.device_put(a, sh), stacked)
    except Exception:
        pass
    return stacked, specs


def pipeline_hetero_interleave(stage_fns: Sequence[Callable], stacked_vec,
                               specs, microbatches, mesh: Mesh,
                               num_chunks: int, pp_axis: str = "pp"):
    """Interleaved (VPP) wavefront over heterogeneous virtual stages.

    Same closed-form schedule as :func:`pipeline_interleave`; the virtual
    stage applied at a tick is ``v = c*P + d`` (a traced value), so the
    per-stage function/spec dispatch is a ``lax.switch`` over all V
    branches — branch v statically unflattens specs[v] from the chunk's
    padded vector. stage_fns are indexed by canonical virtual stage.
    """
    num_stages = mesh.shape[pp_axis]
    V = num_stages * num_chunks
    assert len(stage_fns) == V == len(specs)
    M = microbatches.shape[0]
    assert M % num_stages == 0, (
        f"interleaved schedule needs microbatches ({M}) % pp stages "
        f"({num_stages}) == 0")
    T = M * num_chunks + num_stages - 1
    manual = frozenset({pp_axis})

    def per_device(vec_local, mb_local):
        # {dtype: [num_chunks, Lmax_dt]}
        vec_me = jax.tree.map(lambda a: a[0], vec_local)
        stage = lax.axis_index(pp_axis)
        perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
        x0 = jnp.zeros_like(mb_local[0])
        out0 = jnp.zeros((M,) + mb_local.shape[1:], mb_local.dtype)

        def apply_virtual(c, x_in):
            v_id = c * num_stages + stage
            vec_c = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, c, 0,
                                                   keepdims=False),
                vec_me)
            branches = [
                (lambda args, s=s: stage_fns[s](
                    unflatten_stage(args[0], specs[s]), args[1]))
                for s in range(V)]
            return lax.switch(v_id, branches, (vec_c, x_in))

        def tick(carry, t):
            x_rc, out_buf = carry
            u = t - stage
            vP = V
            g = jnp.where(u >= 0, u // vP, 0)
            rem = jnp.clip(u - g * vP, 0, vP - 1)
            c = rem // num_stages
            m = jnp.clip(g * num_stages + rem % num_stages, 0, M - 1)
            active = (u >= 0) & (u < M * num_chunks)

            feed = lax.dynamic_index_in_dim(mb_local, m, 0, keepdims=False)
            x_in = jnp.where((stage == 0) & (c == 0), feed, x_rc)
            y = apply_virtual(c, x_in)
            y = jnp.where(active, y, x_in)

            emit = active & (stage == num_stages - 1) & \
                (c == num_chunks - 1)
            upd = lax.dynamic_update_index_in_dim(
                out_buf, y.astype(out_buf.dtype), m, 0)
            out_buf = jnp.where(emit, upd, out_buf)

            x_nx = lax.ppermute(y, pp_axis, perm)
            return (x_nx, out_buf), None

        (_, outs), _ = lax.scan(tick, (x0, out0), jnp.arange(T))
        mask = (stage == num_stages - 1).astype(outs.dtype)
        return _psum_act(outs * mask, pp_axis, mesh)

    fn = jax.shard_map(
        per_device, mesh=mesh, axis_names=manual,
        in_specs=(P(pp_axis, None, None), P()), out_specs=P(),
        check_vma=False)
    return fn(stacked_vec, microbatches)


def pipeline_hetero_interleave_1f1b(stage_fns: Sequence[Callable],
                                    loss_fn: Callable, stacked_vec, specs,
                                    head_params, microbatches, labels,
                                    mesh: Mesh, num_chunks: int,
                                    pp_axis: str = "pp",
                                    defer_dw: bool = False):
    """Heterogeneous VPP with the hand-written depth-bounded backward —
    ``pipeline_interleave_1f1b``'s schedule (same shared
    ``_interleave_1f1b_core``) over the per-dtype flattened carrier +
    lax.switch virtual-stage dispatch of the hetero tier.

    stacked_vec: {dtype: [P, num_chunks, Lmax_dt]} (round-robin layout
    from ``flatten_stage_params_interleaved``); specs in canonical
    virtual-stage order. Returns (mean_loss, d_stacked {dtype:
    [P, num_chunks, Lmax_dt] f32}, d_head_params, d_microbatches).
    Requires M % P == 0.
    """
    num_stages = mesh.shape[pp_axis]
    V = num_stages * num_chunks
    assert len(stage_fns) == V == len(specs)

    def apply_chunk(vme, c, x, d):
        vec_c = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, c, 0, keepdims=False),
            vme)
        v_id = c * num_stages + d
        branches = [
            (lambda args, s=s: stage_fns[s](
                unflatten_stage(args[0], specs[s]), args[1]))
            for s in range(V)]
        return lax.switch(v_id, branches, (vec_c, x))

    return _interleave_1f1b_core(
        apply_chunk, stacked_vec, head_params, microbatches, labels,
        mesh, num_chunks, pp_axis, loss_fn, P(pp_axis, None, None),
        defer_dw=defer_dw)
