"""Predictor implementation (reference: paddle/fluid/inference/api/
analysis_predictor.h AnalysisPredictor; python surface
python/paddle/inference/wrapper.py)."""
from __future__ import annotations

import enum
import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..observability import hooks as _obs
from ..observability.spans import SpanTotals
from ..serving.resilience import fault_point as _fault_point


class PlaceType(enum.Enum):
    CPU = 0
    GPU = 1
    TPU = 2


class PrecisionType(enum.Enum):
    Float32 = 0
    Half = 1
    Bfloat16 = 2
    Int8 = 3


def get_version() -> str:
    import paddle_tpu
    return paddle_tpu.__version__


class Config:
    """reference: AnalysisConfig (paddle/fluid/inference/api/
    analysis_config.cc). TensorRT/OneDNN toggles are accepted for parity
    and map to XLA (always-on compilation)."""

    def __init__(self, model_path: Optional[str] = None,
                 params_path: Optional[str] = None):
        self._model_path = model_path
        self._params_path = params_path
        self._device = "tpu" if any(
            d.platform == "tpu" for d in jax.devices()) else "cpu"
        self._precision = PrecisionType.Float32
        self._memory_pool_mb = 0
        self._enable_profile = False
        self._optim = True
        self._mesh = None
        self._input_pspec = None
        self._param_spec_fn = None

    # --- multi-chip serving (TPU-native analog of the reference's
    # multi-device inference paths: TRT multi-stream, fleet inference
    # helper) — the compiled program runs SPMD over a device mesh ---
    def enable_mesh(self, mesh, input_spec=None, param_spec_fn=None):
        """Serve over ``mesh``. ``input_spec``: a PartitionSpec (or one
        per input) for the data inputs — default shards dim 0 over the
        mesh's first axis (data-parallel serving). ``param_spec_fn(name,
        array) -> PartitionSpec | None`` places parameters (None =
        replicate); supply Column/Row splits for tensor-parallel serving.
        """
        self._mesh = mesh
        self._input_pspec = input_spec
        self._param_spec_fn = param_spec_fn

    def mesh(self):
        return self._mesh

    # --- model location ---
    def set_model(self, model_path, params_path=None):
        self._model_path = model_path
        self._params_path = params_path

    def model_dir(self):
        return self._model_path

    def prog_file(self):
        return self._model_path

    def params_file(self):
        return self._params_path

    # --- device selection (GPU API parity maps to the TPU chip) ---
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0,
                       precision=PrecisionType.Float32):
        self._memory_pool_mb = memory_pool_init_size_mb
        self._precision = precision

    def disable_gpu(self):
        self._device = "cpu"

    def use_gpu(self):
        return self._device != "cpu"

    def enable_xpu(self, *a, **k):
        pass

    def enable_custom_device(self, device_type, device_id=0):
        self._device = device_type

    # --- optimization toggles ---
    # XLA subsumes the reference's IR/memory/TensorRT/OneDNN pipeline:
    # every toggle is accepted for parity but has no engine to configure.
    # Toggles that a user might rely on semantically (turning optimization
    # OFF, routing to TensorRT) warn ONCE instead of silently no-opping.
    @staticmethod
    def _inert(what, detail):
        import warnings
        warnings.warn(
            f"inference.Config.{what}: accepted for API parity but inert "
            f"on TPU — {detail}", stacklevel=3)

    def switch_ir_optim(self, flag=True):
        if not flag:
            self._inert("switch_ir_optim(False)",
                        "XLA always compiles/optimizes; there is no "
                        "unoptimized executor to fall back to")
        self._optim = flag

    def enable_tensorrt_engine(self, *a, **k):
        self._inert("enable_tensorrt_engine",
                    "the compiled engine is XLA; TensorRT is a GPU "
                    "deployment path")

    def enable_mkldnn(self):
        self._inert("enable_mkldnn", "OneDNN is a CPU kernel library; "
                    "XLA:CPU compiles the fallback path")

    def enable_memory_optim(self, flag=True):
        if flag:
            return  # XLA's buffer assignment already reuses/donates
        self._inert("enable_memory_optim(False)",
                    "XLA buffer reuse cannot be disabled")

    def switch_use_feed_fetch_ops(self, flag):
        pass  # feed/fetch are jit arguments; nothing to switch

    def switch_specify_input_names(self, flag=True):
        pass  # inputs are always named (get_input_names order)

    def enable_profile(self):
        self._enable_profile = True

    def summary(self) -> str:
        return (f"Config(model={self._model_path}, device={self._device}, "
                f"precision={self._precision.name})")


class Tensor:
    """Input/output handle (reference: ZeroCopyTensor,
    paddle/fluid/inference/api/details/zero_copy_tensor.cc)."""

    def __init__(self, name: str, owner: "Predictor"):
        self.name = name
        self._owner = owner
        self._value: Optional[jax.Array] = None

    def reshape(self, shape):
        pass  # shapes come from the bound array

    def copy_from_cpu(self, arr: np.ndarray):
        self._value = jnp.asarray(arr)

    def copy_to_cpu(self) -> np.ndarray:
        return np.asarray(self._value)

    def share_external_data(self, arr):
        self._value = arr if isinstance(arr, jax.Array) else jnp.asarray(arr)

    def shape(self):
        return list(self._value.shape) if self._value is not None else []

    def type(self):
        return self._value.dtype if self._value is not None else None


class Predictor:
    """reference: AnalysisPredictor. Loads a jit.save artifact (a
    TranslatedLayer) or wraps a live Layer/function."""

    def __init__(self, config: Config, layer=None):
        self._config = config
        if layer is None:
            from ..jit.save_load import load as jit_load
            layer = jit_load(config.model_dir())
        self._layer = layer
        self._input_names: List[str] = getattr(
            layer, "input_names", None) or ["x"]
        self._inputs: Dict[str, Tensor] = {
            n: Tensor(n, self) for n in self._input_names}
        self._outputs: Dict[str, Tensor] = {}
        self._jitted = None
        # snapshot the mesh config: enable_mesh must be called BEFORE
        # create_predictor (a later call changing the live Config would
        # otherwise shard inputs but silently skip param placement)
        self._mesh = config._mesh
        self._input_pspec = config._input_pspec
        if self._mesh is not None and hasattr(self._layer, "state_dict"):
            # plain-function layers have no params to place; the input
            # sharding below still applies
            self._place_params(self._mesh, config._param_spec_fn)

    def _place_params(self, mesh, spec_fn):
        """Install mesh placements on the layer's parameters in place
        (replicated unless spec_fn says otherwise)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        for name, t in self._layer.state_dict().items():
            # state_dict entries are always framework Tensors (Layer
            # wraps buffers; TranslatedLayer._state holds Tensors)
            spec = None
            if spec_fn is not None:
                spec = spec_fn(name, t._value)
            sh = NamedSharding(mesh, spec if spec is not None else P())
            t._value = jax.device_put(t._value, sh)

    def get_input_names(self) -> List[str]:
        return list(self._input_names)

    def get_input_handle(self, name: str) -> Tensor:
        return self._inputs[name]

    def _compiled(self):
        """One compiled XLA program per input-shape set (reference: the
        analysis passes + engine of AnalysisPredictor::Run — here jit
        compile-and-cache does both)."""
        if self._jitted is None:
            import jax
            from .._core.tensor import Tensor as FrameworkTensor
            layer = self._layer

            def f(*raw):
                out = layer(*[FrameworkTensor(r, _internal=True)
                              for r in raw])
                outs = out if isinstance(out, (list, tuple)) else [out]
                return tuple(o._value if isinstance(o, FrameworkTensor)
                             else o for o in outs)

            mesh = self._mesh
            if mesh is None:
                self._jitted = jax.jit(f)
            else:
                from jax.sharding import NamedSharding, PartitionSpec as P
                spec = self._input_pspec
                if spec is None:
                    spec = P(mesh.axis_names[0])   # batch over axis 0
                specs = (list(spec) if isinstance(spec, (list, tuple))
                         and not isinstance(spec, P)
                         else [spec] * len(self._input_names))
                shards = tuple(NamedSharding(mesh, s) for s in specs)
                self._jitted = jax.jit(f, in_shardings=shards)
        return self._jitted

    def run(self, inputs: Optional[List[np.ndarray]] = None):
        """reference: AnalysisPredictor::Run / ZeroCopyRun.

        Telemetry (paddle_tpu.observability): per-request latency
        histogram + request/sample counters, plus a ``Predictor.run``
        span when the profiler is recording — zero-cost when neither
        sink is active."""
        if not _obs.active():
            return self._run_impl(inputs)
        t0 = time.perf_counter_ns()
        out = self._run_impl(inputs)
        first = next(iter(self._inputs.values()), None)
        batch = (first._value.shape[0]
                 if first is not None and first._value is not None
                 and getattr(first._value, "ndim", 0) else 0)
        _obs.predictor_run(t0, int(batch))
        return out

    def _run_impl(self, inputs: Optional[List[np.ndarray]] = None):
        from .._core.tensor import Tensor as FrameworkTensor
        if inputs is not None:
            for n, arr in zip(self._input_names, inputs):
                self._inputs[n].copy_from_cpu(np.asarray(arr))
        raw = [self._inputs[n]._value for n in self._input_names]
        out = None
        jit_failed = False
        if self._jitted is not False:
            try:
                out = self._compiled()(*raw)
            except Exception:
                if self._mesh is not None:
                    # the user asked for SPMD serving: a sharding
                    # misconfiguration (uneven batch, wrong spec count)
                    # must surface, not silently degrade to one chip
                    raise
                jit_failed = True
                self._jitted = None  # decide after the eager attempt
        if out is None:
            args = [FrameworkTensor(v, _internal=True) for v in raw]
            # bad inputs re-raise here for the user to fix — that's an
            # input error, not a non-jittable forward
            out = self._layer(*args)
            if jit_failed:
                # eager worked where jit didn't: the forward itself is
                # non-jittable; latch eager so we don't re-trace per run
                self._jitted = False
        outs = out if isinstance(out, (list, tuple)) else [out]
        self._outputs = {}
        results = []
        for i, o in enumerate(outs):
            t = Tensor(f"out_{i}", self)
            val = o._value if isinstance(o, FrameworkTensor) else jnp.asarray(o)
            t.share_external_data(val)
            self._outputs[t.name] = t
            results.append(np.asarray(val))
        if inputs is not None:
            return results
        return True

    def get_output_names(self) -> List[str]:
        return list(self._outputs.keys())

    def get_output_handle(self, name: str) -> Tensor:
        return self._outputs[name]

    def clear_intermediate_tensor(self):
        pass

    def try_shrink_memory(self):
        pass


def create_predictor(config: Config, layer=None) -> Predictor:
    """reference: paddle_infer::CreatePredictor."""
    return Predictor(config, layer=layer)


# ---------------- continuous-batching decode engine ----------------

def _named_jit(f, name: str, **jit_kw):
    """``jax.jit(f)`` under a name that says what the program is: it
    shows as ``jit_<name>`` on the ``XLA Modules`` line of a trace."""
    f.__name__ = f.__qualname__ = name
    return jax.jit(f, **jit_kw)


class InFlightStep:
    """One dispatched-but-uncommitted decode/verify program.

    Every engine step has a DISPATCH half (launch the jitted program —
    JAX dispatch is asynchronous, so this returns while the device
    works — and advance every piece of host state that no token's
    VALUE decides: lengths, token counts, the sliding pool's pages) and
    a COMMIT half (the device→host read and what hangs on the values:
    ``req.tokens``, ``eos``, retirement). The handle carries what the
    commit needs: the device output array, the mask, and a SNAPSHOT of
    the per-slot request ids AND seat generations at dispatch time —
    commit only applies a slot's result when the slot still holds the
    same SEATING of the same request. A row whose earlier token turned
    out to be ``eos`` was retired before this step's commit and its
    result is dropped here; so is that of a slot that changed hands
    (even when the re-admission seated the SAME request back into its
    own slot — its pages and lengths were reset, so the in-flight
    token belongs to freed pages; the victim re-decodes the dropped
    token on resume, greedy-identically, so no stream ever forks)."""
    __slots__ = ("kind", "mask", "rids", "seats", "out", "drafts",
                 "dlen", "t0", "t0f", "raw", "ttr", "qs", "rows")

    def __init__(self, kind, mask, rids, seats, out, drafts=None,
                 dlen=None, t0=0, t0f=0, raw=None, ttr=0, qs=None,
                 rows=None):
        self.kind = kind                # "decode" | "spec" | "tree"
        self.mask = mask
        self.rids = rids                # per-slot rid snapshot at dispatch
        self.seats = seats              # per-slot seating generation
        self.out = out                  # device array: nxt (B,) / (B, T)
        self.drafts = drafts
        self.dlen = dlen
        self.t0 = t0
        self.t0f = t0f
        self.raw = raw                  # UNCONSTRAINED argmax (B,) when
        #                                 the engine masks sampling — the
        #                                 violation-avoided counter input
        self.ttr = ttr                  # trace-clock anchor (ISSUE 16)
        self.qs = qs                    # slot -> (j, V) draft-model q
        #                                 distributions (ISSUE 20): the
        #                                 real proposal law the rejection
        #                                 sampler's min(1, p/q) needs
        self.rows = rows                # tree verify's un-placed per-node
        #                                 KV (ISSUE 20) — scattered by
        #                                 paged_tree_commit at commit


class GenerationRequest:
    """One in-flight generation request tracked by the engine.

    ``finish_reason`` is STRUCTURED (the string values of
    :class:`paddle_tpu.serving.policy.FinishReason`): ``eos`` /
    ``max_len`` on completion, ``deadline_exceeded`` when a scheduler
    cancels a queued request, and the transient ``preempted`` while the
    request sits evicted awaiting resume (``done`` stays False and the
    reason clears when its replay prefill completes).

    ``priority`` (lower = more important), ``deadline_at`` /
    ``submitted_at`` / ``enqueued_at`` (scheduler-clock seconds; the
    last resets on every requeue) and ``preemptions`` are
    scheduler-facing metadata; the engine's own FIFO path ignores them.
    """
    __slots__ = ("rid", "prompt", "max_new_tokens", "eos_token_id",
                 "tokens", "done", "finish_reason", "slot",
                 "priority", "deadline_at", "submitted_at",
                 "enqueued_at", "preemptions", "swapped",
                 "adapter_id", "constraint", "trace")

    def __init__(self, rid, prompt, max_new_tokens, eos_token_id):
        self.rid = rid
        self.prompt = np.asarray(prompt, np.int32).reshape(1, -1)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.tokens: List[int] = []      # generated tokens (no prompt)
        self.done = False
        self.finish_reason: Optional[str] = None
        self.slot: Optional[int] = None
        self.priority = 1                # serving.policy.Priority.NORMAL
        self.deadline_at: Optional[float] = None
        self.submitted_at: Optional[float] = None
        self.enqueued_at: Optional[float] = None   # latest (re)queue time
        self.preemptions = 0
        self.swapped = False    # KV currently host-resident (ISSUE 10)
        self.adapter_id = 0     # 0 = the base model (ISSUE 14)
        self.constraint = None  # live ConstraintState or None (ISSUE 14)
        self.trace = None       # RequestTrace riding the handle (ISSUE 16)

    def resume_sequence(self) -> np.ndarray:
        """The tokens whose KV must be in the pool before this request
        can (re)enter decode: the prompt plus — after a preemption —
        every generated token EXCEPT the last (decode feeds the last
        sampled token back through the step program, which writes its
        KV then; replaying ``tokens[:-1]`` through the continuation
        prefill reproduces the evicted cache bit-for-bit)."""
        if not self.tokens:
            return self.prompt[0]
        return np.concatenate(
            [self.prompt[0], np.asarray(self.tokens[:-1], np.int32)])

    @property
    def output(self) -> np.ndarray:
        """prompt + generated tokens, one row."""
        return np.concatenate(
            [self.prompt[0], np.asarray(self.tokens, np.int32)])


class ContinuousBatchingEngine:
    """Continuous-batching decode over a paged KV cache (reference: the
    serving stack around block_multi_head_attention; design: vLLM-style
    continuous batching on TPU-static shapes).

    ``max_batch`` decode slots run ONE jitted single-token program per
    step (static shapes throughout); new prompts are admitted into free
    slots MID-DECODE, finished rows retire immediately and their pages
    recycle — so short requests stop pad-burning the long ones' HBM and
    decode throughput at mixed request lengths rises with occupancy.

    Prefill is CHUNKED: an admission's prompt advances by at most one
    fixed-size chunk (``prefill_chunk`` tokens, page-rounded; default
    unbounded = one chunk) per engine step, interleaved with the decode
    program — so a 4k-token admission adds one chunk's latency per step
    to the in-flight decodes instead of stalling them for a monolithic
    prefill. And prefill is PREFIX-CACHED: the paged cache's hash-trie
    maps previously prefilled prompt pages (shared system prompts,
    few-shot headers) straight into the new request's block table —
    refcounted, copy-on-write on the first partial page — so the shared
    span costs neither prefill FLOPs nor fresh KV HBM.

    Admission control is page-pool back-pressure: a request is admitted
    only when the allocator can cover ``prompt + max_new_tokens``
    (prefix-cache-held pages are evicted LRU-first under pressure); a
    :class:`~paddle_tpu.serving.PoolExhausted` defers it until running
    requests retire (OOM-free by construction). The engine's own
    :meth:`step` admits FIFO; the SLO-aware control plane
    (:class:`~paddle_tpu.serving.ServingScheduler`) composes the same
    lifecycle pieces — :meth:`admit_request`, :meth:`preempt_request`
    (pages evicted back to the pool, token-identical resume through the
    continuation-prefill program), :meth:`cancel_request`,
    :meth:`prefill_step`, :meth:`decode_step` — under priority classes,
    deadlines and a per-step token budget. Requests finish with
    STRUCTURED reasons (``eos`` / ``max_len`` / ``deadline_exceeded``,
    transient ``preempted`` — serving.policy.FinishReason).

    Sampling: greedy at ``temperature == 0`` (token-identical to the
    dense :func:`~paddle_tpu.models.generate.generate` — chunking and
    prefix sharing are bit-exact, not approximate), else temperature
    sampling with a per-step PRNG fold.

    Speculative decoding (``spec_k > 0``, greedy only): each step a
    host-side n-gram proposer (:class:`~paddle_tpu.serving.Speculator`,
    prompt-lookup over the row's own ``prompt + generated`` history —
    no draft model, no extra weights) drafts up to ``spec_k`` tokens
    per row, ONE batched verify forward
    (:func:`~paddle_tpu.models.generate.paged_verify_forward`) scores
    every speculating row's drafts against its paged KV, and the
    longest greedily-accepted prefix plus the bonus token commit — so
    a step emits up to ``spec_k + 1`` tokens per row for barely more
    HBM traffic than one. A per-row acceptance-rate EMA adapts the
    draft length and falls back to plain decode when the history does
    not repeat, and greedy output stays TOKEN-IDENTICAL to plain paged
    decode at fp and int8-KV (gated in tests/test_spec_decode.py).

    Tensor-parallel serving (``mesh=`` — ISSUE 7): pass a 1-D
    :func:`~paddle_tpu.distributed.mesh.serving_mesh` and the engine
    shards weights by regex partition rules
    (:data:`~paddle_tpu.models.llama.SERVING_TP_RULES` — column splits
    per layer matrix, vocab-sharded lm_head) and every page pool on the
    kv-head axis, lowering the decode/chunk/verify programs through
    ``shard_map``. Page IDS are identical on every shard, so the whole
    host control plane — queues, slots, allocator, refcounts, prefix
    trie, preemption — runs unchanged; per-shard HBM drops to ``1/tp``
    of the weight+pool bytes (the decode bottleneck), and the sharded
    programs stay BIT-identical to single-chip paged decode at fp and
    int8-KV (exact all-gather concats, no psum —
    tests/test_tp_serving.py). GQA configs with ``num_kv_heads < tp``
    replicate one kv head per shard; invalid head/tp combinations raise
    loudly at construction.

    Telemetry (paddle_tpu.observability): admission/eviction counters,
    prefix hit/miss token counters, per-chunk prefill latency histogram,
    per-step batch-occupancy histogram, block-pool utilization gauge —
    plus, under a mesh, the ``serving_tp_*`` family (traced all-gather
    calls/bytes, per-shard pool gauge, probed logits-collective latency
    histogram) — zero-cost when metrics are disabled.
    """

    def __init__(self, params, cfg, *, max_batch: int = 4,
                 page_size: int = 16, max_len: Optional[int] = None,
                 num_pages: Optional[int] = None, kv_cache_dtype=None,
                 temperature: float = 0.0, eos_token_id=None,
                 use_kernel: Optional[bool] = None,
                 key: Optional[jax.Array] = None,
                 prefill_chunk: Optional[int] = None,
                 enable_prefix_cache: bool = True,
                 spec_k: int = 0, spec_ngram: int = 3,
                 speculator=None, draft_layers: Optional[int] = None,
                 draft_pages: Optional[int] = None,
                 spec_tree: Optional[Tuple[int, int]] = None,
                 mesh=None,
                 host_tier: bool = False,
                 host_tier_kw: Optional[Dict] = None,
                 weight_bits: Optional[int] = None,
                 fused: Optional[bool] = None,
                 overlap: bool = False,
                 adapters=None,
                 constraints: bool = False):
        from ..serving import PagedKVCache
        self.cfg = cfg
        self.temperature = float(temperature)
        self.eos_token_id = eos_token_id
        self.use_kernel = use_kernel
        # overlap=True sends the host tier's swap-out DMAs NON-BLOCKING
        # (issued at preemption, fenced at the next commit) so the
        # device→host copy rides under the decode steps that follow.
        # How deep the decode pipeline runs is NOT this knob's to say:
        # the dispatch/commit split is always there (decode_step ==
        # dispatch immediately followed by commit, the bit-identity
        # reference) and :meth:`pipeline_depth` decides from what the
        # engine holds.
        self.overlap = bool(overlap)
        # --- low-bit decode tiers (ISSUE 11): weight_bits quantizes the
        # weights at construction (8 = per-channel int8, 4 = per-group
        # int4 — models/generate.quantize_weights); every serving
        # program dequants on the fly inside its matmul reads, and the
        # quant scales shard under the same regex partition rules as
        # their matrices. weight_bits=8 + kv_cache_dtype="int8" is the
        # w8/kv8 tier (weight AND cache HBM halved). Pre-quantized
        # param trees pass through untouched (weight_bits=None).
        self.weight_bits = weight_bits
        if weight_bits is not None:
            from ..models.generate import quantize_weights
            params = quantize_weights(params, cfg, bits=weight_bits)
        # --- fused serving kernels (ISSUE 11): route the decode /
        # chunked-prefill / spec-verify programs through the fused
        # Pallas kernels (ops/pallas/serving_fused.py — in-VMEM q-RoPE
        # + KV dequant for decode, flash chunk attention for
        # prefill/verify). Default OFF, same contract as
        # LlamaConfig.fused_kernels: never timed on a chip; what
        # decides is a cell of BENCHMARK.json on each side, run by the
        # driver (ROADMAP D3), after which the loser and this flag go;
        # off-TPU the fused path is the bit-identical reference, and
        # the kernels themselves are gated token-identical per tier
        # (tests/test_lowbit_decode.py) + Mosaic-lowered by
        # aot_validate --config serving-lowbit.
        self.fused = bool(fused)
        # --- tensor-parallel serving (ISSUE 7): a 1-D mesh shards the
        # weights (llama.SERVING_TP_RULES: column splits + vocab-sharded
        # lm_head) and every page pool on the kv-head axis; the jitted
        # step programs below lower through shard_map. ALL host logic —
        # queues, slots, block tables, allocator, trie — is unchanged:
        # page ids are the same on every shard.
        # --- 2-D serving mesh (ISSUE 17): a ("tp", "dp") mesh
        # additionally splits the BATCH axis of the decode and verify
        # programs over dp — each dp shard computes max_batch/dp rows
        # against its own (dp-replicated, tp-head-sharded) page pool
        # replica, and the per-layer KV rows + scatter indices
        # all-gather across dp before the pool write so every replica
        # receives the full batch's writes in single-chip row order.
        # Chunked prefill stays dp-replicated (one row per program).
        # MoE configs shard their expert stacks over dp with per-token
        # all-to-all dispatch (llama.validate_serving_mesh accepts what
        # validate_serving_tp rejects). Host logic is still unchanged.
        # --- state-space layers (LlamaConfig.hybrid): the model asks for
        # a recurrent state a row beside its pages (cfg.cache_layers());
        # the cache keeps it by slot and the decode and chunk programs
        # take it with the pools. What walks pages and cannot yet walk
        # state refuses such a config here, by name (the prefix cache, the
        # fabric's handoff, drain/restore and defrag refuse in
        # PagedKVCache); so does what would shard or extend its programs.
        self._state_layers = cfg.cache_layers().get("state", 0)
        self._state_kind = cfg.state_kind
        if self._state_layers:
            for on, what in ((host_tier, "host_tier"),
                             (draft_layers, "draft_layers"),
                             (spec_k or spec_tree, "speculative decoding"
                              " (spec_k / spec_tree)"),
                             (mesh is not None, "mesh"),
                             (adapters is not None, "adapters"),
                             (fused, "fused"),
                             (enable_prefix_cache, "enable_prefix_cache")):
                if on:
                    raise ValueError(
                        f"ContinuousBatchingEngine: {what} is not "
                        f"supported on a config with state-space layers "
                        f"(a recurrent state a row beside its pages)")
        # --- latent attention (LlamaConfig.latent): the model asks for a
        # pool of latents with no head axis; the decode and chunk programs
        # take it as they take any pool, donated and whole in the layer
        # scan's carry. What would shard it, or verify or draft through
        # the programs that keep keys and values by head, refuses such a
        # config here, by name.
        self._latent_layers = cfg.cache_layers().get("latent", 0)
        if self._latent_layers:
            for on, what in ((host_tier, "host_tier"),
                             (draft_layers, "draft_layers"),
                             (spec_k or spec_tree, "speculative decoding"
                              " (spec_k / spec_tree)"),
                             (mesh is not None, "mesh"),
                             (adapters is not None, "adapters"),
                             (fused, "fused")):
                if on:
                    raise ValueError(
                        f"ContinuousBatchingEngine: {what} is not "
                        f"supported on a config with latent attention (a "
                        f"pool of latents with no head axis)")
        # --- a K/V decoder with more than the plain layer's parts (a dense
        # prologue, an output gate, QK-norm, NoPE layers, sandwich norms,
        # an input scale: cfg.kv_parts()) is served by one chip's unfused
        # programs; what would shard them, fuse them, add adapter terms
        # or draft from a truncation of its layer stack refuses here
        parts = cfg.kv_parts()
        if parts:
            for on, what in ((mesh is not None, "mesh"),
                             (adapters is not None, "adapters"),
                             (fused, "fused"),
                             (draft_layers, "draft_layers")):
                if on:
                    raise ValueError(
                        f"ContinuousBatchingEngine: {what} is not "
                        f"supported on a config with {', '.join(parts)}")
        self.mesh = mesh
        self._tp = None
        self._tp_axis = None
        self._dp_axis = None
        self.dp = 1
        self._param_specs = None
        self._tp_probe = None
        if mesh is not None:
            from ..models import llama as _llama
            names = mesh.axis_names
            if len(names) > 2 or (len(names) == 2 and "tp" not in names):
                raise ValueError(
                    f"ContinuousBatchingEngine: the serving mesh must "
                    f"be 1-D (a tp axis) or 2-D (tp, dp), got axes "
                    f"{names}")
            self._tp_axis = "tp" if "tp" in names else names[0]
            self._tp = int(mesh.shape[self._tp_axis])
            if len(names) == 2:
                self._dp_axis = next(a for a in names
                                     if a != self._tp_axis)
                self.dp = int(mesh.shape[self._dp_axis])
                if max_batch % self.dp:
                    raise ValueError(
                        f"ContinuousBatchingEngine: max_batch="
                        f"{max_batch} is not divisible by dp={self.dp}"
                        f" — the decode batch splits into equal "
                        f"per-dp-shard row blocks")
            # validates num_heads/num_kv_heads divisibility loudly and
            # takes the KV-replication path when num_kv_heads < tp
            # (validate_serving_mesh also checks the MoE expert/dp and
            # expert-matrix/tp splits on 2-D meshes)
            params, self._param_specs = _llama.shard_serving_params(
                params, cfg, mesh, axis=self._tp_axis)
        self.params = params
        # --- hierarchical KV (ISSUE 10): host_tier=True swaps the
        # cache for a TieredKVCache — preemption victims swap out to
        # host RAM and resume by swap-in scatter instead of the replay
        # prefill, evicted prefix-trie chains demote/promote, and
        # registered prompt chains persist to the standing store
        # (host_tier_kw: host_capacity_pages / prefix_store_dir /
        # store — a shared HostPageStore across engines).
        cache_kw = dict(page_size=page_size, num_pages=num_pages,
                        kv_dtype=kv_cache_dtype,
                        enable_prefix_cache=enable_prefix_cache,
                        mesh=mesh, prefill_chunk=prefill_chunk)
        # --- sliding-window layers (LlamaConfig.layer_pattern): the
        # cache keeps a second pool and block table for them and the
        # decode and chunk programs take both. What walks ONE pool's
        # pages a row refuses such a config here, by name: the host
        # tier's swap and demotion, the verify programs of every
        # speculation mode (the fabric's handoff and drain/restore
        # refuse at their call, in PagedKVCache).
        if "sliding" in cfg.period:
            for on, what in ((host_tier, "host_tier"),
                             (spec_k or spec_tree, "speculative decoding"
                              " (spec_k / spec_tree)"),
                             (draft_layers, "draft_layers")):
                if on:
                    raise ValueError(
                        f"ContinuousBatchingEngine: {what} is not "
                        f"supported on a config with sliding-window "
                        f"layers (two pools and block tables a row)")
        if host_tier:
            from ..serving.host_tier import TieredKVCache
            self.cache = TieredKVCache(
                cfg, max_batch, max_len or cfg.max_seq_len,
                **cache_kw, **(host_tier_kw or {}))
        else:
            self.cache = PagedKVCache(
                cfg, max_batch, max_len or cfg.max_seq_len, **cache_kw)
        if prefill_chunk is not None:
            # page-rounded so chunk boundaries stay page-aligned (the
            # chunk program's static ctx_cap) and >= one page
            prefill_chunk = self.cache.pages_for(
                max(1, int(prefill_chunk))) * self.cache.page_size
        self.prefill_chunk = prefill_chunk
        self.max_batch = max_batch
        self._key = key if key is not None else jax.random.key(0)
        # --- multi-tenant adapter plane (ISSUE 14): a device-resident
        # AdapterPool of packed per-layer LoRA factors, paged like KV —
        # per-request adapter_id pins a slot at admission (refcounted;
        # LRU reclaim demotes cold adapters to the host tier) and the
        # per-row slot ids gather into every forward. None compiles
        # the adapter term out of every program (the plain engine).
        # A dict builds the pool in place (slots/rank/registry/store —
        # serving.adapters.AdapterPool kwargs); a pre-built pool must
        # match this engine's mesh (the B factors column-shard with
        # the weights).
        from ..serving.adapters import AdapterPool
        if isinstance(adapters, dict):
            adapters = AdapterPool(cfg, mesh=mesh, **adapters)
        if adapters is not None and adapters.mesh is not mesh:
            raise ValueError(
                "ContinuousBatchingEngine: the AdapterPool's mesh does "
                "not match the engine's — build the pool with the same "
                "serving mesh (its B factors shard with the weights)")
        self.adapters = adapters
        self._aslot = np.zeros((max_batch,), np.int32)
        # --- constrained decoding (ISSUE 14): constraints=True grows
        # the decode program a per-row (B, vocab) allowed-token mask
        # (logits[~mask] = -inf before the argmax/categorical) plus a
        # violation-avoided output; per-request DFA state advances at
        # commit. Default OFF so the plain engine's programs (and the
        # bit-identity gates) are untouched.
        self.constraints = bool(constraints)
        # the (B, vocab) mask is real memory at serving vocab sizes —
        # only constrained engines pay for it
        self._cmask = (np.ones((max_batch, cfg.vocab_size), bool)
                       if self.constraints else None)
        # device copy of the mask, re-uploaded only after a host-side
        # mutation (commit refresh, seat/clear) — steady-state traffic
        # with no constrained rows pays zero per-step transfer
        self._cmask_dev = None
        self._cmask_dirty = True
        self._queue: List[GenerationRequest] = []
        self._slots: List[Optional[GenerationRequest]] = [None] * max_batch
        self._last = np.zeros((max_batch,), np.int32)
        # per-slot request state MIRRORED into flat numpy arrays so the
        # decode commit is vectorized host bookkeeping (ISSUE 12): one
        # fancy-indexed update per step instead of a per-row Python
        # loop of scalar conversions. _install_slot/_clear_slot are the
        # only writers; -1 rid == empty slot.
        self._rids = np.full((max_batch,), -1, np.int64)
        # seating GENERATION per slot, bumped on every _install_slot:
        # the commit guard compares it so a request preempted and
        # re-seated (even into its own slot, rid unchanged) between
        # dispatch and commit never receives the stale seating's token
        self._seat = np.zeros((max_batch,), np.int64)
        # tokens a row has been LAUNCHED for (>= len(req.tokens), which
        # counts those read): the max_len finish is decided from this
        # at dispatch, so no row is launched past its count
        self._ntok = np.zeros((max_batch,), np.int64)
        self._maxnew = np.zeros((max_batch,), np.int64)
        self._eos = np.full((max_batch,), -1, np.int64)
        # dispatched-but-uncommitted work in LAUNCH order, as
        # ``(launch_seq, handle)``: prefill chunk handles (dicts) and
        # decode/verify programs (InFlightStep) — commit_inflight takes
        # them from the front, so a caller can leave the newest step
        # running
        self._inflight: List[tuple] = []
        self.launch_seq = 0
        # every row's newest token AS THE DEVICE HOLDS IT: the decode
        # program's own output (inactive rows carried through), with a
        # final chunk's first token written into its row. The next
        # decode program takes a row's token from here wherever the
        # host has not read it yet (_rows_on_device)
        self._tok_dev = None
        self._put_row_fn = None
        # span totals and counters of this engine (and of the scheduler
        # that owns it); they come out in stats()
        self.spans = SpanTotals()
        for name in ("decode_launches_total",
                     "decode_launches_pipelined_total",
                     "pipeline_rows_dropped_total",
                     "pipeline_fences_total"):
            self.spans.count(name, 0)
        self._launched: set = set()     # program keys already called once
        self._next_rid = 0
        self._steps = 0
        # --- expert-layer counters (cfg.moe): every decode and chunk
        # program adds its layers' [routed items, experts hit, largest
        # expert load, expert layers run] to ``_moe_acc`` ON THE DEVICE;
        # the decode program hands the sum back packed behind its
        # tokens, so the one read of the step's tokens brings them
        # (a hybrid config's expert layers hold a share of the experts:
        # the first three count what is held and computed here, and a
        # fourth the items routed to experts held elsewhere)
        # (a share of the experts held, a property of the parameters: a
        # hybrid config's expert layers, or a layer tree with
        # ``first_expert``; the first three then count what is held and
        # computed here, and a fourth the items routed to experts held
        # elsewhere)
        self._moe_names = (
            ("moe_routed_items_total", "moe_experts_hit_total",
             "moe_max_expert_load_total")
            + (("moe_items_elsewhere_total",) if cfg.hybrid is not None
               or "first_expert" in params["layers"] else ())
            + ("moe_layer_steps_total",))
        self._moe_layers = (cfg.kind_layers("experts")
                            if cfg.hybrid is not None
                            else cfg.num_layers - cfg.dense_layers)
        self._moe_acc = (jnp.zeros((len(self._moe_names),), jnp.int32)
                         if cfg.moe is not None else None)
        self._moe_zero = self._moe_acc
        if self._state_layers:
            # the first two again by the kind of state-space layer
            # (``mamba2_state_rows_total``, ``mamba1_chunk_tokens_total``)
            for name in ("ssm_state_rows_total", "ssm_chunk_tokens_total",
                         "ssm_state_rebuilds_total",
                         f"{self._state_kind}_state_rows_total",
                         f"{self._state_kind}_chunk_tokens_total"):
                self.spans.count(name, 0)
        if self._latent_layers:
            for name in ("latent_tokens_attended_total",
                         "latent_decode_rows_total",
                         "latent_chunk_tokens_total",
                         "latent_chunk_keys_attended_total"):
                self.spans.count(name, 0)
        # --- keys attended by kind: the (query, key) pairs of a program's
        # live rows, a window at most in a sliding layer, times the layers
        # of the kind; known at dispatch from the lengths. A chunk's for
        # every config that keeps keys and values, a decode step's where
        # it has sliding layers
        self._kind_layers = {k: cfg.cache_layers().get(k, 0)
                             for k in ("sliding", "full")}
        if self._kind_layers["full"]:
            self.spans.count("chunk_full_keys_attended_total", 0)
        if self.cache.window:
            for name in ("window_keys_attended_total",
                         "full_keys_attended_total",
                         "chunk_window_keys_attended_total"):
                self.spans.count(name, 0)
        # replica id spans carry (ISSUE 16) — stamped by the cluster /
        # supervisor; -1 renders as the "router" lane in exports
        self.replica_id = -1
        self._decode_fn = None
        # slot -> [request, sequence being prefilled (prompt, or the
        # preemption-resume replay), tokens already in pages]
        self._pending: Dict[int, List] = {}
        self._chunk_fns: Dict[tuple, object] = {}
        # --- speculative decoding (ISSUE 5 / ISSUE 14): n-gram draft +
        # batched verify; spec_k = max drafts per row per step, 0 = off.
        # temperature == 0 verifies against the greedy argmax (the
        # PR 5 path, token-identical to plain decode); temperature > 0
        # runs standard REJECTION SAMPLING against the verify logits
        # (serving.speculative.rejection_sample_tokens — q is the
        # deterministic proposer's point mass, so acceptance is p(x)
        # and the corrected residual keeps the output distribution
        # exactly the plain sampled-decode law), which is what gives
        # temperature>0 traffic the 1+k speedup.
        # --- model-based draft + tree speculation (ISSUE 20):
        # draft_layers builds a truncated-layer shared-embedding DRAFT
        # model (models/generate.make_draft_params) that proposes
        # spec_k tokens autoregressively on device, with its own KV in
        # a SECOND small paged pool under the same BlockAllocator
        # machinery; verification rides the existing verify forward,
        # and the rejection sampler is fed the draft's REAL q
        # distribution instead of a point mass. spec_tree=(width,
        # depth) additionally fans each draft step's top-``width``
        # candidates into a token TREE verified in ONE forward (the
        # tree-attention ancestor mask folds into the chunk kernel's
        # ragged masking); the longest accepted root path commits.
        # Draft pool state is DISPOSABLE: it is never journaled, never
        # swapped — preemption/recovery rebuild it cold through the
        # catch-up forward, token-identically.
        if spec_tree is not None:
            w, d = int(spec_tree[0]), int(spec_tree[1])
            if draft_layers is None:
                raise ValueError(
                    "spec_tree requires draft_layers: the tree's "
                    "candidates come from the draft model's per-step "
                    "top-width distributions")
            if w < 1 or d < 1:
                raise ValueError(
                    f"spec_tree=(width, depth) must both be >= 1, got "
                    f"{spec_tree}")
            if spec_k and int(spec_k) != d:
                raise ValueError(
                    f"spec_tree depth {d} conflicts with spec_k="
                    f"{spec_k}: the tree's chain IS the linear draft "
                    f"(leave spec_k at 0 or pass spec_k={d})")
            spec_k = d
            if 1 + w * d > 32:
                raise ValueError(
                    f"spec_tree=({w}, {d}) needs {1 + w * d} tree "
                    f"nodes; the fused kernel's per-query ancestor "
                    f"bitmask holds at most 32")
            self.spec_tree = (w, d)
            self._tree_T = 1 + w * d
        else:
            self.spec_tree = None
            self._tree_T = None
        if draft_layers is not None and int(spec_k) < 1:
            raise ValueError(
                "draft_layers requires spec_k >= 1: the draft model "
                "proposes spec_k tokens per step")
        self.spec_k = int(spec_k)
        if self.spec_k:
            if self.constraints:
                raise ValueError(
                    "spec_k > 0 cannot combine with constraints=True: "
                    "a verify batch commits tokens the per-row grammar "
                    "mask never saw — run constrained requests on a "
                    "plain-decode engine (the scenarios compose at the "
                    "cluster tier, one engine per workload class)")
            from ..serving.speculative import Speculator
            self.spec = (speculator if speculator is not None
                         else Speculator(self.spec_k,
                                         ngram_max=spec_ngram))
        else:
            self.spec = None
        self._spec_fns: Dict[tuple, object] = {}
        # host-side acceptance RNG for sampled speculation, seeded from
        # the engine key so two engines built identically draw the same
        # stream (recovery keeps committed tokens; uncommitted futures
        # re-draw — the same step-granularity contract sampled decode
        # already has)
        self._accept_rng = np.random.default_rng(
            int(np.asarray(jax.random.key_data(self._key)).sum()
                & 0x7FFFFFFF))
        self.draft_layers = (int(draft_layers)
                             if draft_layers is not None else None)
        self.draft_params = self.draft_cfg = self.draft_cache = None
        if self.draft_layers is not None:
            from ..models.generate import make_draft_params
            # truncation slices the (possibly quantized, possibly
            # sharded) SERVING params — the draft inherits the target's
            # weight tier and tp partitioning by construction, and the
            # param-spec pytree structure is unchanged (only the stacked
            # layer axis shrank), so _tp_map reuses self._param_specs
            self.draft_params, self.draft_cfg = make_draft_params(
                self.params, cfg, self.draft_layers)
            # + spec_k + 1 headroom past the main pool's max_len: the
            # draft loop's speculative feeds write up to spec_k
            # positions BEYOND the committed context, so a row drafted
            # at the tail of a full-length request still has pages
            self.draft_cache = PagedKVCache(
                self.draft_cfg, max_batch,
                (max_len or cfg.max_seq_len) + self.spec_k + 1,
                page_size=page_size, num_pages=draft_pages,
                kv_dtype=kv_cache_dtype, enable_prefix_cache=False,
                mesh=mesh)
        # per-slot draft bookkeeping: _draft_base[slot] is the main
        # context length at the last propose (the draft pool's valid
        # prefix is base + the accepted tokens that MATCH the fed
        # chain); _draft_chain holds the chain tokens actually fed
        # through the draft model, _draft_q the stashed per-position q
        # distributions awaiting the next linear dispatch
        self._draft_base = np.zeros((max_batch,), np.int64)
        self._draft_chain: Dict[int, np.ndarray] = {}
        self._draft_q: Dict[int, np.ndarray] = {}
        self._draft_fns: Dict[tuple, object] = {}
        self._draft_dec_fn = None
        self._tree_fns: Dict[tuple, object] = {}
        self._tree_commit_fns: Dict[int, object] = {}

    # ---- request intake ----
    def create_request(self, prompt, max_new_tokens: int = 16,
                       eos_token_id=None, adapter_id: int = 0,
                       constraint=None) -> GenerationRequest:
        """Validate and build a request WITHOUT queueing it — external
        schedulers (:class:`~paddle_tpu.serving.ServingScheduler`) own
        their queues and place requests via :meth:`admit_request`.

        ``adapter_id`` (ISSUE 14): the LoRA variant serving this
        request (0 = base model); needs an engine built with an
        :class:`~paddle_tpu.serving.adapters.AdapterPool`. The slot is
        pinned at ADMISSION, not here — a queued request holds no
        device residency. ``constraint``: a
        :class:`~paddle_tpu.serving.constraints.TokenDFA` (wrapped
        into a fresh per-request state) or a live
        :class:`~paddle_tpu.serving.constraints.ConstraintState`;
        needs ``constraints=True``."""
        if int(adapter_id) != 0:
            if self.adapters is None:
                raise ValueError(
                    f"create_request: adapter_id={adapter_id} on an "
                    f"engine without an adapter pool — pass adapters= "
                    f"at construction")
            # resolvability check at INTAKE: an unknown/oversized id
            # must reject this request here, not raise at admission
            # inside the serving loop (a poison-pill that would crash
            # every step and every recovery re-admission)
            self.adapters.validate_id(adapter_id)
        if constraint is not None and not self.constraints:
            raise ValueError(
                "create_request: a grammar constraint needs an engine "
                "built with constraints=True (the decode program "
                "carries the per-row mask input)")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("submit: empty prompt")
        need = self.cache.pages_for(prompt.size + int(max_new_tokens))
        if need > self.cache.pages_per_seq:
            raise ValueError(
                f"request of {prompt.size}+{max_new_tokens} tokens "
                f"exceeds max_len={self.cache.max_len}")
        usable = (self.cache.allocator.num_pages
                  - self.cache.allocator.reserved)
        if need > usable:
            # reject up front: queued, this request would deadlock
            # admission once it reached the head (no amount of
            # retirement frees more than the whole pool)
            raise ValueError(
                f"request needs {need} pages but the pool holds only "
                f"{usable}; grow num_pages or shrink the request")
        req = GenerationRequest(
            self._next_rid, prompt, max_new_tokens,
            self.eos_token_id if eos_token_id is None else eos_token_id)
        req.adapter_id = int(adapter_id)
        if constraint is not None:
            from ..serving.constraints import ConstraintState, TokenDFA
            if isinstance(constraint, TokenDFA):
                constraint = ConstraintState(constraint,
                                             eos_token_id=req.eos_token_id)
            req.constraint = constraint
        self._next_rid += 1
        return req

    def attach_constraint(self, req: GenerationRequest,
                          constraint) -> GenerationRequest:
        """Attach a live
        :class:`~paddle_tpu.serving.constraints.ConstraintState` to an
        EXISTING request handle — the restore/cold-recovery path
        (ISSUE 15): checkpointed grammar state rebuilds outside
        :meth:`create_request`, and re-attaching through the engine
        keeps the one validation that matters — an engine whose decode
        program carries no mask input must refuse loudly, never
        silently finish the session unconstrained."""
        if constraint is None:
            return req
        if not self.constraints:
            raise ValueError(
                "attach_constraint: this engine was built without "
                "constraints=True — restoring a grammar-constrained "
                "session into it would decode unconstrained; rebuild "
                "the engine with constraints=True")
        req.constraint = constraint
        return req

    def submit(self, prompt, max_new_tokens: int = 16,
               eos_token_id=None, adapter_id: int = 0,
               constraint=None) -> GenerationRequest:
        """Queue a prompt (1D int sequence); returns the request handle
        (``.done`` / ``.tokens`` / ``.output`` fill in as steps run)."""
        req = self.create_request(prompt, max_new_tokens=max_new_tokens,
                                  eos_token_id=eos_token_id,
                                  adapter_id=adapter_id,
                                  constraint=constraint)
        self._queue.append(req)
        return req

    # ---- jitted programs (one decode; one prefill per page bucket) ----
    def _rows_specs(self):
        """PartitionSpecs for the tree verify's un-placed per-node KV
        rows (ISSUE 20): ``rows[name]`` is (L, B, T, nkv[, hd]) — the
        kv-head axis shards over tp exactly like the pool's (same axis
        index 3), and the BATCH axis rides the dp split (the rows come
        out of the per-shard dense temp cache, one row block per dp
        shard; paged_tree_commit all-gathers them before the
        scatter)."""
        from jax.sharding import PartitionSpec as P
        ax, dpx = self._tp_axis, self._dp_axis
        return {name: (P(None, dpx, None, ax, None) if a.ndim == 5
                       else P(None, dpx, None, ax))
                for name, a in self.cache.pool.items()}

    def _tp_map(self, fn, arg_kinds, out_kinds=("rep", "pool"),
                cache=None):
        """Lower a per-shard serving forward through shard_map on the
        engine's serving mesh. ``arg_kinds``: one of ``"params"`` (the
        regex-rule spec pytree), ``"pool"`` (page pools, head axis
        sharded over tp, replicated across dp), ``"rep"`` (replicated
        host-side small args), ``"batch"`` (per-row batch args —
        last tokens, block tables, lengths, the active mask, adapter
        slots — split over the dp axis on a 2-D mesh, replicated on a
        1-D one) or ``"rows"`` (tree-verify per-node KV,
        :meth:`_rows_specs`) per positional argument. ``out_kinds``
        names the output positions the same way (default ``(logits,
        pool)``; a single kind maps the output pytree directly) —
        logits are replicated (the per-shard body already all-gathered
        them over tp AND dp; ``check_vma=False`` skips the symbolic
        replication proof, same as the training-side ring-attention
        shard_map). ``cache`` picks whose pool specs "pool" means —
        the DRAFT pool's programs (ISSUE 20) pass their own cache."""
        from jax.sharding import PartitionSpec as P
        pool_specs = (cache if cache is not None else self.cache
                      ).pool_specs
        kinds = {"params": self._param_specs,
                 "pool": pool_specs, "rep": P(),
                 "batch": (P(self._dp_axis)
                           if self._dp_axis is not None else P()),
                 "rows": self._rows_specs()}
        if self.adapters is not None:
            # adapter-pool factor dict: B factors column-sharded on the
            # same output axis as the base weights, A + scales
            # replicated (llama.adapter_partition_specs)
            kinds["adapters"] = self.adapters.specs
        out_specs = (kinds[out_kinds[0]] if len(out_kinds) == 1
                     else tuple(kinds[k] for k in out_kinds))
        return jax.shard_map(
            fn, mesh=self.mesh,
            in_specs=tuple(kinds[k] for k in arg_kinds),
            out_specs=out_specs, check_vma=False)

    def _decode(self):
        if self._decode_fn is None:
            from ..models import generate as gen
            cfg, temp, uk = self.cfg, self.temperature, self.use_kernel
            ax, fz = self._tp_axis, self.fused
            dpx = self._dp_axis
            ad_on, cons = self.adapters is not None, self.constraints
            win = self.cache.window is not None
            moe = cfg.moe is not None
            nlayers = self._moe_layers

            def fwd(params, last, paged, tables, lengths, active, *rest):
                # rest (engine-config-static): [the sliding layers'
                # block tables], then [adapter arrays, adapter slots]
                rest = list(rest)
                wt = rest.pop(0) if win else None
                ad, aslot = (rest if ad_on else (None, None))
                return gen.paged_decode_forward(
                    params, last, paged, tables, lengths, cfg,
                    active=active, use_kernel=uk, tp_axis=ax,
                    dp_axis=dpx, fused=fz, adapters=ad,
                    adapter_slots=aslot, window_tables=wt,
                    with_stats=moe)
            n_fwd = int(win) + 2 * int(ad_on)
            if self.mesh is not None:
                fwd = self._tp_map(
                    fwd, ("params", "batch", "pool", "batch", "batch",
                          "batch") + ("batch",) * win
                    + ("adapters", "batch") * ad_on,
                    out_kinds=("rep", "pool") + ("rep",) * moe)

            nrows = self.max_batch

            def f(params, last, paged, tables, lengths, active, key,
                  prev, *extra):
                # ``last`` is the host's copy of every row's newest
                # token, -1 where the host has not read it yet: that
                # row's comes from ``prev``, the output of the decode
                # program launched before this one (tokens first)
                last = jnp.where(last >= 0, last, prev[:nrows])
                # extra layout (engine-config-static): what ``fwd``
                # takes (sliding block tables, adapter arrays and
                # slots), then [the (B, V) allowed-token mask] when
                # constraints are on, then [the expert counters'
                # accumulator] of a MoE config
                extra = list(extra)
                logits, paged, *st = fwd(params, last, paged, tables,
                                         lengths, active, *extra[:n_fwd])
                del extra[:n_fwd]
                raw = None
                if cons:
                    # the UNCONSTRAINED argmax rides along so the commit
                    # can count violations the mask avoided; masking
                    # happens BEFORE the temperature split so greedy and
                    # sampled constrained decode share one rule
                    cmask = extra.pop(0)
                    raw = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    logits = jnp.where(cmask, logits, -jnp.inf)
                if temp == 0.0:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                else:
                    nxt = jax.random.categorical(
                        key, logits / temp, axis=-1).astype(jnp.int32)
                # a row that did not decode keeps its token, so the
                # output is every row's newest token for the next launch
                nxt = jnp.where(active, nxt, last)
                if moe:
                    # the counters ride behind the tokens: one read
                    nxt = jnp.concatenate(
                        [nxt, extra.pop(0) + jnp.append(st[0], nlayers)])
                if cons:
                    return (nxt, raw), paged
                return nxt, paged

            self._decode_fn = _named_jit(f, "paged_decode",
                                         donate_argnums=(2,))
        return self._decode_fn

    def _chunk_fn(self, ctx_cap: int, width: int):
        """One compiled chunked-prefill program per static ``(context
        cap, chunk width)`` pair. ``ctx_cap`` is power-of-two-bucketed
        and ``width`` page-bucketed (capped at ``prefill_chunk``), so a
        long-lived server compiles O(width_buckets x log(pages_per_seq))
        variants — not one per distinct prompt or shared-prefix
        length."""
        key = (ctx_cap, width)
        if key not in self._chunk_fns:
            from ..models import generate as gen
            cfg, ax, fz = self.cfg, self._tp_axis, self.fused
            uk, dpx = self.use_kernel, self._dp_axis

            # chunked prefill stays dp-REPLICATED (one row per
            # program): every batch arg keeps the "rep" kind and only
            # dp_axis threads through, so a MoE config's expert
            # dispatch can still all-to-all over the dp axis
            ad_on = self.adapters is not None
            win = self.cache.window is not None
            state = bool(self._state_layers)
            moe = cfg.moe is not None
            nlayers = self._moe_layers

            def fwd(params, chunk, paged, table, ctx_len, chunk_len,
                    *rest):
                # rest: [the sliding layers' block table], then [the
                # row's slot in the state pool], then [adapter arrays,
                # adapter slot]
                rest = list(rest)
                wt = rest.pop(0) if win else None
                st = rest.pop(0) if state else None
                ad, aslot = (rest if ad_on else (None, None))
                return gen.paged_prefill_chunk(
                    params, chunk, paged, table, cfg, ctx_cap=ctx_cap,
                    ctx_len=ctx_len, chunk_len=chunk_len, tp_axis=ax,
                    dp_axis=dpx, fused=fz, use_kernel=uk, adapters=ad,
                    adapter_slot=aslot, window_table=wt, with_stats=moe,
                    state_slot=st)
            if self.mesh is not None:
                fwd = self._tp_map(
                    fwd, ("params", "rep", "pool", "rep", "rep", "rep")
                    + ("rep",) * win + ("adapters", "rep") * ad_on,
                    out_kinds=("rep", "pool") + ("rep",) * moe)
            f = fwd
            if moe:
                def f(*args):
                    # the last argument is the expert counters'
                    # accumulator; it comes back with this chunk's added
                    logits, paged, st = fwd(*args[:-1])
                    return logits, paged, args[-1] + jnp.append(
                        st, nlayers)
            self._chunk_fns[key] = _named_jit(
                f, f"prefill_chunk_c{ctx_cap}_w{width}",
                donate_argnums=(2,))
        return self._chunk_fns[key]

    def _spec_fn(self, ctx_cap: int, T: int):
        """One compiled speculative-verify program per static ``(context
        cap, chunk width)`` pair: the batched verify forward + greedy
        argmax at every position. ``ctx_cap`` buckets to power-of-two
        page counts (same rule as :meth:`_chunk_fn`) and ``T`` is
        ``spec_k + 1``, so a long-lived server compiles
        O(log(pages_per_seq)) variants."""
        key = (ctx_cap, T)
        if key not in self._spec_fns:
            from ..models import generate as gen
            cfg, uk, ax = self.cfg, self.use_kernel, self._tp_axis
            fz, dpx = self.fused, self._dp_axis
            ad_on, temp = self.adapters is not None, self.temperature

            if ad_on:
                def fwd(params, chunk, paged, tables, lengths, active,
                        ad, aslot):
                    return gen.paged_verify_forward(
                        params, chunk, paged, tables, lengths, cfg,
                        ctx_cap=ctx_cap, active=active, use_kernel=uk,
                        tp_axis=ax, dp_axis=dpx, fused=fz, adapters=ad,
                        adapter_slots=aslot)
                if self.mesh is not None:
                    fwd = self._tp_map(fwd, ("params", "batch", "pool",
                                             "batch", "batch", "batch",
                                             "adapters", "batch"))
            else:
                def fwd(params, chunk, paged, tables, lengths, active):
                    return gen.paged_verify_forward(
                        params, chunk, paged, tables, lengths, cfg,
                        ctx_cap=ctx_cap, active=active, use_kernel=uk,
                        tp_axis=ax, dp_axis=dpx, fused=fz)
                if self.mesh is not None:
                    fwd = self._tp_map(fwd, ("params", "batch", "pool",
                                             "batch", "batch", "batch"))

            def f(params, chunk, paged, tables, lengths, active,
                  *extra):
                logits, paged = (fwd(params, chunk, paged, tables,
                                     lengths, active, *extra) if ad_on
                                 else fwd(params, chunk, paged, tables,
                                          lengths, active))
                if temp == 0.0:
                    # greedy verify: only the per-position argmax leaves
                    # the device (the ISSUE 5 path, unchanged)
                    return (jnp.argmax(logits, axis=-1)
                            .astype(jnp.int32), paged)
                # sampled verify (ISSUE 14): rejection sampling needs
                # the full (B, T, V) verify distributions on the host —
                # acceptance is min(1, p/q) per draft position and the
                # corrected residual draws from p with the draft zeroed
                return logits.astype(jnp.float32), paged

            self._spec_fns[key] = _named_jit(
                f, f"spec_verify_c{ctx_cap}_t{T}", donate_argnums=(2,))
        return self._spec_fns[key]

    # ---- draft-model + tree speculation programs (ISSUE 20) ----
    def _draft_catchup_fn(self, ctx_cap: int, T: int):
        """One compiled draft-pool CATCH-UP program per static
        ``(context cap, width)`` pair: the verify forward over the
        DRAFT model writing a ``T``-token chunk of already-committed
        context into the draft pool (logits discarded — only the KV
        matters). Cold draft pools (first propose after prefill,
        post-preemption resume, crash recovery) replay through this,
        which is what makes the rebuilt pool token-identical."""
        key = (ctx_cap, T)
        if key not in self._draft_fns:
            from ..models import generate as gen
            cfg, uk, ax = self.draft_cfg, self.use_kernel, self._tp_axis
            fz, dpx = self.fused, self._dp_axis

            def f(params, chunk, paged, tables, lengths, active):
                _, paged = gen.paged_verify_forward(
                    params, chunk, paged, tables, lengths, cfg,
                    ctx_cap=ctx_cap, active=active, use_kernel=uk,
                    tp_axis=ax, dp_axis=dpx, fused=fz)
                return paged
            if self.mesh is not None:
                f = self._tp_map(f, ("params", "batch", "pool",
                                     "batch", "batch", "batch"),
                                 out_kinds=("pool",),
                                 cache=self.draft_cache)
            self._draft_fns[key] = _named_jit(
                f, f"draft_catchup_c{ctx_cap}_w{T}", donate_argnums=(2,))
        return self._draft_fns[key]

    def _draft_decode(self):
        """The draft model's one-token decode program: same ragged
        paged decode as :meth:`_decode` but over the draft params/pool
        and returning the full (B, V) f32 LOGITS — the proposer needs
        the real distribution q on the host (chain token + tree
        candidates + the rejection sampler's min(1, p/q))."""
        if self._draft_dec_fn is None:
            from ..models import generate as gen
            cfg, uk, ax = self.draft_cfg, self.use_kernel, self._tp_axis
            fz, dpx = self.fused, self._dp_axis

            def f(params, last, paged, tables, lengths, active):
                logits, paged = gen.paged_decode_forward(
                    params, last, paged, tables, lengths, cfg,
                    active=active, use_kernel=uk, tp_axis=ax,
                    dp_axis=dpx, fused=fz)
                return logits.astype(jnp.float32), paged
            if self.mesh is not None:
                f = self._tp_map(f, ("params", "batch", "pool",
                                     "batch", "batch", "batch"),
                                 cache=self.draft_cache)
            self._draft_dec_fn = _named_jit(f, "draft_decode",
                                            donate_argnums=(2,))
        return self._draft_dec_fn

    def _tree_fn(self, ctx_cap: int, T: int):
        """One compiled TREE-VERIFY program per static ``(context cap,
        node count)`` pair: the verify forward in tree mode — rope
        positions ``lengths + depth``, the ancestor mask folded into
        the chunk attention — returning the greedy per-node argmax
        (temp 0) or the full per-node logits (sampled), PLUS the
        un-placed per-node KV rows (no scatter: placement waits for
        the host's accepted root path, :meth:`_tree_commit_fn`). The
        main pool passes through untouched, so it is NOT donated."""
        key = (ctx_cap, T)
        if key not in self._tree_fns:
            from ..models import generate as gen
            cfg, uk, ax = self.cfg, self.use_kernel, self._tp_axis
            fz, dpx = self.fused, self._dp_axis
            ad_on, temp = self.adapters is not None, self.temperature

            def fwd(params, chunk, paged, tables, lengths, active,
                    depths, anc, *extra):
                kw = {}
                if ad_on:
                    kw = {"adapters": extra[0],
                          "adapter_slots": extra[1]}
                return gen.paged_verify_forward(
                    params, chunk, paged, tables, lengths, cfg,
                    ctx_cap=ctx_cap, active=active, use_kernel=uk,
                    tp_axis=ax, dp_axis=dpx, fused=fz,
                    tree_depth=depths, tree_mask=anc, **kw)

            def f(params, chunk, paged, tables, lengths, active,
                  depths, anc, *extra):
                logits, rows = fwd(params, chunk, paged, tables,
                                   lengths, active, depths, anc,
                                   *extra)
                if temp == 0.0:
                    return (jnp.argmax(logits, axis=-1)
                            .astype(jnp.int32), rows)
                return logits.astype(jnp.float32), rows
            if self.mesh is not None:
                kinds = ["params", "batch", "pool", "batch", "batch",
                         "batch", "batch", "batch"]
                if ad_on:
                    kinds += ["adapters", "batch"]
                f = self._tp_map(f, tuple(kinds),
                                 out_kinds=("rep", "rows"))
            self._tree_fns[key] = _named_jit(
                f, f"tree_verify_c{ctx_cap}_t{T}")
        return self._tree_fns[key]

    def _tree_commit_fn(self, T: int):
        """The tree commit's jitted placement: gather each row's
        accepted root-path nodes out of the verify's KV rows and
        scatter them into the main pool at ``lengths + d`` —
        bit-identical to what a linear verify of the accepted path
        would have written. Only the pool is donated — the rows'
        ``(L, B, T, nkv, hd)`` buffers never match an output shape,
        so donating them would just warn."""
        if T not in self._tree_commit_fns:
            from ..models import generate as gen
            dpx = self._dp_axis

            def f(paged, rows, tables, lengths, path_nodes, path_len):
                return gen.paged_tree_commit(
                    paged, rows, tables, lengths, path_nodes,
                    path_len, dp_axis=dpx)
            if self.mesh is not None:
                f = self._tp_map(f, ("pool", "rows", "batch", "batch",
                                     "batch", "batch"),
                                 out_kinds=("pool",))
            self._tree_commit_fns[T] = _named_jit(
                f, f"tree_commit_t{T}", donate_argnums=(0,))
        return self._tree_commit_fns[T]

    def _launch(self, fn, args, kind: str, ctx_cap: int = 0,
                width: int = 0):
        """Call a jitted program. The first call for a key traces,
        lowers and compiles it (or loads it from the cache): that one
        runs under ``engine.build_program``, which marks the step that
        paid for it."""
        key = (kind, ctx_cap, width)
        if key in self._launched:
            return fn(*args)
        with self.spans.span("engine.build_program", kind=kind,
                             ctx_cap=ctx_cap, width=width,
                             period=len(self.cfg.period)):
            out = fn(*args)
        self._launched.add(key)
        return out

    # ---- scheduling ----
    def _install_slot(self, slot: int, req: GenerationRequest):
        """Seat ``req`` in ``slot`` and mirror its commit-relevant
        state into the flat per-slot arrays the vectorized decode
        commit indexes (rid guard, token count, max_new, eos id)."""
        req.slot = slot
        self._slots[slot] = req
        self._rids[slot] = req.rid
        self._seat[slot] += 1
        self._ntok[slot] = len(req.tokens)
        self._maxnew[slot] = req.max_new_tokens
        self._eos[slot] = (-1 if req.eos_token_id is None
                           else int(req.eos_token_id))
        # per-row adapter slot mirror (ISSUE 14): the pool pin taken at
        # admission guarantees the slot id stays valid while seated
        self._aslot[slot] = (self.adapters.slot_of(req.adapter_id)
                             if self.adapters is not None else 0)
        if self.constraints:
            self._cmask[slot] = (
                req.constraint.mask(self.cfg.vocab_size)
                if req.constraint is not None else True)
            self._cmask_dirty = True

    def _clear_slot(self, slot: int):
        self._slots[slot] = None
        self._rids[slot] = -1
        self._aslot[slot] = 0
        if self.draft_cache is not None:
            # draft pool state is DISPOSABLE (ISSUE 20): retire,
            # preempt, swap and cancel all just drop the slot's draft
            # pages — resume/recovery rebuild them cold through the
            # catch-up forward, token-identically
            if self.draft_cache.active[slot]:
                self.draft_cache.release(slot)
            self._draft_chain.pop(slot, None)
            self._draft_q.pop(slot, None)
            self._draft_base[slot] = 0
        if self.constraints:
            self._cmask[slot] = True
            self._cmask_dirty = True

    def admit_request(self, req: GenerationRequest) -> bool:
        """Place ``req`` into a free slot, reserving its pages (prefix-
        shared where the trie already holds them). Returns False when
        every slot is busy; raises
        :class:`~paddle_tpu.serving.PoolExhausted` when the pool can't
        cover it. Admission only RESERVES pages; the request's tokens
        prefill chunk-by-chunk in :meth:`prefill_step`.

        A previously PREEMPTED request re-admits through the same path:
        its replay sequence (``resume_sequence()`` — prompt + generated
        tokens minus the last) reserves pages and replays through the
        continuation-prefill program, so resume is token-identical to
        an uninterrupted run. Under the host tier (ISSUE 10) a victim
        that was SWAPPED OUT resumes by swap-in scatter instead: its
        exact KV bytes return from host RAM in one donated scatter —
        bit-identical and decode-ready immediately, no replay forward.
        A missing/stale payload (LRU-dropped) falls back to the replay
        path, which remains the one gated resume code path."""
        cache = self.cache
        free = cache.free_slots()
        if not free:
            return False
        slot = free[0]
        # adapter residency (ISSUE 14): pin the request's adapter slot
        # BEFORE any page work — acquire may itself defer
        # (AdapterPoolExhausted is a PoolExhausted: every slot pinned is
        # back-pressure, same as a full page pool), and a later
        # PoolExhausted from the page side must drop the pin it took so
        # a deferred admission leaks nothing
        pinned = False
        if self.adapters is not None and req.adapter_id:
            self.adapters.acquire(req.adapter_id)
            pinned = True
        try:
            return self._admit_pinned(req, slot)
        except BaseException:
            if pinned:
                self.adapters.release(req.adapter_id)
            raise

    def _admit_pinned(self, req: GenerationRequest, slot: int) -> bool:
        """The page-side half of :meth:`admit_request`, run with the
        request's adapter pin (if any) already held."""
        cache = self.cache
        seq = req.resume_sequence()
        # trace: queue_wait closes at the admission INSTANT (anchored
        # here), so the swap-in work below lands in swap_ms, not queue
        t_adm = _obs.serving_trace_now()
        if (req.swapped and req.tokens
                and getattr(cache, "host", None) is not None):
            # a raised swap_in (injected fault, PoolExhausted) leaves
            # the flag SET — the payload committed nothing and survives
            # for the retried admission after recovery/back-pressure
            length = cache.swap_in(
                slot, req.rid, req.prompt.shape[1] + req.max_new_tokens,
                expect_tokens=seq.size)
            req.swapped = False
            if length is not None:
                self._install_slot(slot, req)
                # decode continues from the already-sampled last token,
                # exactly as the replay path would after its final chunk
                self._last[slot] = np.int32(req.tokens[-1])
                req.finish_reason = None    # clears transient "preempted"
                _obs.serving_resumed(1, 0)  # zero replay tokens: swap-in
                _obs.serving_trace_admitted(
                    req, replica=self.replica_id, slot=slot, t_ns=t_adm)
                _obs.serving_trace_span(
                    req, "swap_in", t_adm, replica=self.replica_id,
                    slot=slot, seq=len(req.tokens),
                    meta={"tokens": int(length)})
                return True
            # payload gone (capacity drop / stale — swap_in counted the
            # fallback): replay below, the gated resume path
            if t_adm:
                _obs.serving_trace_mark(
                    req, "swap_fallback", replica=self.replica_id,
                    slot=slot, meta={"why": getattr(
                        cache, "last_swap_fallback", None)})
        _, shared = cache.admit_prompt(
            slot, seq, req.prompt.shape[1] + req.max_new_tokens)
        self._install_slot(slot, req)
        self._pending[slot] = [req, seq, int(shared)]
        _obs.serving_trace_admitted(
            req, replica=self.replica_id, slot=slot, t_ns=t_adm,
            meta={"shared": int(shared)} if t_adm else None)
        if req.preemptions > 0:
            if t_adm:
                _obs.serving_trace_mark(
                    req, "resume_replay", replica=self.replica_id,
                    slot=slot,
                    meta={"replay": int(seq.size) - int(shared)})
            # resume re-entry: the replay cost has its own counter —
            # counting it as an admission would drift the occupancy
            # identity (admissions - evictions - preemptions), and its
            # generated-token replay is NOT a prompt prefix miss (it
            # would collapse the dashboarded prefix hit rate)
            _obs.serving_resumed(1, seq.size - int(shared))
            if self._state_layers:
                # the row's recurrent state went with its slot: the
                # replay's chunks rebuild it from zero with the pages
                self.spans.count("ssm_state_rebuilds_total", 1)
        else:
            # full sequence size here — the prefix hit/miss split is
            # the serving_prefix pair's job, and the chunk-token
            # counter already measures tokens actually forwarded
            _obs.serving_admitted(1, seq.size)
            _obs.serving_prefix(int(shared), seq.size - int(shared))
            self.spans.count("prompt_tokens_total", seq.size)
            self.spans.count("prefix_hit_tokens_total", int(shared))
        return True

    def swap_candidate(self, req: GenerationRequest) -> bool:
        """True when preempting ``req`` would SWAP its KV to the host
        tier (near-free resume) rather than evict-and-replay: the
        cache is tiered and the request is decode-phase (committed KV
        exists — mid-prefill victims have nothing worth moving). The
        :class:`~paddle_tpu.serving.PreemptionPolicy` prefers such
        victims when the scheduler passes this predicate through."""
        return (getattr(self.cache, "host", None) is not None
                and req.slot is not None
                and req.slot not in self._pending
                and int(self.cache.lengths[req.slot]) > 0)

    def preempt_request(self, req: GenerationRequest) -> int:
        """Evict a RUNNING request's pages back to the pool (the
        scheduler's evict-for-preempt: refcounts drop; pages shared
        with the prefix trie or other tables survive under those
        references) and reset the request for a token-identical resume
        via :meth:`admit_request`. Under the host tier (ISSUE 10) a
        decode-phase victim's live pages SWAP OUT to host RAM first,
        so the later resume is a swap-in scatter instead of the
        ``O(resident tokens)`` replay prefill. ``finish_reason`` reads
        the transient ``preempted`` until the resume completes;
        ``done`` stays False. Returns the number of pages actually
        returned to the free list."""
        # the victim's committed state (tokens, lengths, what a swap
        # copies out) has to be final first: read what is in flight
        self.fence()
        slot = req.slot
        if slot is None or self._slots[slot] is not req:
            raise ValueError(
                f"preempt_request: request {req.rid} is not running")
        return self._evict_seated(req)

    def _evict_seated(self, req: GenerationRequest) -> int:
        """:meth:`preempt_request` after its fence. A program still in
        flight for this seating (only a caller that skips the fence
        leaves one) has its row dropped at commit by the seat guard."""
        slot = req.slot
        swap = self.swap_candidate(req)
        self._pending.pop(slot, None)
        t_tr = _obs.serving_trace_now()
        if swap:
            # overlap engines issue the swap-out DMA NON-BLOCKING: the
            # device→host copy rides under the in-flight decode step
            # and the host-store entry materializes at the next commit
            # fence (ISSUE 12 satellite a)
            freed = self.cache.swap_out(slot, req.rid,
                                        nonblocking=self.overlap)
            req.swapped = True
            if t_tr:
                _obs.serving_trace_span(
                    req, "swap_out", t_tr, replica=self.replica_id,
                    slot=slot, seq=len(req.tokens),
                    meta={"pages": int(freed),
                          "nonblocking": bool(self.overlap)})
        else:
            freed = self.cache.evict_for_preempt(slot)
        if t_tr:
            _obs.serving_trace_mark(
                req, "preempt", replica=self.replica_id, slot=slot,
                seq=len(req.tokens), meta={"swap": bool(swap)})
        self._clear_slot(slot)
        req.slot = None
        req.preemptions += 1
        req.finish_reason = "preempted"
        if self.adapters is not None and req.adapter_id:
            # the evicted request holds no device residency of any kind
            # while preempted: re-admission re-pins (and, if the slot
            # was reclaimed meanwhile, promotes the adapter back)
            self.adapters.release(req.adapter_id)
        _obs.serving_preempted(1, freed)
        return freed

    def cancel_request(self, req: GenerationRequest,
                       reason: str = "cancelled"):
        """Finish ``req`` without further decode (e.g. a scheduler's
        ``deadline_exceeded``): a running request releases its slot and
        pages, a queued/preempted one just marks done. Idempotent on
        finished requests."""
        if req.done:
            return
        if req.slot is not None and self._slots[req.slot] is req:
            self.fence()        # it may finish by itself in there
            if req.done:
                return
            self._pending.pop(req.slot, None)
            self._retire(req, reason)
            return
        try:
            self._queue.remove(req)
        except ValueError:
            pass                        # scheduler-owned queue entry
        req.done = True
        req.finish_reason = reason
        _obs.serving_trace_finish(req, reason, replica=self.replica_id)
        if getattr(self.cache, "host", None) is not None:
            # a swap-preempted victim cancelled while evicted retires
            # its host payload with it (nothing will ever swap it in)
            self.cache.drop_swapped(req.rid)
        if req.preemptions > 0:
            # preempted awaiting resume: it WAS admitted (its pages
            # already freed at preempt time) — the cancel finalizes
            # the retirement so admissions - evictions drains to zero
            _obs.serving_retired(1, reason)
        else:
            # never held a slot/pages: a cancellation, NOT an eviction
            _obs.serving_cancelled(1, reason)

    def _admit(self):
        """Fill free slots from the queue (FIFO; a head-of-line request
        the pool can't cover yet blocks admission — fairness over
        utilization). Priority-aware admission lives in
        :class:`~paddle_tpu.serving.ServingScheduler`, which calls
        :meth:`admit_request` directly."""
        from ..serving import PoolExhausted
        while self._queue:
            try:
                if not self.admit_request(self._queue[0]):
                    break               # no free slot
            except PoolExhausted:
                if not self.cache.active.any():
                    raise  # nothing running will ever free pages
                break
            self._queue.pop(0)

    def prefill_dispatch(self, slot: Optional[int] = None,
                         max_tokens: Optional[int] = None) -> int:
        """DISPATCH half of :meth:`prefill_step`: launch one pending
        admission's next static-shape chunk program, queue an in-flight
        handle, and advance what no token's value decides — the
        ``done`` cursor, the sliding pool's pages and, on a FINAL
        chunk, the row's length and its place among the decode-ready
        rows (the next decode program may be launched behind it at
        once). On a FINAL chunk the first token is argmax/sampled ON
        DEVICE here — the PRNG split happens at dispatch, so the
        synchronous and pipelined paths split keys in the same order —
        and goes into the device's token row; the scalar fetch, the
        prefix registration and ``req.tokens`` wait for the commit.
        Returns the width actually scheduled (0 when nothing was)."""
        if not self._pending:
            return 0
        cache = self.cache
        if slot is None:
            slot = min(self._pending,
                       key=lambda s: self._pending[s][0].rid)
        ent = self._pending[slot]
        req, seq, done = ent
        S = seq.size
        page = cache.page_size
        remaining = S - done
        width = cache.pages_for(remaining) * page
        if self.prefill_chunk is not None:
            width = min(width, self.prefill_chunk)
        if max_tokens is not None:
            cap = (int(max_tokens) // page) * page
            if cap < page:
                return 0
            width = min(width, cap)
        take = min(remaining, width)
        # ctx_cap buckets UP to a power-of-two page count so the
        # (ctx_cap, width) compile-key space stays O(width_buckets *
        # log(pages_per_seq)) instead of quadratic in pages_per_seq —
        # shared-prefix lengths and prompt lengths vary independently
        # across requests.
        ctx_cap = cache.ctx_cap_pages(cache.pages_for(done)) * page
        chunk = np.zeros((1, width), np.int32)
        chunk[0, :take] = seq[done:done + take]
        # resilience site: fires before the chunk program so a fault
        # commits nothing (neither ``done`` nor a sampled token)
        _fault_point("prefill_chunk")
        t0 = _obs.generate_begin()
        with self.spans.span("engine.dispatch", kind="chunk"):
            # host arrays go to the device as COPIES: on the CPU
            # backend jnp.asarray may alias the numpy buffer, and the
            # host writes these rows again while the program runs
            args = [self.params, jnp.asarray(chunk), cache.pool,
                    jnp.asarray(cache.block_tables[slot].copy()),
                    jnp.int32(done), jnp.int32(take)]
            if cache.window:
                # the sliding layers' pages for the chunk's positions
                cache.window_extend(slot, done + take)
                args += [jnp.asarray(cache.window_tables[slot].copy())]
            if self._state_layers:
                # the row's recurrent state goes in and comes out with
                # the pools; tokens through the chunked scan, a layer
                args += [jnp.int32(slot)]
                self.spans.count("ssm_chunk_tokens_total", take)
                self.spans.count(f"{self._state_kind}_chunk_tokens_total",
                                 take)
            if self._kind_layers["full"]:
                # token i of the chunk sees done + i + 1 positions
                self._count_keys("chunk_", np.arange(done + 1,
                                                     done + take + 1))
            if self._latent_layers:
                # tokens whose latents this chunk writes, a layer, and the
                # (query, key) pairs its valid tokens attend: token i sees
                # done + i + 1 positions; times layers
                self.spans.count("latent_chunk_tokens_total", take)
                self.spans.count(
                    "latent_chunk_keys_attended_total",
                    (take * done + take * (take + 1) // 2)
                    * self._latent_layers)
            if self.adapters is not None:
                args += [self.adapters.arrays,
                         jnp.asarray(self._aslot[slot:slot + 1].copy())]
            if self._moe_acc is not None:
                args += [self._moe_acc]
            logits, cache.pool, *acc = self._launch(
                self._chunk_fn(ctx_cap, width), args, "chunk", ctx_cap,
                width)
            if acc:
                self._moe_acc = acc[0]
            samp = rawmax = None
            if done + take >= S and not req.tokens:
                # final chunk of a fresh admission (or a mid-prefill
                # victim's resume): the first token comes from these
                # logits. Keep the sample on device; fetch at commit.
                lg = logits[0]
                if self.constraints and req.constraint is not None:
                    # the FIRST token obeys the grammar too: the slot
                    # mask (installed at admission from the DFA start
                    # state) applies before the argmax/categorical,
                    # same rule as the decode program's in-graph where.
                    # The UNMASKED argmax rides along so the
                    # violation-avoided counter covers this commit path
                    # like the decode one.
                    rawmax = jnp.argmax(lg)
                    lg = jnp.where(jnp.asarray(self._cmask[slot].copy()),
                                   lg, -jnp.inf)
                if self.temperature == 0.0:
                    samp = jnp.argmax(lg)
                else:
                    self._key, k = jax.random.split(self._key)
                    samp = jax.random.categorical(
                        k, lg / self.temperature)
            # ---- the half of the commit that no value decides ----
            done += take
            final = done >= S
            if cache.window:
                # what the next chunk's (or the first decode step's)
                # first query no longer sees goes back to the sliding
                # pool: a program that takes such a page is launched
                # after this one, so it runs after it
                self.spans.count("window_pages_released_total",
                                 cache.window_release(slot, done))
            if not final:
                ent[2] = done
            else:
                del self._pending[slot]
                cache.lengths[slot] = S
                if cache.window:
                    cache.window_extend(slot, S + 1)  # first decode write
                if req.tokens:
                    # preemption resume: the replay covered prompt +
                    # tokens[:-1]; decode continues from the already-
                    # sampled last token (its KV lands on the next
                    # decode step, exactly as in the uninterrupted run)
                    self._last[slot] = np.int32(req.tokens[-1])
                else:
                    self._ntok[slot] = 1
                    self._tok_dev = self._put_row(slot, samp)
            self._launched_handle(
                {"slot": slot, "req": req,
                 "seat": int(self._seat[slot]), "take": take, "t0": t0,
                 "done": done, "final": final,
                 "logits": logits, "samp": samp, "rawmax": rawmax,
                 "ttr": _obs.serving_trace_now()})
        return width

    def _count_keys(self, prefix: str, seen):
        """Book the keys a program's queries read, ``seen`` (n,) the
        positions each query sees: ``min(seen, window)`` a sliding layer,
        ``seen`` a full one, times the layers of the kind."""
        seen = np.asarray(seen, np.int64)
        if self.cache.window:
            self.spans.count(
                prefix + "window_keys_attended_total",
                int(np.minimum(seen, self.cache.window).sum())
                * self._kind_layers["sliding"])
        self.spans.count(prefix + "full_keys_attended_total",
                         int(seen.sum()) * self._kind_layers["full"])

    def _launched_handle(self, h):
        """Queue the handle of a program just launched, in launch
        order; returns it."""
        self.launch_seq += 1
        self._inflight.append((self.launch_seq, h))
        return h

    def _token_row(self):
        """The device's copy of every row's newest token (zeros before
        the first launch), on the engine's mesh where it has one."""
        if self._tok_dev is None:
            tok = jnp.zeros((self.max_batch + (
                len(self._moe_names) if self._moe_acc is not None
                else 0),), jnp.int32)
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                tok = jax.device_put(
                    tok, NamedSharding(self.mesh, PartitionSpec()))
            self._tok_dev = tok
        return self._tok_dev

    def _put_row(self, slot: int, tok):
        """The device's token row with ``tok`` (a device scalar, not
        read) in ``slot``. One small program whatever the slot."""
        if self._put_row_fn is None:
            self._put_row_fn = _named_jit(
                lambda row, i, t: row.at[i].set(t.astype(row.dtype)),
                "put_token_row")
        return self._put_row_fn(self._token_row(), jnp.int32(slot), tok)

    def _commit_chunk(self, h: Dict) -> int:
        """COMMIT half of one dispatched prefill chunk. A chunk that is
        not the prompt's last has nothing to read and costs no wait;
        the last one fetches the first token, publishes the prompt to
        the prefix trie and records the token (``eos`` may retire the
        row here). On a preemption RESUME the next token was known at
        dispatch and nothing is sampled (the resumed request must not
        fork)."""
        slot, req, take = h["slot"], h["req"], h["take"]
        with self.spans.span("engine.wait", kind="chunk"):
            # both obs calls fence the chunk logits when a sink is
            # active — that wait is device time, not exposed host time
            if self.fused:
                _obs.serving_fused_latency("chunk_flash_attn", h["t0"],
                                           h["logits"])
            _obs.serving_prefill_chunk(h["t0"], h["logits"], take)
            # a final chunk's first token: the ONE device→host fetch
            first = int(h["samp"]) if h["samp"] is not None else None
        if (self._slots[slot] is not req
                or int(self._seat[slot]) != h["seat"]):
            # the slot changed hands between dispatch and commit (even
            # to the same request: the seat generation moved, so this
            # chunk's KV went to the old seating's freed pages):
            # commit nothing; the fresh admission replays the span
            # through its own chunks
            return 0
        with self.spans.span("engine.commit", rows=1):
            return self._commit_chunk_host(h, first)

    def _commit_chunk_host(self, h: Dict, first) -> int:
        """The host bookkeeping of :meth:`_commit_chunk`, after the
        read: the trace span, prefix registration, the first token."""
        slot, req, take = h["slot"], h["req"], h["take"]
        _obs.serving_trace_span(
            req, "prefill_chunk", h.get("ttr", 0),
            replica=self.replica_id, slot=slot, seq=len(req.tokens),
            meta={"take": int(take), "done": int(h["done"])})
        if not h["final"]:
            return take
        self.cache.register_prefix(slot, req.prompt[0])
        req.finish_reason = None            # clears transient "preempted"
        if first is not None:
            self._last[slot] = first
            # violation check against the PRE-advance slot mask with
            # the UNMASKED argmax, mirroring the decode commit — read
            # BEFORE _record_token, whose retirement clears the slot
            # (and its mask) when this token finishes the request
            viol = (int(not self._cmask[slot, int(h["rawmax"])])
                    if self.constraints and req.constraint is not None
                    else 0)
            self._record_token(req, first)
            if self.constraints and req.constraint is not None:
                t0m = time.perf_counter_ns()
                req.constraint.advance(first)
                if not req.done:
                    self._cmask[slot] = req.constraint.mask(
                        self.cfg.vocab_size)
                    self._cmask_dirty = True
                _obs.serving_constrain(
                    time.perf_counter_ns() - t0m, viol, 1)
        return take

    def prefill_step(self, slot: Optional[int] = None,
                     max_tokens: Optional[int] = None) -> int:
        """Advance ONE pending admission by one static-shape chunk
        (default: the oldest, FIFO): the per-step latency added to
        in-flight decodes is bounded by one chunk's forward instead of
        a whole prompt's. ``max_tokens`` caps the chunk width (floored
        to a page multiple — the scheduler's token-budget debit must be
        a hard ceiling); returns the width actually scheduled (0 when
        nothing was). The final chunk's logits (taken at the last VALID
        token) seed sampling — except on a preemption RESUME, where the
        next token is already known and is fed back into decode instead
        — and the completed prompt's pages are published to the prefix
        trie for future admissions. Synchronous composition: whatever
        is in flight commits first, then :meth:`prefill_dispatch` +
        :meth:`commit_inflight`."""
        self.commit_inflight()
        width = self.prefill_dispatch(slot, max_tokens=max_tokens)
        self.commit_inflight()
        return width

    def _record_token(self, req: GenerationRequest, tok: int):
        req.tokens.append(int(tok))
        if len(req.tokens) == 1:
            _obs.serving_trace_first_token(req)
        if req.slot is not None:
            # the launched-token count never lags the read one: the
            # verify paths commit several tokens a launch and count
            # them here (a decode launch counted its own already)
            self._ntok[req.slot] = max(self._ntok[req.slot],
                                       len(req.tokens))
        if req.eos_token_id is not None and tok == req.eos_token_id:
            self._retire(req, "eos")
        elif len(req.tokens) >= req.max_new_tokens:
            self._retire(req, "max_len")

    def _retire(self, req: GenerationRequest, reason: str):
        req.done = True
        req.finish_reason = reason
        _obs.serving_trace_finish(req, reason, replica=self.replica_id)
        # A row that ends by ``eos`` may have one more decode program
        # in flight (launched before this token was read): that
        # program writes the eos token's KV into a page released here.
        # No later owner can read the stray row. It was launched before
        # anything the next owner launches, so it runs before it
        # (device programs run in launch order); the owner reads only
        # positions below its own length, each of which it wrote
        # itself, later; and the prefix trie holds only the rows a
        # PROMPT filled (full prompt pages, and the first
        # ``prompt % page`` rows of the tail page), while the stray row
        # lies at position prompt + tokens, past all of them.
        self.cache.release(req.slot)
        self._clear_slot(req.slot)
        if self.adapters is not None and req.adapter_id:
            self.adapters.release(req.adapter_id)
        _obs.serving_retired(1, reason)

    def _tp_observe(self):
        """tp-serving telemetry (ISSUE 7): the per-shard pool gauge
        every step, plus — every 16th step — a TIMED logits-collective
        probe: a dedicated jitted all-gather of a logits-shard-sized
        array over the serving mesh. The step program's own collective
        time is invisible from the host (it fuses into one XLA
        program), so the probe measures the same collective in
        isolation and feeds the ``serving_tp_logits_gather_ms``
        histogram."""
        if self.mesh is None or not _obs.active():
            return
        alloc = self.cache.allocator
        _obs.serving_tp_step(self._tp, alloc.num_used, alloc.num_usable)
        if (self._steps - 1) % 16:      # first step, then every 16th
            return
        if self._tp_probe is None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            mesh, ax, tp = self.mesh, self._tp_axis, self._tp
            vp = -(-self.cfg.vocab_size // tp)  # per-shard logits cols
            x = jax.device_put(
                jnp.zeros((self.max_batch, vp * tp), jnp.float32),
                NamedSharding(mesh, P(None, ax)))
            f = jax.jit(jax.shard_map(
                lambda t: jax.lax.all_gather(t, ax, axis=1, tiled=True),
                mesh=mesh, in_specs=P(None, ax), out_specs=P(),
                check_vma=False))
            np.asarray(f(x))            # compile outside the timing
            self._tp_probe = (f, x)
        probe, x = self._tp_probe
        t0 = _obs.generate_begin()
        _obs.serving_tp_logits_gather(t0, probe(x))

    # ---- prefill→decode KV handoff (ISSUE 9) ----
    def handoff_candidates(self) -> List[GenerationRequest]:
        """Seated requests whose prompt is in the pool and whose first
        token has been read: what a prefill replica may hand to a
        decode replica. Reads what is in flight first, so a request is
        offered as soon as its final chunk has run, before it decodes
        here."""
        self.fence()
        return [r for r in self._slots
                if r is not None and not r.done and r.tokens
                and r.slot not in self._pending]

    def export_prefilled(self, req: GenerationRequest,
                         with_kv: bool = True) -> Dict:
        """Export a fully prefilled, decode-ready request's KV pages as
        a handoff payload (the disaggregated cluster's prefill→decode
        transfer): the slot's live page bytes
        (:meth:`~paddle_tpu.serving.PagedKVCache.export_request`), the
        committed length and the already-sampled last token. PURE READ
        — the request keeps running here until :meth:`finish_handoff`
        detaches it, so a failed import on the decode side loses
        nothing. ``with_kv=False`` (the ISSUE 11 fused direct-handoff
        path) skips materializing the page bytes on the host — the
        importer copies them device-to-device through the fused
        :func:`~paddle_tpu.serving.paged_cache._pool_move` instead;
        the payload then carries only the slot metadata."""
        self.fence()            # the last token and the length, read
        slot = req.slot
        if slot is None or self._slots[slot] is not req:
            raise ValueError(
                f"export_prefilled: request {req.rid} is not running")
        if slot in self._pending:
            raise ValueError(
                f"export_prefilled: request {req.rid} is still "
                f"mid-prefill — hand off only decode-ready slots")
        out = {"rid": req.rid, "slot": slot,
               "length": int(self.cache.lengths[slot]),
               "last": int(self._last[slot])}
        if with_kv:
            out["kv"] = self.cache.export_request(slot)
        return out

    def import_prefilled(self, req: GenerationRequest,
                         payload: Dict, src_engine=None) -> bool:
        """Install an exported request DIRECTLY into a decode slot: the
        payload's pages scatter into freshly allocated pages
        (:meth:`~paddle_tpu.serving.PagedKVCache.import_request`), the
        block table / length / last-token state matches what in-place
        prefill would have left, and the prompt's pages publish to THIS
        engine's prefix trie (future same-prefix admissions here HIT).
        Returns False when no slot is free; raises
        :class:`~paddle_tpu.serving.PoolExhausted` (nothing changed)
        when the pool can't cover it. Decode from here is BIT-identical
        to having prefilled in place.

        ``src_engine`` (ISSUE 11): the exporting engine, when it shares
        this process — the pages then copy device-to-device through the
        fused :func:`~paddle_tpu.serving.paged_cache._pool_move` (one
        donated program, no host staging) and the payload needs no
        ``"kv"`` bytes (``export_prefilled(with_kv=False)``). Same
        byte-identity gate either way."""
        free = self.cache.free_slots()
        if not free:
            return False
        slot = free[0]
        # the importing engine pins the adapter on ITS pool (the KV
        # payload is adapter-agnostic by the q/o-only design, so the
        # bytes install unchanged; a failed page install drops the pin)
        pinned = False
        if self.adapters is not None and req.adapter_id:
            self.adapters.acquire(req.adapter_id)
            pinned = True
        try:
            if src_engine is not None:
                self.cache.import_request_direct(
                    slot, src_engine.cache, payload["slot"],
                    req.prompt.shape[1] + req.max_new_tokens)
            else:
                self.cache.import_request(
                    slot, payload["kv"],
                    req.prompt.shape[1] + req.max_new_tokens)
        except BaseException:
            if pinned:
                self.adapters.release(req.adapter_id)
            raise
        self.cache.lengths[slot] = np.int32(payload["length"])
        self._last[slot] = np.int32(payload["last"])
        self._install_slot(slot, req)
        self.cache.register_prefix(slot, req.prompt[0])
        return True

    def finish_handoff(self, req: GenerationRequest, slot: int):
        """Detach a handed-off request from THIS engine after a
        successful import elsewhere: the slot entry clears FIRST (so
        even a fault inside the page release cannot leave two engines
        decoding the same request), then the pages release — ones the
        prefix trie shares survive under its references, which is what
        keeps the prefill replica's trie warm for the tenant's next
        prompt. ``slot`` is the ORIGINAL slot from the export payload
        (``req.slot`` already points at the importing engine)."""
        self.fence()
        if self._slots[slot] is not req:
            raise ValueError(
                f"finish_handoff: slot {slot} does not hold request "
                f"{req.rid}")
        self._clear_slot(slot)
        self._pending.pop(slot, None)
        self.cache.release(slot)
        if self.adapters is not None and req.adapter_id:
            # the importing engine took its own pin; this side's drops
            self.adapters.release(req.adapter_id)

    def ready_mask(self) -> np.ndarray:
        """(max_batch,) bool — slots whose sequence is fully in the
        pool (or will be when the chunks launched so far have run) and
        that have tokens left to be launched for; slots mid-prefill
        hold pages (active) but skip the decode program, and a row
        launched for its last token waits for its commit."""
        ready = self.cache.active & (self._ntok < self._maxnew)
        if self._pending:
            ready[list(self._pending)] = False
        return ready

    # ---- dispatch / commit halves: the decode pipeline ----
    def has_inflight(self) -> bool:
        """True while a dispatched decode/verify program or prefill
        chunk awaits its commit."""
        return bool(self._inflight)

    def pipeline_depth(self) -> int:
        """How many decode steps may run ahead of the one being read:
        1 wherever the next launch needs nothing of the tokens in
        flight but their values on the device, 0 where the host has to
        see them first — a proposer reads the committed history
        (speculation, tree verification) and a grammar's next mask
        follows from the token. Decided from what the engine holds,
        each step; no caller's flag."""
        if self.spec is not None:
            return 0
        if self.constraints and any(
                r is not None and r.constraint is not None
                for r in self._slots):
            return 0
        return 1

    def fence(self) -> int:
        """Read and commit everything in flight because what comes next
        needs the committed state: a launch at depth 0, a preemption,
        swap-out, cancel or handoff of a seated request, a drain.
        Counted in ``pipeline_fences_total`` when there was something
        to wait for. Returns the committed units."""
        if not self._inflight:
            return 0
        self.spans.count("pipeline_fences_total", 1)
        return self.commit_inflight()

    def drop_inflight(self) -> None:
        """Forget what is in flight without reading it (a supervisor
        abandoning a poisoned engine)."""
        self._inflight = []

    def _rows_on_device(self) -> np.ndarray:
        """(max_batch,) bool — rows whose newest token the host has not
        read yet: launched in a decode program, or sampled by a final
        chunk, that is still in flight for the slot's present seating.
        The next decode program takes those from the device's token
        row and every other from ``_last``."""
        on = np.zeros((self.max_batch,), bool)
        for _, h in self._inflight:
            if isinstance(h, dict):
                if (h["samp"] is not None
                        and self._slots[h["slot"]] is h["req"]
                        and int(self._seat[h["slot"]]) == h["seat"]):
                    on[h["slot"]] = True
            elif h.kind == "decode":
                on |= (h.mask & (h.rids == self._rids)
                       & (h.seats == self._seat))
        return on

    def decode_dispatch(self, mask) -> Optional[InFlightStep]:
        """DISPATCH half of :meth:`decode_step`: launch the jitted
        ragged decode program for the ``mask`` slots and return the
        in-flight handle WITHOUT fetching the result. The program may
        be launched BEHIND one whose tokens the host has not read: a
        row's input token then comes from that program's output on the
        device. What follows from the launch alone is booked here —
        each row's length and launched-token count (so ``ready_mask``
        stops a row at its ``max_new_tokens``), the sliding pool's page
        for the next position and the pages the window slid past, the
        PRNG split (same order as the synchronous path); what depends
        on a token's value waits for the commit. At
        :meth:`pipeline_depth` 0 everything in flight commits first."""
        cache = self.cache
        mask = np.asarray(mask, bool)
        if self._inflight and not self.pipeline_depth():
            self.fence()
            mask = mask & self.ready_mask()
        if not mask.any():
            return None
        # resilience sites: step execution (before the launch), then
        # the dispatch seam (after it) — neither commits host state,
        # so a fault at either recovers by journal replay
        _fault_point("decode_step")
        t0f = _obs.generate_begin() if self.fused else 0
        with self.spans.span("engine.dispatch", kind="decode"):
            self._key, k = jax.random.split(self._key)
            # host arrays go to the device as COPIES (np.where makes
            # one): on the CPU backend jnp.asarray may alias the numpy
            # buffer, and the host advances lengths and tables while
            # the program runs
            last = np.where(self._rows_on_device(), np.int32(-1),
                            self._last)
            args = [self.params, jnp.asarray(last), cache.pool,
                    jnp.asarray(cache.block_tables.copy()),
                    jnp.asarray(cache.lengths.copy()),
                    jnp.asarray(mask), k, self._token_row()]
            if cache.window:
                args += [jnp.asarray(cache.window_tables.copy())]
            if self.adapters is not None:
                args += [self.adapters.arrays,
                         jnp.asarray(self._aslot.copy())]
            if self.constraints:
                if self._cmask_dirty or self._cmask_dev is None:
                    self._cmask_dev = jnp.asarray(self._cmask.copy())
                    self._cmask_dirty = False
                args += [self._cmask_dev]
            if self._moe_acc is not None:
                args += [self._moe_acc]
                self._moe_acc = self._moe_zero
            out, cache.pool = self._launch(self._decode(), args, "decode")
            raw = None
            if self.constraints:
                out, raw = out
            _fault_point("dispatch")
            self._tok_dev = out
            self.spans.count("decode_launches_total", 1)
            if any(isinstance(h, InFlightStep) for _, h in self._inflight):
                self.spans.count("decode_launches_pipelined_total", 1)
            h = self._launched_handle(InFlightStep(
                "decode", mask, self._rids.copy(), self._seat.copy(),
                out, t0f=t0f, raw=raw, ttr=_obs.serving_trace_now()))
            # ---- the half of the commit that no value decides ----
            slots = np.flatnonzero(mask)
            cache.lengths[slots] += 1
            if cache.window:
                self.spans.count("window_pages_released_total",
                                 cache.window_step(slots))
                # a row's query sees its length's positions, the new one
                # among them
                self._count_keys("", cache.lengths[slots])
            if self._state_layers:
                # row-layers of recurrent state this program advances
                self.spans.count("ssm_state_rows_total",
                                 slots.size * self._state_layers)
                self.spans.count(f"{self._state_kind}_state_rows_total",
                                 slots.size * self._state_layers)
            if self._latent_layers:
                # tokens this program's rows attend over, the new one
                # among them, known from the lengths; times layers
                self.spans.count(
                    "latent_tokens_attended_total",
                    int(cache.lengths[slots].sum()) * self._latent_layers)
                self.spans.count("latent_decode_rows_total", slots.size)
            self._ntok[slots] += 1
        return h

    def _decode_commit(self, h: InFlightStep) -> int:
        """COMMIT half of :meth:`decode_step`: the single device→host
        fetch plus VECTORIZED host bookkeeping — the last-token scatter
        via one fancy-indexed update, eos/max_len finish detection
        against the mirrored per-slot arrays, per-row Python work only
        for the append and for the rows that actually finish. A slot
        whose seating changed since dispatch (retired by an earlier
        step's ``eos``, or preempted and re-seated) is skipped via the
        rid/seat snapshot and counted in
        ``pipeline_rows_dropped_total``."""
        # resilience sites: the commit seam, then the device→host
        # transfer — request handles change only after both, so a fault
        # at either leaves them at the previous step's committed state
        # (the supervisor's recovery contract)
        _fault_point("commit")
        # the device-wait span OPENS before the observability calls:
        # serving_fused_latency fences h.out when metrics are on, and
        # charging that wait to exposed host time would inflate the
        # host_overhead_fraction gauge exactly when it is emitted
        with self.spans.span("engine.wait", kind="decode"):
            _obs.serving_fused_latency("decode_rope_attn", h.t0f, h.out)
            _fault_point("transfer")
            nxt = np.asarray(h.out)
            raw = np.asarray(h.raw) if self.constraints else None
        if self._moe_acc is not None:
            nxt, moe = nxt[:self.max_batch], nxt[self.max_batch:]
            for name, n in zip(self._moe_names, moe.tolist()):
                self.spans.count(name, n)
        rows = int(h.mask.sum())
        with self.spans.span("engine.commit", rows=rows):
            return self._decode_commit_host(h, nxt, raw, rows)

    def _decode_commit_host(self, h: InFlightStep, nxt, raw,
                            rows: int) -> int:
        """The host bookkeeping of :meth:`_decode_commit`, after the
        read."""
        cache = self.cache
        valid = (h.mask & (self._rids == h.rids) & (h.rids >= 0)
                 & (self._seat == h.seats))
        slots = np.flatnonzero(valid)
        self.spans.count("pipeline_rows_dropped_total", rows - slots.size)
        if slots.size:
            toks = nxt[slots]
            self._last[slots] = toks
            sl, tl = slots.tolist(), toks.tolist()
            cnt = []
            for s, t in zip(sl, tl):
                tokens = self._slots[s].tokens
                tokens.append(t)
                cnt.append(len(tokens))
            fin_eos = (self._eos[slots] >= 0) & (toks == self._eos[slots])
            fin_max = np.asarray(cnt) >= self._maxnew[slots]
            if h.ttr:
                # one decode_step span per committed row, closed at the
                # commit fence (h.ttr anchored at dispatch). The
                # vectorized append above bypasses _record_token, so
                # the TTFT stamp happens here for first tokens.
                t1 = _obs.serving_trace_now()
                for s in sl:
                    treq = self._slots[s]
                    _obs.serving_trace_span(
                        treq, "decode_step", h.ttr, t1,
                        replica=self.replica_id, slot=s,
                        seq=len(treq.tokens))
                    if len(treq.tokens) == 1:
                        _obs.serving_trace_first_token(treq)
            if self.constraints:
                # advance each constrained row's DFA with the token
                # that actually COMMITTED, refresh its next-step mask,
                # and count the steps where the UNCONSTRAINED argmax
                # would have violated the grammar (each one is a saved
                # parse failure). Runs BEFORE retirement clears slots.
                t0m = time.perf_counter_ns()
                viol = crows = 0
                for s, t in zip(sl, tl):
                    creq = self._slots[s]
                    if creq is None or creq.constraint is None:
                        continue
                    crows += 1
                    if not self._cmask[s, int(raw[s])]:
                        viol += 1
                    creq.constraint.advance(t)
                    self._cmask[s] = creq.constraint.mask(
                        self.cfg.vocab_size)
                    self._cmask_dirty = True
                if crows:
                    _obs.serving_constrain(
                        time.perf_counter_ns() - t0m, viol, crows)
            for i in np.flatnonzero(fin_eos | fin_max).tolist():
                self._retire(self._slots[sl[i]],
                             "eos" if fin_eos[i] else "max_len")
        self._steps += 1
        alloc = cache.allocator
        # occupancy reports the rows the DISPATCHED program computed
        # (mask), matching the synchronous path; the return counts only
        # rows that passed the seat guard and actually committed
        _obs.serving_step(rows, self.max_batch,
                          alloc.num_used, alloc.num_usable)
        if self._dp_axis is not None:
            # per-dp-shard row load of the DISPATCHED program: slot s
            # rides shard s // (max_batch/dp), the same contiguous
            # row-block split the "batch" in_specs apply
            _obs.serving_dp_step(
                self.dp, h.mask.reshape(self.dp, -1).sum(axis=1))
        self._tp_observe()
        return int(slots.size)

    def commit_inflight(self, upto: Optional[int] = None) -> int:
        """Commit what is in flight, in launch order: everything, or
        with ``upto`` what was launched no later than that
        ``launch_seq`` (the pipelined scheduler leaves its newest step
        running); finally fence any pending async swap-out DMAs into
        the host store. Returns the number of committed units (prompt
        tokens + decode slots / verify tokens)."""
        n = 0
        flight = self._inflight
        while flight and (upto is None or flight[0][0] <= upto):
            _, h = flight.pop(0)
            n += (self._commit_chunk(h) if isinstance(h, dict)
                  else self._decode_commit(h) if h.kind == "decode"
                  else self._tree_commit(h) if h.kind == "tree"
                  else self._spec_commit(h))
        fence = getattr(self.cache, "fence_swaps", None)
        if fence is not None:
            with self.spans.span("engine.wait", kind="swap"):
                fence()
        return n

    def decode_step(self, mask) -> int:
        """Advance every ``mask`` slot one decode token through the
        single jitted ragged decode program (callers pass
        :meth:`ready_mask` or a scheduler's budgeted subset of it).
        Returns the number of slots advanced (0 skips the program
        entirely). Synchronous composition — whatever is in flight
        commits first, then :meth:`decode_dispatch` +
        :meth:`commit_inflight`: the bit-identity reference the
        pipelined scheduler is gated against."""
        self.commit_inflight()
        if self.decode_dispatch(mask) is None:
            return 0
        return self.commit_inflight()

    # ---- speculative decoding (ISSUE 5) ----
    def propose_drafts(self, mask) -> Dict[int, np.ndarray]:
        """Draft proposals for every masked ready slot — ``slot ->
        up-to-spec_k draft tokens`` (rows with no in-history match, a
        poor acceptance EMA, or no remaining token room are simply
        absent and decode plainly). Separated from :meth:`spec_step`
        so the SLO scheduler can charge each row's verify width
        against its token budget BEFORE executing.

        With a DRAFT MODEL configured (``draft_layers``, ISSUE 20) the
        proposals come from :meth:`_propose_model_drafts` instead of
        the host n-gram lookup; under ``spec_tree`` the returned
        values are :class:`~paddle_tpu.serving.speculative.TreeDraft`
        trees, which satisfy the same ``d.size`` / ``d[:k]`` planner
        contract (the budget charges tree NODES; trimming drops
        leaves, never the root path)."""
        if self.spec is None:
            return {}
        if self.draft_params is not None:
            return self._propose_model_drafts(mask)
        mask = np.asarray(mask, bool)
        drafts: Dict[int, np.ndarray] = {}
        for slot, req in enumerate(self._slots):
            if req is None or not mask[slot]:
                continue
            # a verify commits accepted + 1 (bonus) tokens: cap drafts
            # so the commit can never overshoot max_new_tokens — plain
            # decode would have stopped there, and parity is the gate
            room = req.max_new_tokens - len(req.tokens) - 1
            if room <= 0:
                continue
            d = self.spec.propose(
                slot, req.rid,
                np.concatenate([req.prompt[0],
                                np.asarray(req.tokens, np.int32)]),
                cap=min(self.spec_k, room))
            if d.size:
                drafts[slot] = d
        return drafts

    def _propose_model_drafts(self, mask) -> Dict:
        """DRAFT-MODEL proposer (ISSUE 20): k autoregressive steps of
        the truncated-layer draft model on device, against the slot's
        own pages in the SECOND (draft) paged pool.

        Protocol per masked row: (1) lazy-admit a draft-pool slot
        (PoolExhausted skips drafting — pure back-pressure, the row
        decodes plainly); (2) CATCH-UP — feed the gap between the
        draft pool's valid prefix and the committed context (all but
        the last token) through the draft verify forward. Steady state
        is zero-width: every commit leaves the pool caught up, so the
        catch-up only pays on a cold slot (first propose, resume,
        crash recovery) — which is exactly the disposable-pool
        rebuild; (3) k one-token draft decode steps from the last
        sampled token, each yielding the full distribution q on the
        host: the chain token is its argmax (or a q-sample at
        temperature — the rejection sampler's min(1, p/q) requires
        drafts ~ q), tree mode takes the top-``width`` candidates per
        depth (deterministic candidates keep sequential point-mass
        rejection exact in law).

        The draft pool's ``lengths`` stay at the VALID prefix — the
        speculative feeds advance only a local run-length, so a
        fallback plain-decode step (or a preemption) never has to roll
        anything back; the commit advances the valid prefix past
        exactly the accepted tokens that match the fed chain."""
        from ..serving import PoolExhausted
        from ..serving.speculative import TreeDraft, build_comb_tree
        mask = np.asarray(mask, bool)
        dc = self.draft_cache
        _fault_point("draft_propose")
        rows: Dict[int, np.ndarray] = {}
        rooms: Dict[int, int] = {}
        for slot, req in enumerate(self._slots):
            if req is None or not mask[slot]:
                continue
            room = req.max_new_tokens - len(req.tokens) - 1
            if room <= 0:
                continue
            if not dc.active[slot]:
                total = (req.prompt.shape[1] + req.max_new_tokens
                         + self.spec_k + 1)
                try:
                    dc.admit(slot, total)
                except PoolExhausted:
                    continue        # back-pressure: decode plainly
                dc.lengths[slot] = 0
            rows[slot] = np.concatenate(
                [req.prompt[0], np.asarray(req.tokens, np.int32)])
            rooms[slot] = room
        if not rows:
            return {}
        B, k, temp = self.max_batch, self.spec_k, self.temperature
        # --- catch-up: page-bucketed verify chunks over the draft
        # model until every row's pool covers its context minus the
        # last token (multi-chunk only for prompt-scale gaps)
        catchup = 0
        while True:
            need = {s: rows[s].size - 1 - int(dc.lengths[s])
                    for s in rows}
            cmax = max(need.values())
            if cmax <= 0:
                break
            W = 1
            while W < min(cmax, 128):
                W *= 2
            chunk = np.zeros((B, W), np.int32)
            cmask = np.zeros((B,), bool)
            adv = np.zeros((B,), np.int32)
            for s, c in need.items():
                if c <= 0:
                    continue
                c = min(c, W)
                st = int(dc.lengths[s])
                chunk[s, :c] = rows[s][st:st + c]
                cmask[s] = True
                adv[s] = c
                catchup += c
            ctx_cap = dc.ctx_cap_pages(dc.pages_for(
                int(dc.lengths[cmask].max()))) * dc.page_size
            with self.spans.span("engine.dispatch", kind="draft_catchup"):
                dc.pool = self._launch(
                    self._draft_catchup_fn(ctx_cap, W),
                    [self.draft_params, jnp.asarray(chunk), dc.pool,
                     jnp.asarray(dc.block_tables),
                     jnp.asarray(dc.lengths), jnp.asarray(cmask)],
                    "draft_catchup", ctx_cap, W)
            dc.lengths[cmask] += adv[cmask]
        # --- autoregressive draft loop (speculative feeds advance
        # only the LOCAL run-length; dc.lengths stays the valid prefix)
        amask = np.zeros((B,), bool)
        for s in rows:
            amask[s] = True
            self._draft_base[s] = rows[s].size
        run_len = dc.lengths.copy()
        x = self._last.copy()
        tree_w = self.spec_tree[0] if self.spec_tree is not None else 0
        chains = {s: [] for s in rows}
        fed = {s: [] for s in rows}
        qs = ({s: [] for s in rows}
              if temp != 0.0 and not tree_w else None)
        cands = {s: [] for s in rows} if tree_w else None
        dec = self._draft_decode()
        for i in range(k):
            with self.spans.span("engine.dispatch", kind="draft"):
                logits, dc.pool = self._launch(
                    dec, [self.draft_params, jnp.asarray(x), dc.pool,
                          jnp.asarray(dc.block_tables),
                          jnp.asarray(run_len), jnp.asarray(amask)],
                    "draft")
            with self.spans.span("engine.wait", kind="draft"):
                logits = np.asarray(logits)
            run_len[amask] += 1
            for s in rows:
                z = logits[s].astype(np.float64)
                if tree_w:
                    top = np.argsort(z)[::-1][:tree_w]
                    cands[s].append(top.astype(np.int32))
                    nxt = int(top[0])
                elif temp != 0.0:
                    z = z / temp
                    z -= z.max()
                    q = np.exp(z)
                    q /= q.sum()
                    nxt = int(self._accept_rng.choice(q.size, p=q))
                    qs[s].append(q)
                else:
                    nxt = int(np.argmax(z))
                if len(chains[s]) < min(k, rooms[s]):
                    chains[s].append(nxt)
                if i < k - 1:
                    fed[s].append(nxt)
                x[s] = nxt
        out: Dict = {}
        drafted = 0
        for s in rows:
            self._draft_chain[s] = np.asarray(fed[s], np.int32)
            if tree_w:
                t = build_comb_tree(
                    int(self._last[s]),
                    np.asarray(chains[s], np.int32),
                    [c[1:] for c in cands[s]])
                t = t[:min(t.size, rooms[s])]
                if t.size:
                    out[s] = t
                    drafted += t.size
            else:
                d = np.asarray(chains[s], np.int32)
                if qs is not None:
                    self._draft_q[s] = np.stack(qs[s])[:d.size]
                if d.size:
                    out[s] = d
                    drafted += d.size
        _obs.serving_draft_propose(len(rows), drafted, catchup)
        _obs.serving_draft_pool(dc.allocator.num_used,
                                dc.allocator.num_usable)
        return out

    def spec_step(self, mask, drafts: Optional[Dict] = None) -> int:
        """The speculative sibling of :meth:`decode_step`, sharing its
        ready-mask machinery: draft (host n-gram lookup), verify all
        masked rows' drafts in ONE batched forward
        (:func:`~paddle_tpu.models.generate.paged_verify_forward` +
        greedy argmax at every position), then commit each row's
        longest accepted prefix plus the bonus token. Rows without
        drafts ride the same program and commit exactly their plain
        greedy token (the static-shape program computes every lane
        regardless, like the decode program's inactive rows); when NO
        masked row drafted, this falls back to :meth:`decode_step`
        outright — the worst case is the baseline step. Returns the
        number of tokens committed (>= slots advanced).

        Rollback of rejected draft KV is pure host bookkeeping:
        ``lengths`` advances only past the accepted prefix, the length
        mask keeps the stale page rows invisible, and the strictly
        sequential writes at ``lengths`` overwrite them before the mask
        ever reaches them — no device copy, no page churn (the
        allocator never sees a verify)."""
        self.commit_inflight()
        if self.spec_dispatch(mask, drafts) is None:
            return 0
        return self.commit_inflight()

    def spec_dispatch(self, mask,
                      drafts: Optional[Dict] = None
                      ) -> Optional[InFlightStep]:
        """DISPATCH half of :meth:`spec_step`: build the draft chunk,
        launch the batched verify program, return the in-flight handle.
        Falls back to :meth:`decode_dispatch` when no masked row
        drafted (the worst case is the baseline step)."""
        if self.spec is None:
            return self.decode_dispatch(mask)
        cache = self.cache
        mask = np.asarray(mask, bool)
        if not mask.any():
            return None
        if self._inflight:
            # depth 0: the proposals were drawn from the committed
            # history, and so must the verify's last tokens be
            self.fence()
            mask = mask & self.ready_mask()
            if not mask.any():
                return None
        if drafts is None:
            drafts = self.propose_drafts(mask)
        if self.spec_tree is not None:
            # tree speculation (ISSUE 20): the proposals are TreeDraft
            # trees — one tree-mode verify forward scores every node
            return self._tree_dispatch(mask, drafts)
        drafts = {s: np.asarray(d, np.int32) for s, d in drafts.items()
                  if len(d) and mask[s]}
        if not drafts:
            return self.decode_dispatch(mask)
        # draft-model q snapshot (ISSUE 20): the stashed per-position
        # proposal distributions ride the in-flight handle, trimmed to
        # the (possibly planner-shortened) dispatched width — the
        # commit's rejection sampler accepts with min(1, p/q)
        qs = None
        if self.draft_params is not None and self.temperature != 0.0:
            qs = {s: self._draft_q[s][:d.size]
                  for s, d in drafts.items() if s in self._draft_q}
        B, T = self.max_batch, self.spec_k + 1
        chunk = np.zeros((B, T), np.int32)
        chunk[:, 0] = self._last
        dlen = np.zeros((B,), np.int32)
        for s, d in drafts.items():
            chunk[s, 1:1 + d.size] = d
            dlen[s] = d.size
        # ctx_cap: power-of-two page bucket of the longest masked
        # context (same compile-key rule as chunked prefill; ready
        # rows always hold >= 1 prefilled token, so the cap is > 0)
        ctx_cap = cache.ctx_cap_pages(cache.pages_for(
            int(cache.lengths[mask].max()))) * cache.page_size
        _fault_point("verify_step")
        t0 = _obs.generate_begin()
        with self.spans.span("engine.dispatch", kind="spec"):
            args = [self.params, jnp.asarray(chunk), cache.pool,
                    jnp.asarray(cache.block_tables.copy()),
                    jnp.asarray(cache.lengths.copy()), jnp.asarray(mask)]
            if self.adapters is not None:
                args += [self.adapters.arrays,
                         jnp.asarray(self._aslot.copy())]
            out, cache.pool = self._launch(
                self._spec_fn(ctx_cap, T), args, "spec", ctx_cap, T)
            _fault_point("dispatch")
            self.spans.count("decode_launches_total", 1)
            h = self._launched_handle(InFlightStep(
                "spec", mask, self._rids.copy(), self._seat.copy(), out,
                drafts=drafts, dlen=dlen, t0=t0,
                ttr=_obs.serving_trace_now(), qs=qs))
        return h

    def _spec_commit(self, h: InFlightStep) -> int:
        """COMMIT half of :meth:`spec_step`: fetch the greedy targets,
        commit each row's longest accepted prefix + bonus token.
        Rollback of rejected draft KV is pure host bookkeeping (see
        :meth:`spec_step`); slots whose request changed since dispatch
        are skipped via the rid snapshot."""
        _fault_point("commit")
        # device-wait span opens before the (fencing) obs call —
        # same host-attribution rule as _decode_commit
        with self.spans.span("engine.wait", kind="spec"):
            if self.fused:
                _obs.serving_fused_latency("verify_flash_attn", h.t0,
                                           h.out)
            _fault_point("transfer")
            out = np.asarray(h.out)   # (B, T) greedy targets — or, under
            #                           sampled speculation, (B, T, V)
            #                           verify logits for rejection sampling
        t1 = time.perf_counter_ns()        # device fence: verify done
        with self.spans.span("engine.commit", rows=int(h.mask.sum())):
            return self._spec_commit_host(h, out, t1)

    def _spec_commit_host(self, h: InFlightStep, out, t1: int) -> int:
        """The host bookkeeping of :meth:`_spec_commit`, after the
        read."""
        cache = self.cache
        mask, drafts, dlen = h.mask, h.drafts, h.dlen
        from ..serving.speculative import (longest_accepted_prefix,
                                           rejection_sample_tokens)
        sampled = self.temperature != 0.0
        n_slots = committed = drafted = accepted = 0
        for slot, req in enumerate(self._slots):
            if (req is None or not mask[slot]
                    or self._rids[slot] != h.rids[slot]
                    or self._seat[slot] != h.seats[slot]):
                continue
            n_slots += 1
            j = int(dlen[slot])
            d = drafts.get(slot)
            if sampled:
                # standard rejection sampling (ISSUE 14): accept draft i
                # with p_i(draft), otherwise draw the corrective token
                # from the residual — output distribution identical in
                # law to plain sampled decode, so temperature>0 rows get
                # the 1+k speedup without changing what they emit.
                # Under the draft model (ISSUE 20) q is the REAL
                # proposal distribution (acceptance min(1, p/q),
                # residual norm_+(p - q)); None keeps the n-gram
                # point-mass law
                q = h.qs.get(slot) if h.qs is not None else None
                toks, a = rejection_sample_tokens(
                    out[slot, :j + 1], d if j else None,
                    self.temperature, self._accept_rng,
                    q=(q[:j] if q is not None and j else None))
            else:
                a = longest_accepted_prefix(d, out[slot]) if j else 0
                toks = ((list(d[:a]) if j else [])
                        + [int(out[slot, a])])
            # draft-pool valid prefix (ISSUE 20): advance past exactly
            # the accepted tokens that MATCH what was fed through the
            # draft model — a mismatch tail re-feeds via catch-up
            if (self.draft_cache is not None
                    and slot in self._draft_chain):
                ch = self._draft_chain.pop(slot)
                m = 0
                while (m < min(a, ch.size)
                       and int(toks[m]) == int(ch[m])):
                    m += 1
                self.draft_cache.lengths[slot] = int(
                    self._draft_base[slot]) + m
            # commit: the last token's KV + a accepted drafts are now
            # context; the corrective/bonus token becomes the new last
            cache.lengths[slot] += a + 1
            self._last[slot] = np.int32(toks[-1])
            for tok in toks:
                self._record_token(req, int(tok))
                committed += 1
                if req.done:
                    break                  # eos/max_len: drop the tail
            if j:
                drafted += j
                accepted += a
                self.spec.observe(slot, req.rid, j, a)
            if h.ttr:
                _obs.serving_trace_span(
                    req, "spec_verify", h.ttr, replica=self.replica_id,
                    slot=slot, seq=len(req.tokens),
                    meta={"drafted": j, "accepted": int(a)})
        if sampled and drafted:
            _obs.serving_sample_accept(drafted, accepted)
        self._steps += 1
        _obs.serving_spec_verify(h.t0, out, n_slots, drafted, accepted,
                                 t1_ns=t1)
        alloc = cache.allocator
        _obs.serving_step(n_slots, self.max_batch, alloc.num_used,
                          alloc.num_usable)
        if self._dp_axis is not None:
            _obs.serving_dp_step(
                self.dp, h.mask.reshape(self.dp, -1).sum(axis=1))
        self._tp_observe()
        return committed

    # ---- tree speculation (ISSUE 20) ----
    def _tree_dispatch(self, mask, trees) -> Optional[InFlightStep]:
        """DISPATCH half of the TREE-speculation step: pack every
        masked row's token tree into one (B, T) chunk — node 0 the
        last sampled token (the root), topology as per-node parent
        indices turned into depths + ancestor matrices — and launch
        the ONE tree-mode verify forward (:meth:`_tree_fn`). Un-drafted
        rows ride the same program as a root-only tree and commit
        exactly their plain token; pad nodes hang off the root and are
        never referenced at commit. When NO masked row holds a tree,
        falls back to plain decode — the worst case is the baseline
        step, same as the linear path."""
        from ..serving.speculative import (TreeDraft, tree_depths,
                                           tree_ancestor_matrix)
        cache = self.cache
        trees = {s: t for s, t in trees.items()
                 if mask[s] and isinstance(t, TreeDraft) and t.size}
        if not trees:
            return self.decode_dispatch(mask)
        B, T = self.max_batch, self._tree_T
        chunk = np.zeros((B, T), np.int32)
        chunk[:, 0] = self._last
        depths = np.ones((B, T), np.int32)
        depths[:, 0] = 0
        anc = np.zeros((B, T, T), bool)
        anc[:, np.arange(T), np.arange(T)] = True
        anc[:, :, 0] = True             # pad nodes hang off the root
        for s, t in trees.items():
            n = t.tokens.size
            chunk[s, :n] = t.tokens
            depths[s, :n] = tree_depths(t.parents)
            anc[s, :n, :n] = tree_ancestor_matrix(t.parents)
        ctx_cap = cache.ctx_cap_pages(cache.pages_for(
            int(cache.lengths[mask].max()))) * cache.page_size
        _fault_point("tree_verify")
        t0 = _obs.generate_begin()
        with self.spans.span("engine.dispatch", kind="tree"):
            args = [self.params, jnp.asarray(chunk), cache.pool,
                    jnp.asarray(cache.block_tables.copy()),
                    jnp.asarray(cache.lengths.copy()), jnp.asarray(mask),
                    jnp.asarray(depths), jnp.asarray(anc)]
            if self.adapters is not None:
                args += [self.adapters.arrays,
                         jnp.asarray(self._aslot.copy())]
            out, rows = self._launch(self._tree_fn(ctx_cap, T), args,
                                     "tree", ctx_cap, T)
            _fault_point("dispatch")
            self.spans.count("decode_launches_total", 1)
            h = self._launched_handle(InFlightStep(
                "tree", mask, self._rids.copy(), self._seat.copy(), out,
                drafts=trees, t0=t0, ttr=_obs.serving_trace_now(),
                rows=rows))
        return h

    def _tree_commit(self, h: InFlightStep) -> int:
        """COMMIT half of the tree step: fetch the per-node targets,
        pick each row's longest accepted ROOT PATH (greedy:
        :func:`~paddle_tpu.serving.speculative.longest_accepted_path`;
        sampled: sequential point-mass rejection down the tree,
        :func:`~paddle_tpu.serving.speculative.tree_rejection_sample`),
        place exactly those nodes' KV via the jitted
        :meth:`_tree_commit_fn` (positions are the PRE-commit lengths,
        bit-identical to a linear verify of the path), then run the
        host bookkeeping. Rejected nodes were never placed, so
        rejection needs NO rollback of any kind; guard-skipped slots
        pass path_len 0 and their nodes route to the trash page."""
        _fault_point("commit")
        with self.spans.span("engine.wait", kind="tree"):
            if self.fused:
                _obs.serving_fused_latency("verify_flash_attn", h.t0,
                                           h.out)
            _fault_point("transfer")
            out = np.asarray(h.out)     # (B, T) argmax — or, sampled,
            #                             (B, T, V) per-node verify logits
        t1 = time.perf_counter_ns()
        rows = int(h.mask.sum())
        # the placement program sits between the two halves of the
        # bookkeeping, under a dispatch span of its own
        with self.spans.span("engine.commit", rows=rows):
            plans, place = self._tree_pick_paths(h, out)
        with self.spans.span("engine.dispatch", kind="tree_commit"):
            self.cache.pool = self._launch(
                self._tree_commit_fn(self._tree_T),
                [self.cache.pool, h.rows] + [jnp.asarray(a) for a in place],
                "tree_commit", width=self._tree_T)
        with self.spans.span("engine.commit", rows=rows):
            return self._tree_commit_host(h, out, plans, t1)

    def _tree_pick_paths(self, h: InFlightStep, out):
        """Each row's accepted root path: the commit plans and the
        placement program's host inputs (block tables, pre-commit
        lengths, path nodes, path lengths)."""
        cache = self.cache
        mask = h.mask
        from ..serving.speculative import (longest_accepted_path,
                                           tree_rejection_sample)
        sampled = self.temperature != 0.0
        B, T = self.max_batch, self._tree_T
        path_nodes = np.zeros((B, T), np.int32)
        path_len = np.zeros((B,), np.int32)
        base_len = cache.lengths.copy()
        plans = []
        for slot, req in enumerate(self._slots):
            if (req is None or not mask[slot]
                    or self._rids[slot] != h.rids[slot]
                    or self._seat[slot] != h.seats[slot]):
                continue
            t = h.drafts.get(slot)
            if t is None:
                # un-drafted row: exactly the plain token at the root
                if sampled:
                    z = out[slot, 0].astype(np.float64)
                    z /= self.temperature
                    z -= z.max()
                    p = np.exp(z)
                    p /= p.sum()
                    toks = [int(self._accept_rng.choice(p.size, p=p))]
                else:
                    toks = [int(out[slot, 0])]
                path, a = [0], 0
            elif sampled:
                path, toks, a = tree_rejection_sample(
                    t.tokens, t.parents, out[slot],
                    self.temperature, self._accept_rng)
            else:
                path, toks, a = longest_accepted_path(
                    t.tokens, t.parents, out[slot])
            path_nodes[slot, :len(path)] = path
            path_len[slot] = len(path)
            plans.append((slot, req, t, toks, a))
        # device placement FIRST, against the pre-commit tables and
        # lengths (retirement afterwards resets them for finished rows
        # — their already-placed rows die with their freed pages, the
        # contract every release relies on)
        return plans, (cache.block_tables, base_len, path_nodes, path_len)

    def _tree_commit_host(self, h: InFlightStep, out, plans,
                          t1: int) -> int:
        """The host bookkeeping of :meth:`_tree_commit`, after the
        placement program is launched."""
        cache = self.cache
        sampled = self.temperature != 0.0
        n_slots = committed = drafted = accepted = 0
        paths = []
        for slot, req, t, toks, a in plans:
            n_slots += 1
            # draft-pool valid prefix: same matched-chain rule as the
            # linear commit (the fed chain is the tree's top-1 spine;
            # an accepted path through a SIBLING diverges and re-feeds
            # from the divergence via catch-up)
            if (self.draft_cache is not None
                    and slot in self._draft_chain):
                ch = self._draft_chain.pop(slot)
                m = 0
                while (m < min(a, ch.size)
                       and int(toks[m]) == int(ch[m])):
                    m += 1
                self.draft_cache.lengths[slot] = int(
                    self._draft_base[slot]) + m
            cache.lengths[slot] += a + 1
            self._last[slot] = np.int32(toks[-1])
            for tok in toks:
                self._record_token(req, int(tok))
                committed += 1
                if req.done:
                    break              # eos/max_len: drop the tail
            if t is not None:
                drafted += t.size
                accepted += a
                paths.append(a + 1)
                self.spec.observe(slot, req.rid, t.size, a)
            if h.ttr:
                _obs.serving_trace_span(
                    req, "tree_verify", h.ttr, replica=self.replica_id,
                    slot=slot, seq=len(req.tokens),
                    meta={"nodes": t.size if t is not None else 0,
                          "accepted": int(a)})
        if sampled and drafted:
            _obs.serving_sample_accept(drafted, accepted)
        self._steps += 1
        _obs.serving_tree_verify(h.t0, out, n_slots, drafted, accepted,
                                 paths, t1_ns=t1)
        alloc = cache.allocator
        _obs.serving_step(n_slots, self.max_batch, alloc.num_used,
                          alloc.num_usable)
        if self._dp_axis is not None:
            _obs.serving_dp_step(
                self.dp, h.mask.reshape(self.dp, -1).sum(axis=1))
        self._tp_observe()
        return committed

    def step(self) -> bool:
        """Admit (FIFO), advance chunked prefill by one chunk, then
        advance every fully prefilled slot — one decode token each, or
        a drafted-and-verified run of tokens when speculation is on
        (``spec_k``). Returns False when no work remains (queue empty,
        all slots idle). Priority/budget/preemption scheduling composes
        the same pieces from
        :class:`~paddle_tpu.serving.ServingScheduler`."""
        self._admit()
        self.prefill_step()
        advance = (self.spec_step if self.spec is not None
                   else self.decode_step)
        if advance(self.ready_mask()) == 0:
            return bool(self._queue or self._pending
                        or self.cache.active.any())
        return bool(self._queue) or bool(self.cache.active.any())

    def run(self) -> None:
        """Drive steps until every submitted request finished."""
        while self.step():
            pass

    # ---- scheduler-facing state accessors ----
    @property
    def idle(self) -> bool:
        """True when nothing is queued, mid-prefill, or decoding — the
        state an external scheduler requires at attach time."""
        return not (self._queue or self._pending
                    or self.cache.active.any())

    def running_requests(self) -> List[GenerationRequest]:
        """Live requests currently holding slots (mid-prefill ones
        included) — the preemption-victim candidate set."""
        return [r for r in self._slots if r is not None]

    def queued_requests(self) -> List[GenerationRequest]:
        """Requests waiting in the engine's OWN FIFO queue (the
        scheduler-less :meth:`submit` path; empty under an attached
        :class:`~paddle_tpu.serving.ServingScheduler`, which owns its
        queues)."""
        return list(self._queue)

    def pending_prefills(self) -> Dict[int, tuple]:
        """``slot -> (request, remaining_tokens)`` for every admission
        whose sequence is not yet fully in the pool — the planner's
        prefill work items."""
        return {s: (ent[0], int(ent[1].size - ent[2]))
                for s, ent in self._pending.items()}

    def generate(self, prompts, max_new_tokens: int = 16) -> List[np.ndarray]:
        """Convenience batch API: submit all, run to completion, return
        each request's prompt+generated row (submission order)."""
        reqs = [self.submit(p, max_new_tokens=max_new_tokens)
                for p in prompts]
        self.run()
        return [r.output for r in reqs]

    def stats(self) -> Dict:
        s = self.cache.allocator.stats()
        s["steps"] = self._steps
        s["queued"] = len(self._queue)
        if self.mesh is not None:
            s["tp"] = self._tp
            if self._dp_axis is not None:
                s["dp"] = self.dp
            s["pool_bytes_per_shard"] = self.cache.pool_bytes_per_shard
        s["active_slots"] = int(self.cache.active.sum())
        s["pending_prefills"] = len(self._pending)
        if self.weight_bits is not None:
            s["weight_bits"] = self.weight_bits
        if self.fused:
            s["fused_kernels"] = True
        s["cow_copies"] = self.cache.cow_copies
        s["full_pool_used_peak"] = self.cache.allocator.peak_in_use
        if self.cache.window:
            wa = self.cache.window_allocator
            s["window_pool_used_peak"] = wa.peak_in_use
            s["window_pool_usable"] = wa.num_usable
        s.update(self.cache.state_stats())
        s.update(self.cache.latent_stats())
        if self.adapters is not None:
            s.update(self.adapters.stats())
        if getattr(self.cache, "host", None) is not None:
            s.update(self.cache.tier_stats())
        if self.cache.prefix is not None:
            s["prefix_evictions_total"] = \
                self.cache.prefix.evictions_total
        if self.spec is not None:
            s.update(self.spec.stats())
        if self.draft_cache is not None:
            s["draft_layers"] = self.draft_layers
            da = self.draft_cache.allocator
            s["draft_pool_pages_used"] = da.num_used
            s["draft_pool_pages_usable"] = da.num_usable
        if self.spec_tree is not None:
            s["tree_width"], s["tree_depth"] = self.spec_tree
            s["tree_nodes"] = self._tree_T - 1
        # the step's phases as totals, and the counters fed where the
        # work happens (observability/spans.py)
        s.update(self.spans.snapshot())
        return s
