"""Llama-family causal LM, TPU-first.

Capability target: the reference trains Llama-style models through fleet
hybrid parallel (reference: python/paddle/distributed/fleet/meta_parallel/,
mpu/mp_layers.py VocabParallelEmbedding:49 / ColumnParallelLinear:336 /
RowParallelLinear:543; fused kernels paddle/phi/kernels/fusion/
fused_rope_kernel.cu, fused_layernorm, flash_attn_kernel.cu).

TPU-native design (NOT a translation):
- Parameters are a flat pytree of jnp arrays; decoder layers are *stacked*
  along a leading axis and executed with ``lax.scan`` so XLA compiles one
  layer body regardless of depth.
- Parallelism is declared, not programmed: every leaf has a
  ``PartitionSpec`` over mesh axes ("dp", "fsdp", "tp"). Megatron TP =
  sharding the head/ffn axes by "tp"; ZeRO-3 = sharding the other weight
  axis by "fsdp"; Megatron sequence-parallel = sharding the residual
  stream's seq axis by "tp" between blocks. XLA GSPMD inserts the
  all-gathers / reduce-scatters that the reference's mp_ops.py
  (_c_identity:91, _mp_allreduce:293) and sequence_parallel_utils.py issue
  by hand.
- RoPE + RMSNorm + SwiGLU computed in bf16 with fp32 accumulation; flash
  attention uses the Pallas kernel on TPU (ops/pallas/flash_attention.py)
  and a fused-softmax jnp path elsewhere.
"""
from __future__ import annotations

import dataclasses
import math
import re
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops.pallas import flash_attention as _fa
from . import moe as _moe


@dataclasses.dataclass(frozen=True)
class YarnRope:
    """YaRN rotary scaling (the published ``rope_parameters`` keys of the
    same names): below the ``beta_fast`` correction dimension a frequency
    is kept, above the ``beta_slow`` one it is divided by ``factor``, a
    linear ramp blends the two between, and cos and sin are both
    multiplied by ``attention_factor`` (``0.1 ln(factor) + 1`` when the
    file gives none)."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None


#: the kinds of layer a ``layer_pattern`` may name. ``sliding`` and ``full``
#: are attention + MLP pairs (the decoder ``_block``); the next three are
#: the single-part layers of a hybrid model (``models/hybrid.py``): ONE
#: mixer or ONE feed-forward part under its own pre-norm and residual.
#: ``latent`` is an attention + MLP pair whose attention is latent
#: attention (``models/latent.py``, :class:`LatentConfig`).
LAYER_KINDS = ("sliding", "full", "mamba2", "attention", "experts", "latent")
HYBRID_KINDS = LAYER_KINDS[2:5]

#: what a layer of each kind keeps between calls of a serving program: a
#: paged KV pool (``full``: block tables and admission follow it;
#: ``sliding``: the window's ring), a slot of recurrent ``state``, or
#: nothing; a ``latent`` layer a paged pool of LATENTS, one row of
#: :attr:`LatentConfig.row` numbers a token with no head axis, under the
#: block tables a ``full`` pool would have. The cache manager and the
#: engine build pools and programs from :meth:`LlamaConfig.cache_layers`,
#: not from the kinds' names.
CACHE_OF_KIND = {"sliding": "sliding", "full": "full", "attention": "full",
                 "mamba2": "state", "experts": None, "latent": "latent"}


@dataclasses.dataclass(frozen=True)
class LatentConfig:
    """Latent attention's widths (the published ``deepseek_v3`` / ``kimi_k2``
    keys in brackets). A head's query and key are ``nope_dim + rope_dim``
    wide and its value ``v_dim``, so ``LlamaConfig.hd`` names no head size
    of such a config; what a token keeps a layer is :attr:`row` numbers.
    ``mscale``: YaRN's ``0.1 mscale_all_dim ln(factor) + 1``, whose square
    multiplies the softmax scale (1.0: none)."""
    q_rank: int = 48              # q_lora_rank
    kv_rank: int = 32             # kv_lora_rank
    nope_dim: int = 16            # qk_nope_head_dim
    rope_dim: int = 8             # qk_rope_head_dim
    v_dim: int = 16               # v_head_dim
    mscale: float = 1.0

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def row(self) -> int:
        """The normed latent and the one rotated key all heads share."""
        return self.kv_rank + self.rope_dim

    @property
    def row_lanes(self) -> int:
        """:attr:`row` in whole 128-lane tiles: how wide the pool lays a
        row out (the chip's layout pads the last axis to tiles whatever the
        shape says, and a page is copied out of HBM in whole tiles), zeros
        in the lanes past ``row``."""
        return -(-self.row // 128) * 128

    @property
    def scale(self) -> float:
        return self.qk_dim ** -0.5 * self.mscale * self.mscale


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """The widths of a hybrid model's single-part layers (the published
    ``nemotron_h`` keys in brackets). ``LlamaConfig.moe`` carries the
    router's width and ``top_k``; how many of the routed experts THIS
    process holds is a property of the parameters (the expert stacks'
    expert axis and ``first_expert``), not of the config."""
    ssm_heads: int = 8            # mamba_num_heads
    ssm_head_dim: int = 8         # mamba_head_dim
    ssm_groups: int = 2           # n_groups
    ssm_state: int = 16           # ssm_state_size
    conv_kernel: int = 4          # conv_kernel
    chunk_size: int = 128         # chunk_size: the scan's sub-chunk
    latent_size: int = 32         # moe_latent_size
    expert_size: int = 48         # moe_intermediate_size
    shared_size: int = 64         # moe_shared_expert_intermediate_size
    routed_scale: float = 1.0     # routed_scaling_factor

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = False
    remat: bool = True
    # remat policy: "nothing" = recompute all (min memory), "attn" = save
    # attention outputs (skip the expensive flash recompute in backward),
    # "dots" = save all matmul outputs (max speed, max memory)
    remat_policy: str = "nothing"
    # rms_norm/rope/swiglu implementation: "xla" (default) = jnp left to
    # XLA fusion; "auto" = Pallas kernels (ops/pallas/fused.py) on TPU;
    # "pallas" forces the kernels (interpret mode off-TPU — tests).
    # "pallas" has never run on a chip (it compiles by AOT); what flips
    # the default is the train cell of BENCHMARK.json on each side, run
    # by the driver (ROADMAP D3).
    fused_kernels: str = "xla"
    moe: Optional["_moe.MoEConfig"] = None  # experts replace the dense MLP
    # layers that differ by position: ONE period of attention kinds
    # ("sliding" / "full"), repeated num_layers / len(layer_pattern)
    # times. Weights stay stacked (L, ...); the trunk and the serving
    # forwards scan over periods with the period's layers unrolled
    # inside. None is a period of one full layer: the plain decoder.
    layer_pattern: Optional[Tuple[str, ...]] = None
    # keys a sliding layer's query sees, ITSELF INCLUDED: i - j < window
    sliding_window: Optional[int] = None
    # rotary parameters by layer kind: sliding layers rotate by
    # ``rope_theta_sliding`` (None: ``rope_theta``) unscaled, full
    # layers by ``rope_theta`` under ``yarn`` when it is given
    rope_theta_sliding: Optional[float] = None
    yarn: Optional[YarnRope] = None
    # a hybrid model: the period names "mamba2" / "attention" / "experts"
    # layers, each ONE part (models/hybrid.py); weights are a stack per
    # kind, ``params["layers"][kind]``. Its attention applies no rotary
    # embedding, and ``moe`` carries the router's width and top_k.
    hybrid: Optional[HybridConfig] = None
    # latent attention: the period is ("latent",), ``rope_theta`` / ``yarn``
    # rotate ``latent.rope_dim`` lanes, and the parameters, the no-cache
    # forward and the serving layer bodies are models/latent.py's
    latent: Optional[LatentConfig] = None
    # leading layers whose MLP is dense at ``intermediate_size`` although
    # ``moe`` is set (``first_k_dense_replace``): their weights are a stack
    # of their own, ``params["dense_layers"]``, run as a prologue before the
    # scan over the expert layers ``params["layers"]``
    dense_layers: int = 0

    def __post_init__(self):
        pat = self.layer_pattern
        if (self.latent is not None) != (pat == ("latent",)):
            raise ValueError(
                f"layer_pattern={pat!r}: a config with latent attention "
                f"(LlamaConfig.latent) has the period ('latent',), and no "
                f"other config names that kind")
        if self.latent is not None and self.moe is None:
            raise ValueError("latent attention: moe must describe the "
                             "expert layers (models/latent.py)")
        if self.dense_layers and (
                self.latent is None or self.moe is None
                or not 0 < self.dense_layers < self.num_layers):
            raise ValueError(
                f"dense_layers={self.dense_layers}: leading dense layers "
                f"are served before the expert layers of a latent-attention "
                f"config (0 < dense_layers < num_layers, moe set); for "
                f"other layer kinds cfg.moe switches every MLP at once")
        if pat is None:
            if self.hybrid is not None:
                raise ValueError("hybrid: layer_pattern must name the "
                                 "period's layer kinds")
            return
        if not pat or any(k not in LAYER_KINDS for k in pat):
            raise ValueError(
                f"layer_pattern={pat!r}: a period names each layer one of "
                f"{LAYER_KINDS}")
        single = sum(k in HYBRID_KINDS for k in pat)
        if single not in (0, len(pat)) or bool(single) != (
                self.hybrid is not None):
            raise ValueError(
                f"layer_pattern={pat!r}: the kinds {HYBRID_KINDS} are a "
                f"hybrid model's (LlamaConfig.hybrid) and do not mix with "
                f"'sliding' / 'full'")
        if "experts" in pat and self.moe is None:
            raise ValueError("layer_pattern names expert layers: moe must "
                             "give the router's width and top_k")
        if self.num_layers % len(pat):
            raise ValueError(
                f"num_layers={self.num_layers} is not a whole number of "
                f"periods of {len(pat)} layers")
        if "sliding" in pat and not self.sliding_window:
            raise ValueError(
                "layer_pattern names sliding layers: sliding_window "
                "must say how many keys they see")

    @property
    def hd(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def period(self) -> Tuple[str, ...]:
        """The kinds of one period's layers, in order."""
        return tuple(self.layer_pattern) if self.layer_pattern else ("full",)

    def window_of(self, kind: str) -> Optional[int]:
        return self.sliding_window if kind == "sliding" else None

    def kind_layers(self, kind: str) -> int:
        """Layers of this kind in the whole model."""
        return (self.num_layers // len(self.period)) * self.period.count(kind)

    def cache_layers(self) -> Dict[str, int]:
        """What the model asks of a cache manager: kind of cache
        (:data:`CACHE_OF_KIND`) -> how many of its layers need one."""
        out: Dict[str, int] = {}
        for kind in dict.fromkeys(self.period):
            c = CACHE_OF_KIND[kind]
            if c is not None:
                out[c] = out.get(c, 0) + self.kind_layers(kind)
        return out

    # ---- presets (sizes follow the public Llama-2 family) ----
    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)

    @staticmethod
    def llama2_13b(**kw) -> "LlamaConfig":
        return LlamaConfig(hidden_size=5120, intermediate_size=13824,
                           num_layers=40, num_heads=40, num_kv_heads=40, **kw)

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """Small config for tests / dryruns."""
        d = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                 num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
                 dtype=jnp.float32, remat=False)
        d.update(kw)
        return LlamaConfig(**d)

    def num_params(self) -> int:
        if self.hybrid is not None or self.latent is not None:
            raise ValueError("num_params: count the leaves of a hybrid or "
                             "latent-attention model (param_shapes of "
                             "models/hybrid.py, models/latent.py)")
        h, i, v, L = (self.hidden_size, self.intermediate_size,
                      self.vocab_size, self.num_layers)
        hd, nh, nkv = self.hd, self.num_heads, self.num_kv_heads
        if self.moe is None:
            mlp = 3 * h * i
        else:
            mlp = self.moe.num_experts * 3 * h * i + h * self.moe.num_experts
        per_layer = (h * nh * hd + 2 * h * nkv * hd + nh * hd * h  # attn
                     + mlp + 2 * h)                                # 2 rmsnorm
        emb = v * h * (1 if self.tie_embeddings else 2)
        return L * per_layer + emb + h


# ---------------- init ----------------
def init_params(key: jax.Array, cfg: LlamaConfig) -> Dict[str, Any]:
    if cfg.hybrid is not None:
        from . import hybrid as _hybrid
        return _hybrid.init_params(key, cfg)
    if cfg.latent is not None:
        from . import latent as _latent
        return _latent.init_params(key, cfg)
    h, i, v, L = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                  cfg.num_layers)
    hd, nh, nkv = cfg.hd, cfg.num_heads, cfg.num_kv_heads
    k = jax.random.split(key, 8)
    std = 0.02

    def norm(kk, shape, fan_in=None):
        s = std if fan_in is None else 1.0 / math.sqrt(fan_in)
        return (jax.random.normal(kk, shape, jnp.float32) * s).astype(cfg.dtype)

    layers = {
        "wq": norm(k[1], (L, h, nh * hd), fan_in=h),
        "wk": norm(k[2], (L, h, nkv * hd), fan_in=h),
        "wv": norm(k[3], (L, h, nkv * hd), fan_in=h),
        "wo": norm(k[4], (L, nh * hd, h), fan_in=nh * hd),
        "attn_norm": jnp.ones((L, h), cfg.dtype),
        "mlp_norm": jnp.ones((L, h), cfg.dtype),
    }
    if cfg.moe is None:
        layers.update({
            "wg": norm(k[5], (L, h, i), fan_in=h),
            "wu": norm(k[6], (L, h, i), fan_in=h),
            "wd": norm(k[7], (L, i, h), fan_in=i),
        })
    else:
        E = cfg.moe.num_experts
        layers.update({
            "moe_gate": (jax.random.normal(k[5], (L, h, E), jnp.float32) /
                         math.sqrt(h)),
            "moe_wg": norm(k[6], (L, E, h, i), fan_in=h),
            "moe_wu": norm(jax.random.fold_in(k[6], 1), (L, E, h, i),
                           fan_in=h),
            "moe_wd": norm(k[7], (L, E, i, h), fan_in=i),
        })
    params = {
        "embed": norm(k[0], (v, h)),
        "final_norm": jnp.ones((h,), cfg.dtype),
        "layers": layers,
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(jax.random.fold_in(key, 99), (h, v), fan_in=h)
    return params


def param_specs(cfg: LlamaConfig) -> Dict[str, Any]:
    """PartitionSpecs per leaf over mesh axes ("dp","fsdp","tp").

    TP shards the head/ffn dimension; fsdp (ZeRO-3) shards the opposite
    dimension; norms/embeddings replicate over tp and shard vocab/hidden
    over fsdp. (reference semantics: mp_layers.py Column/RowParallelLinear
    + sharding stage-3 group_sharded_stage3.py — here a pure declaration.)
    """
    layers = {
        "wq": P(None, "fsdp", "tp"),
        "wk": P(None, "fsdp", "tp"),
        "wv": P(None, "fsdp", "tp"),
        "wo": P(None, "tp", "fsdp"),
        "attn_norm": P(None, None),
        "mlp_norm": P(None, None),
    }
    if cfg.moe is None:
        layers.update({
            "wg": P(None, "fsdp", "tp"),
            "wu": P(None, "fsdp", "tp"),
            "wd": P(None, "tp", "fsdp"),
        })
    else:
        layers.update({
            "moe_gate": P(None, None, None),
            "moe_wg": P(None, "ep", "fsdp", "tp"),
            "moe_wu": P(None, "ep", "fsdp", "tp"),
            "moe_wd": P(None, "ep", "tp", "fsdp"),
        })
    return {
        "embed": P("fsdp", "tp"),
        "final_norm": P(None),
        "layers": layers,
        **({} if cfg.tie_embeddings else {"lm_head": P("fsdp", "tp")}),
    }


# ---------------- serving tensor parallelism ----------------
#
# The serving engine shards decode/prefill/verify over a 1-D tp mesh
# (ISSUE 7 / ROADMAP 1). Unlike the training specs above (Megatron
# column->ROW split, psums inserted by GSPMD), serving TP is built for
# BIT-IDENTITY with the single-chip paged path: every weight matmul is
# COLUMN-parallel (output dim sharded over tp) and the activation is
# all-gathered to full width before each contraction. An all-gather is
# an exact concatenation and a column-subset matmul computes each output
# element with the full, identically-ordered contraction — whereas a
# row-parallel psum of partial matmuls reassociates the reduction and
# drifts in the last mantissa bits. Decode is HBM-bound (PERF.md
# section 5): the win is weight + KV BYTES per shard (all seven layer matrices and
# lm_head shard 1/tp), and the (B, ·) decode activations the gathers
# move are noise next to that, so buying exactness with two extra
# gathers per layer costs ~nothing on the hot path.

#: name-regex -> rule for :func:`match_partition_rules` ("last" shards
#: the final axis over tp; "replicate" keeps the leaf whole). Quantized
#: serving weights ride along on the SAME rule as their matrix: the
#: per-channel int8 scale ``(L, out)`` and the per-GROUP int4 scale
#:``(L, G, out)`` (ISSUE 11) both end in the output axis the rule
#: shards, so a ``weight_bits=4`` tree partitions with zero extra
#: rules — and :func:`_expand_kv_heads` applies the GQA replication
#: transform to ``wk_scale``/``wv_scale`` exactly as to ``wk``/``wv``
#: (per-head column blocks, group axis untouched). Coverage gated in
#: tests/test_lowbit_decode.py.
#:
#: MoE leaves (ISSUE 17): the router ``moe_gate`` replicates (every
#: shard routes identically — the bit-identity precondition for
#: expert-parallel dispatch), while the expert stacks ``moe_wg`` /
#: ``moe_wu`` / ``moe_wd`` (``(L, E, h, i)`` / ``(L, E, i, h)``) shard
#: their EXPERT axis over dp (expert parallelism — each dp shard owns
#: ``E/dp`` experts) and their output columns over tp, the same
#: column-parallel trick as the dense matrices. On a 1-D mesh
#: (``dp_axis=None``) the expert axis stays whole and only the column
#: split applies.
SERVING_TP_RULES = (
    (r"layers/moe_gate$", "replicate"),
    (r"layers/(moe_wg|moe_wu|moe_wd)(_scale)?$", "experts"),
    (r"layers/(wq|wk|wv|wo|wg|wu|wd)(_scale)?$", "last"),
    (r"lm_head(_scale)?$", "last"),
    (r"", "replicate"),
)


def match_partition_rules(params, rules=SERVING_TP_RULES, axis="tp",
                          dp_axis=None):
    """Regex partition rules over '/'-joined leaf names -> a pytree of
    PartitionSpecs (the fmengine/EasyLM ``match_partition_rules`` idiom;
    see SNIPPETS [3]). First matching rule wins; scalars replicate.
    ``dp_axis`` names the mesh axis the "experts" rule shards the
    expert dimension over (None = replicate the experts, the 1-D
    mesh)."""
    def spec(path, leaf):
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        for pat, kind in rules:
            if re.search(pat, name) is None:
                continue
            if kind == "replicate" or leaf.ndim == 0:
                return P()
            if kind == "last":
                return P(*([None] * (leaf.ndim - 1) + [axis]))
            if kind == "experts":
                # (L, E, ..., out): experts over dp, columns over tp
                return P(None, dp_axis,
                         *([None] * (leaf.ndim - 3) + [axis]))
            raise ValueError(f"unknown partition rule kind {kind!r}")
        raise ValueError(f"no partition rule matched param {name!r}")
    return jax.tree_util.tree_map_with_path(spec, params)


def validate_serving_tp(cfg: LlamaConfig, tp: int) -> int:
    """Divisibility gate for serving TP; returns PER-SHARD kv heads.

    Raises a LOUD error instead of mis-sharding: ``num_heads % tp != 0``
    would split a head across shards (rope/softmax are per-head), and a
    ``num_kv_heads`` that neither divides into ``tp`` shards nor is a
    divisor of ``tp`` has no consistent query->kv mapping per shard.
    GQA with ``num_kv_heads < tp`` takes the KV-REPLICATION path: each
    shard stores exactly one kv head (its local query heads' group
    head), i.e. the pool's head extent expands to ``tp`` with each kv
    head repeated ``tp/num_kv_heads`` times — page bytes per shard are
    ``1/num_kv_heads`` of the pool instead of ``1/tp``."""
    if cfg.moe is not None:
        raise ValueError(
            "serving TP does not support MoE configs yet — use "
            "validate_serving_mesh / a 2-D serving_mesh(tp, dp) for "
            "expert-parallel MoE decode (ISSUE 17)")
    return _validate_serving_heads(cfg, tp)


def _validate_serving_heads(cfg: LlamaConfig, tp: int) -> int:
    """The head-divisibility half of the serving-mesh gate (shared by
    :func:`validate_serving_tp` and :func:`validate_serving_mesh`);
    returns per-shard kv heads."""
    if tp < 1:
        raise ValueError(f"serving tp must be >= 1, got {tp}")
    if cfg.latent is not None:
        raise ValueError(
            "serving tp/mesh is not supported on a latent-attention config: "
            "the pool of latents has no head axis to shard (heads sharded "
            "over a replicated latent is not built)")
    if cfg.num_heads % tp:
        raise ValueError(
            f"num_heads={cfg.num_heads} is not divisible by tp={tp}: "
            f"attention shards at head granularity (rope + softmax are "
            f"per-head); a silent mis-shard would split a head across "
            f"chips. Pick tp from the divisors of num_heads.")
    if cfg.num_kv_heads % tp == 0:
        return cfg.num_kv_heads // tp
    if tp % cfg.num_kv_heads == 0:
        return 1                      # replication path: 1 kv head/shard
    raise ValueError(
        f"num_kv_heads={cfg.num_kv_heads} is neither a multiple of "
        f"tp={tp} (head-sharded KV pools) nor a divisor of it (the "
        f"replicated-KV GQA path, one kv head per shard); no consistent "
        f"per-shard query->kv mapping exists. Pick tp so that "
        f"num_kv_heads % tp == 0 or tp % num_kv_heads == 0.")


def validate_serving_mesh(cfg: LlamaConfig, tp: int, dp: int = 1) -> int:
    """Divisibility gate for the 2-D tp x dp serving mesh (ISSUE 17);
    returns PER-SHARD kv heads (the tp half — identical contract to
    :func:`validate_serving_tp`).

    The dp axis splits the step programs' BATCH, so it imposes no
    weight-divisibility constraint of its own on dense configs — the
    engine separately requires ``max_batch % dp == 0``. MoE configs ARE
    accepted here (unlike ``validate_serving_tp``): expert parallelism
    shards the expert stacks' E axis over dp and their output columns
    over tp, so ``num_experts % dp``, ``intermediate_size % tp`` and
    ``hidden_size % tp`` must all divide — anything else raises LOUDLY
    instead of mis-sharding an expert across shards."""
    if dp < 1:
        raise ValueError(f"serving dp must be >= 1, got {dp}")
    nkv_shard = _validate_serving_heads(cfg, tp)
    if cfg.moe is not None:
        E = cfg.moe.num_experts
        if E % dp:
            raise ValueError(
                f"num_experts={E} is not divisible by dp={dp}: expert "
                f"parallelism places whole experts (E/dp per dp shard); "
                f"a split expert has no owner for its tokens. Pick dp "
                f"from the divisors of num_experts.")
        if cfg.intermediate_size % tp or cfg.hidden_size % tp:
            raise ValueError(
                f"MoE expert matrices cannot column-shard: "
                f"intermediate_size={cfg.intermediate_size} and "
                f"hidden_size={cfg.hidden_size} must both divide "
                f"tp={tp} (the experts' gate/up columns and down-proj "
                f"output columns shard over tp).")
    return nkv_shard


def _expand_kv_heads(w: jax.Array, hd: int, rep: int) -> jax.Array:
    """Repeat the per-head column blocks of a K/V projection (or its
    quant scale) ``rep`` times: (..., nkv*hd) -> (..., nkv*rep*hd). The
    GQA replication transform — after it, the uniform "head axis shards
    over tp" machinery applies with every shard holding one kv head."""
    nkv = w.shape[-1] // hd
    w = w.reshape(w.shape[:-1] + (nkv, 1, hd))
    w = jnp.broadcast_to(w, w.shape[:-3] + (nkv, rep, hd))
    return w.reshape(w.shape[:-3] + (nkv * rep * hd,))


def shard_serving_params(params: Dict[str, Any], cfg: LlamaConfig, mesh,
                         axis: str = "tp"):
    """Place a (possibly weight-quantized) serving param tree on the
    serving mesh — 1-D tp or 2-D tp x dp (ISSUE 17): validate
    divisibility, apply the GQA KV-replication expand when
    ``num_kv_heads < tp``, match the regex partition rules, and
    device_put every leaf. Returns ``(placed_params, spec_pytree)`` —
    the specs double as the ``shard_map`` in_specs of the serving
    programs (inference/predictor.py). On the 2-D mesh dense weights
    replicate across dp (their specs name only the tp axis) and the MoE
    expert stacks shard E over the dp axis."""
    tp = int(mesh.shape[axis])
    dp_axis = next((a for a in mesh.axis_names if a != axis), None)
    dp = int(mesh.shape[dp_axis]) if dp_axis is not None else 1
    nkv_shard = validate_serving_mesh(cfg, tp, dp)
    if nkv_shard * tp != cfg.num_kv_heads:        # replication path
        rep = tp // cfg.num_kv_heads
        layers = dict(params["layers"])
        for nm in ("wk", "wv", "wk_scale", "wv_scale"):
            if nm in layers:
                layers[nm] = _expand_kv_heads(layers[nm], cfg.hd, rep)
        params = {**params, "layers": layers}
    specs = match_partition_rules(params, axis=axis, dp_axis=dp_axis)
    placed = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)
    return placed, specs


def adapter_partition_specs(cfg: LlamaConfig, mesh,
                            axis: Optional[str] = None) -> Dict[str, P]:
    """Partition specs for an adapter-pool factor dict (ISSUE 14) —
    the LoRA sibling of :data:`SERVING_TP_RULES`, kept next to them so
    the column-split bit-identity argument lives in one place.

    The pool arrays are ``(L, slots, in, r)`` ``A`` factors /
    ``(L, slots, r, out)`` ``B`` factors / ``(slots,)`` scales. ``B``
    factors shard their OUTPUT axis over tp — the same axis the base
    ``wq``/``wo`` shard under the "last" rule — while ``A`` factors and
    scales replicate: each shard then computes its own delta columns
    ``(x @ A_i) @ B_i[:, local]`` with the full, identically ordered
    rank-r contraction, so the adapter term is bit-identical to
    single-chip by the same exact-concat argument as the column-split
    weights. Validates the same divisibility contract the base rules
    assume (q width ``nh*hd`` and o width ``hidden`` both divide tp)."""
    ax = axis or ("tp" if "tp" in mesh.axis_names else mesh.axis_names[0])
    if ax not in mesh.axis_names:
        raise ValueError(
            f"adapter_partition_specs: axis {ax!r} is not an axis of "
            f"the serving mesh {mesh.axis_names}")
    tp = int(mesh.shape[ax])
    h, dq = cfg.hidden_size, cfg.num_heads * cfg.hd
    if dq % tp or h % tp:
        raise ValueError(
            f"adapter factors cannot column-shard: B-factor output "
            f"axes (q: {dq}, o: {h}) must divide tp={tp} — the "
            f"adapter term shards with the base matrices")
    return {"aq": P(), "ao": P(),
            "bq": P(None, None, None, ax),
            "bo": P(None, None, None, ax),
            "scale": P()}


# ---------------- building blocks ----------------
def _pallas_fused(cfg: "LlamaConfig") -> bool:
    if cfg.fused_kernels == "pallas":
        return True
    return cfg.fused_kernels == "auto" and _fa.available()


def rms_norm(x: jax.Array, w: jax.Array, eps: float,
             pallas: bool = False) -> jax.Array:
    if pallas:
        from ..ops.pallas import fused as _pf
        return _pf.rms_norm(x, w, eps)
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def rope_tables(seq_len: int, hd: int, theta: float,
                dtype=jnp.float32, yarn: Optional[YarnRope] = None
                ) -> Tuple[jax.Array, jax.Array]:
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    scale = None
    if yarn is not None:
        # the dimension at which ``r`` rotations fit the original context
        def dim_of(r):
            return hd * math.log(yarn.original_max_position_embeddings
                                 / (r * 2 * math.pi)) / (2 * math.log(theta))
        low = max(math.floor(dim_of(yarn.beta_fast)), 0)
        high = min(math.ceil(dim_of(yarn.beta_slow)), hd - 1)
        ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        inv = inv / yarn.factor * ramp + inv * (1.0 - ramp)
        scale = (yarn.attention_factor if yarn.attention_factor is not None
                 else 0.1 * math.log(yarn.factor) + 1.0)
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)                      # (S, hd/2)
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    return cos.astype(dtype), sin.astype(dtype)


def rope_tables_by_kind(cfg: LlamaConfig, seq_len: int
                        ) -> Dict[str, Tuple[jax.Array, jax.Array]]:
    """cos and sin for each kind of layer in the config's period."""
    out = {}
    for kind in dict.fromkeys(cfg.period):
        if kind == "sliding":
            out[kind] = rope_tables(seq_len, cfg.hd,
                                    cfg.rope_theta_sliding or cfg.rope_theta)
        elif kind == "latent":
            # the rotary width is the shared key's, not a head's
            out[kind] = rope_tables(seq_len, cfg.latent.rope_dim,
                                    cfg.rope_theta, yarn=cfg.yarn)
        else:
            out[kind] = rope_tables(seq_len, cfg.hd, cfg.rope_theta,
                                    yarn=cfg.yarn)
    return out


def period_stack(tree, period_len: int):
    """Stacked layer leaves ``(L, ...)`` as ``(L / p, p, ...)``, for a
    scan over periods; a period of one is the tree itself."""
    if period_len == 1:
        return tree
    return jax.tree.map(
        lambda a: a.reshape((a.shape[0] // period_len, period_len)
                            + a.shape[1:]), tree)


def period_layer(tree, j: int, period_len: int):
    """Layer ``j`` of one period's slice of :func:`period_stack`."""
    if period_len == 1:
        return tree
    return jax.tree.map(lambda a: a[j], tree)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, hd); rotate-half formulation (reference:
    paddle/phi/kernels/fusion/fused_rope_kernel.cu — here left to XLA
    fusion, which folds it into the surrounding elementwise graph)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def _attention(q, k, v, causal=True, mesh_axes=None, window=None):
    """(B,S,H,hd) attention; Pallas flash where
    :func:`~paddle_tpu.ops.pallas.flash_attention.flash_eligible` says
    so, fused jnp elsewhere. Under a mesh the kernel runs per shard
    (batch over the data axes, heads over tp): Mosaic kernels are not
    partitioned by GSPMD, and attention needs no cross-shard traffic.
    A sliding ``window`` takes the jnp path: the flash kernel has no
    lower bound on its keys."""
    if window is None and _fa.flash_eligible(q.shape[1], q.shape[-1]):
        attn = partial(_fa.flash_attention, causal=causal)
        if mesh_axes is not None:
            mesh = mesh_axes["mesh"]
            spec = P(mesh_axes["data"], None, mesh_axes["tp"], None)
            # map over every axis not already manual: the kernel lowers
            # only with none left to GSPMD. Inside a pipeline stage
            # (manual over pp) the enclosing mesh is the one to name.
            ctx = jax.sharding.get_abstract_mesh()
            attn = jax.shard_map(
                attn, mesh=ctx if ctx.manual_axes else mesh,
                axis_names=set(mesh.axis_names) - set(ctx.manual_axes),
                in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False)
        return attn(q, k, v)
    return _attention_jnp(q, k, v, causal, window)


def _attention_jnp(q, k, v, causal=True, window=None):
    """The fused-softmax jnp attention: the path off the chip, and the
    reference the flash kernel is checked against on it. ``window``:
    query i sees keys j with ``i - j < window`` only."""
    b, sq, h, hd = q.shape
    hk = k.shape[2]
    if hk != h:
        k = jnp.repeat(k, h // hk, axis=2)
        v = jnp.repeat(v, h // hk, axis=2)
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sq), bool))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((sq, sq), bool), -window)
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _block(x, lp, cos, sin, cfg: LlamaConfig, mesh_axes, attn_axes=None,
           window=None):
    """One decoder layer. lp = per-layer params (no leading L axis).
    ``attn_axes``: mesh axes for the per-shard flash kernel alone, for a
    caller that places activations itself (the pipeline stages);
    defaults to ``mesh_axes``. ``window``: the layer's sliding window."""
    B, S, H = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd

    cp = mesh_axes.get("cp") if mesh_axes else None
    # seq-dim sharding of the residual stream: the cp axis when context
    # parallel is on, else the tp axis (Megatron-SP)
    seq_axis = cp if cp else (mesh_axes["tp"] if mesh_axes else None)

    def sp(t):
        if mesh_axes is None:
            return t
        return jax.lax.with_sharding_constraint(
            t, NamedSharding(mesh_axes["mesh"],
                             P(mesh_axes["data"], seq_axis, None)))

    def tpact(t):  # inside-block activations: heads/ffn sharded over tp
        if mesh_axes is None:
            return t
        return jax.lax.with_sharding_constraint(
            t, NamedSharding(mesh_axes["mesh"],
                             P(mesh_axes["data"], cp, mesh_axes["tp"])))

    fused = _pallas_fused(cfg)
    h1 = rms_norm(x, lp["attn_norm"], cfg.rms_eps, pallas=fused)
    q = tpact(h1 @ lp["wq"]).reshape(B, S, nh, hd)
    k = tpact(h1 @ lp["wk"]).reshape(B, S, nkv, hd)
    v = tpact(h1 @ lp["wv"]).reshape(B, S, nkv, hd)
    # rope stays XLA even when fused=True: it folds into the qkv matmul
    # epilogue for free, while the pallas rope kernel needs its halves
    # split/concatenated outside the kernel (extra HBM passes)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if cp:
        from ..distributed.fleet.meta_parallel.context_parallel import (
            ring_attention)
        spec = P(mesh_axes["data"], cp, mesh_axes["tp"], None)
        attn = jax.shard_map(
            partial(ring_attention, axis_name=cp, causal=True),
            mesh=mesh_axes["mesh"], in_specs=(spec, spec, spec),
            out_specs=spec, check_vma=False)
        o = attn(q, k, v).reshape(B, S, nh * hd)
    else:
        o = _attention(q, k, v, causal=True,
                       mesh_axes=attn_axes or mesh_axes,
                       window=window).reshape(B, S, nh * hd)
    from jax.ad_checkpoint import checkpoint_name
    o = checkpoint_name(o, "attn_out")
    x = sp(x + o @ lp["wo"])

    h2 = rms_norm(x, lp["mlp_norm"], cfg.rms_eps, pallas=fused)
    if cfg.moe is not None:
        ff, losses = _moe.moe_ffn(
            h2, {"w_gate": lp["moe_gate"], "wg": lp["moe_wg"],
                 "wu": lp["moe_wu"], "wd": lp["moe_wd"]},
            cfg.moe, mesh_axes=mesh_axes)
        aux = losses["aux_loss"] + losses["z_loss"]
    else:
        g = tpact(h2 @ lp["wg"])
        u = tpact(h2 @ lp["wu"])
        if fused:
            from ..ops.pallas import fused as _pf
            ff = _pf.swiglu(g, u) @ lp["wd"]
        else:
            ff = (jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)
                  * u) @ lp["wd"]
        aux = jnp.float32(0.0)
    return sp(x + ff), aux


def _trunk(params, tokens, cfg: LlamaConfig, mesh_axes=None):
    """-> (final-norm hidden (B,S,H), summed MoE aux loss scalar)."""
    if cfg.latent is not None:
        raise ValueError(
            "training a latent-attention config is not supported: its "
            "expert layer has no grouped backward and its attention no "
            "flash kernel (models/latent.py serves it; llama.forward is "
            "its no-cache forward)")
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    if mesh_axes is not None:
        # Pin the gather output to the one layout the partitioner can
        # produce without moving the table: batch over the data axes (the
        # tokens' sharding) and hidden over tp (the table's sharding).
        # Left unconstrained, GSPMD assigns the gather the residual-stream
        # layout (seq sharded over cp or tp, hidden replicated) and cannot
        # reach it from the operands — it falls back to "involuntary full
        # rematerialization", a full-tensor replicate in the hot path.
        # From here the hop to the residual layout is a cheap explicit
        # reshard: hidden-dim all-gather (cp) or seq<->hidden all-to-all
        # (Megatron-SP), both inserted by the next sharding constraint
        # inside the first block.
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh_axes["mesh"],
                             P(mesh_axes["data"], mesh_axes.get("cp"),
                               mesh_axes["tp"])))
    period = cfg.period
    tables = rope_tables_by_kind(cfg, S)

    def block(kind):
        def f(carry, lp):
            return _block(carry, lp, *tables[kind], cfg, mesh_axes,
                          window=cfg.window_of(kind))
        if cfg.remat:
            policies = {
                "nothing": jax.checkpoint_policies.nothing_saveable,
                "attn": jax.checkpoint_policies.save_only_these_names(
                    "attn_out"),
                "dots":
                    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            }
            f = jax.checkpoint(f, policy=policies[cfg.remat_policy])
        return f

    blocks = {kind: block(kind) for kind in tables}

    def body(x, lps):
        # one period: its layers in order, each of its own kind
        aux = jnp.float32(0.0)
        for j, kind in enumerate(period):
            x, a = blocks[kind](x, period_layer(lps, j, len(period)))
            aux = a if len(period) == 1 else aux + a
        return x, aux

    x, auxs = jax.lax.scan(body, x,
                           period_stack(params["layers"], len(period)))
    x = rms_norm(x, params["final_norm"], cfg.rms_eps,
                 pallas=_pallas_fused(cfg))
    return x, jnp.sum(auxs)


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: LlamaConfig,
            mesh_axes: Optional[Dict[str, Any]] = None,
            return_hidden: bool = False) -> jax.Array:
    """tokens (B, S) int32 -> logits (B, S, V) float32 (or final-norm
    hidden states (B, S, H) when ``return_hidden``).

    ``mesh_axes``: {"mesh", "data": axis-or-tuple for batch, "tp": axis,
    "cp": axis, "ep": axis} to enable activation sharding constraints;
    None for single-device.
    """
    if cfg.hybrid is not None:
        if mesh_axes is not None:
            raise ValueError("forward: a hybrid model has no sharded "
                             "no-cache forward")
        from . import hybrid as _hybrid
        x = _hybrid.trunk(params, tokens, cfg)
    elif cfg.latent is not None:
        if mesh_axes is not None:
            raise ValueError("forward: a latent-attention config has no "
                             "sharded no-cache forward")
        from . import latent as _latent
        x = _latent.trunk(params, tokens, cfg)
    else:
        x, _ = _trunk(params, tokens, cfg, mesh_axes)
    if return_hidden:
        return x
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x @ head.astype(x.dtype)).astype(jnp.float32)


def _ce(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-position cross-entropy, fp32 logits."""
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - ll


def loss_fn(params, tokens, cfg: LlamaConfig, mesh_axes=None,
            seq_chunk: Optional[int] = None) -> jax.Array:
    """Next-token cross-entropy (mean over B*(S-1)).

    Forward runs on the FULL sequence (keeping seq a multiple of the flash
    block size); the last position is masked out of the loss rather than
    sliced off. ``seq_chunk``: compute the (B, chunk, V) fp32 logits in a
    scan over position chunks so the full logits tensor is never
    materialized — the HBM win that lets batch size scale (the reference
    pays the full fp32 logits; this is a TPU-first deviation).
    """
    h, aux = _trunk(params, tokens, cfg, mesh_axes)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    head = head.astype(h.dtype)
    B, S, H = h.shape
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    mask = jnp.concatenate(
        [jnp.ones((B, S - 1), jnp.float32), jnp.zeros((B, 1), jnp.float32)],
        axis=1)
    denom = jnp.float32(B * (S - 1))
    if seq_chunk is not None and S % seq_chunk != 0:
        raise ValueError(
            f"seq_chunk={seq_chunk} must divide seq_len={S}; a silent dense "
            f"fallback would re-materialize the full fp32 logits")
    if seq_chunk is None:
        ce = _ce((h @ head).astype(jnp.float32), labels)
        return jnp.sum(ce * mask) / denom + aux

    nc = S // seq_chunk
    hc = jnp.moveaxis(h.reshape(B, nc, seq_chunk, H), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, nc, seq_chunk), 1, 0)
    mc = jnp.moveaxis(mask.reshape(B, nc, seq_chunk), 1, 0)

    def body(acc, xs):
        hh, ll, mm = xs
        ce = _ce((hh @ head).astype(jnp.float32), ll)
        return acc + jnp.sum(ce * mm), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (hc, lc, mc))
    return total / denom + aux
