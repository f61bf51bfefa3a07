"""The yardstick's arithmetic for a hybrid model of Mamba-2, attention and
LatentMoE layers with a share of the routed experts held here (``model_type:
nemotron_h``): parameters, operations and least bytes from the configuration
file alone, as ``arith.py`` and ``arith_moe.py`` have them for the decoders.
Conventions beside theirs:

- ``n_routed_experts`` of the file is the number of experts HELD HERE; the
  router's width is ``router_outputs``. An expert counts where it is hit,
  and operations of the experts are those of the items computed here (the
  program's ``moe_routed_items_total``), not ``top_k`` a row;
- a decode step reads AND writes every live row's recurrent state in every
  Mamba-2 layer (float32) and its convolution tail (bf16); a chunk program
  does so for its one row;
- attention layers apply no rotary embedding and read the whole context.
"""
from __future__ import annotations

from typing import Dict, Sequence


def kind_layers(c: Dict, layers: int = None):
    """(Mamba-2, attention, expert) layers among the first ``layers`` of
    the published pattern (``num_hidden_layers`` when None)."""
    p = c["hybrid_override_pattern"][:layers or c["num_hidden_layers"]]
    return p.count("M"), p.count("*"), p.count("E")


def _ssm(c: Dict):
    H, P, G, N = (c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"],
                  c["ssm_state_size"])
    return H, P, G, N, H * P, H * P + 2 * G * N


def mamba_matrix_params(c: Dict) -> int:
    H, _, _, _, di, cd = _ssm(c)
    return c["hidden_size"] * (di + cd + H) + di * c["hidden_size"]


def mamba_small_params(c: Dict) -> int:
    """Convolution, its bias, dt_bias, A_log, D, the gated norm, the norm."""
    H, _, _, _, di, cd = _ssm(c)
    return c["conv_kernel"] * cd + cd + 3 * H + di + c["hidden_size"]


def attn_params(c: Dict) -> int:
    h, nh, nkv, hd = (c["hidden_size"], c["num_attention_heads"],
                      c["num_key_value_heads"], c["head_dim"])
    return h * nh * hd + 2 * h * nkv * hd + nh * hd * h


def expert_params(c: Dict) -> int:
    """One routed expert's two matrices in the latent."""
    return 2 * c["moe_latent_size"] * c["moe_intermediate_size"]


def router_params(c: Dict) -> int:
    return c["hidden_size"] * c["router_outputs"] + c["router_outputs"]


def expert_layer_matrix_params(c: Dict) -> int:
    """An expert layer outside its routed experts and its router: the
    latent projections and the shared expert."""
    h = c["hidden_size"]
    return (2 * h * c["moe_latent_size"]
            + 2 * h * c["moe_shared_expert_intermediate_size"])


def num_params(c: Dict, layers: int = None, experts: int = None,
               vocab: int = None) -> int:
    """Every stored parameter (the head is untied) of the first ``layers``
    layers with ``experts`` routed experts a layer and ``vocab`` rows; the
    file's own cut by default."""
    h = c["hidden_size"]
    Lm, La, Le = kind_layers(c, layers)
    E = c["n_routed_experts"] if experts is None else experts
    v = c["vocab_size"] if vocab is None else vocab
    return (Lm * (mamba_matrix_params(c) + mamba_small_params(c))
            + La * (attn_params(c) + h)
            + Le * (expert_layer_matrix_params(c) + router_params(c) + h
                    + E * expert_params(c))
            + 2 * v * h + h)


def active_dense_params(c: Dict) -> int:
    """Matrix parameters that multiply every token, the routed experts
    apart: the mixers' projections, router, latent projections, shared
    expert and the head."""
    Lm, La, Le = kind_layers(c)
    return (Lm * mamba_matrix_params(c) + La * attn_params(c)
            + Le * (expert_layer_matrix_params(c)
                    + c["hidden_size"] * c["router_outputs"])
            + c["vocab_size"] * c["hidden_size"])


def shared_weight_bytes(c: Dict, weight_bytes: int = 2) -> float:
    """What every program reads once whatever it routes: everything but the
    routed experts and the embedding table; router, dt_bias, A_log and D
    in float32."""
    h = c["hidden_size"]
    Lm, La, Le = kind_layers(c)
    f32 = Lm * 3 * c["mamba_num_heads"] + Le * router_params(c)
    rest = (Lm * (mamba_matrix_params(c) + mamba_small_params(c))
            + La * (attn_params(c) + h)
            + Le * (expert_layer_matrix_params(c) + h)
            + c["vocab_size"] * h + h) - Lm * 3 * c["mamba_num_heads"]
    return rest * weight_bytes + f32 * 4


def expert_bytes(c: Dict, experts_hit: float, weight_bytes: int = 2) -> float:
    return experts_hit * expert_params(c) * weight_bytes


def state_row_bytes(c: Dict) -> int:
    """One row's state in one Mamba-2 layer: the recurrence's in float32
    and the convolution's last columns in bf16."""
    H, P, _, N, _, cd = _ssm(c)
    return H * P * N * 4 + (c["conv_kernel"] - 1) * cd * 2


def kv_row_bytes(c: Dict, kv_bytes: int = 2) -> int:
    return 2 * c["num_key_value_heads"] * c["head_dim"] * kv_bytes


def kv_live_bytes(c: Dict, contexts: Sequence[int], kv_bytes: int = 2) -> float:
    return kind_layers(c)[1] * float(sum(contexts)) * kv_row_bytes(c, kv_bytes)


def step_bytes(c: Dict, contexts: Sequence[int], programs: int,
               experts_hit: float, chunk_rows: int = 0) -> float:
    """Least bytes of one scheduler step: the shared weights once a
    program, the experts hit, one embedding row a sequence, every decoding
    row's state (and the chunk's row's) read and written in every Mamba-2
    layer, and the live keys and values."""
    rows = len(contexts) + chunk_rows
    return (programs * shared_weight_bytes(c) + expert_bytes(c, experts_hit)
            + len(contexts) * c["hidden_size"] * 2
            + 2 * rows * kind_layers(c)[0] * state_row_bytes(c)
            + kv_live_bytes(c, contexts))


def ssm_update_flops(c: Dict, row_layers: float) -> float:
    """The one-token update: decay, outer product, their sum, the product
    with C and its sum: 5 a state element."""
    H, P, _, N, _, _ = _ssm(c)
    return 5.0 * H * P * N * row_layers


def ssm_update_bytes(c: Dict, row_layers: float) -> float:
    """The state read and written in float32; ``dt x``, B and C spread to
    heads, the decay in and ``y`` out are the kernel's small operands."""
    H, P, _, N, _, _ = _ssm(c)
    return row_layers * 4.0 * (2 * H * P * N + 2 * P * H + 2 * N * H + H)


def ssm_scan_flops(c: Dict, tokens: float) -> float:
    """The chunked scan a token and Mamba-2 layer: inside a sub-chunk of Q
    the C.B and the weighted sum over it, 2 Q (G N + H P); across, the
    state's product with C and its update, 4 H P N."""
    H, P, G, N, _, _ = _ssm(c)
    return tokens * (2.0 * c["chunk_size"] * (G * N + H * P) + 4.0 * H * P * N)


def decode_flops(c: Dict, contexts: Sequence[int], items: float) -> float:
    """2 per dense matrix parameter and row and per expert parameter and
    item computed here, the state update, 4 per head lane and key seen."""
    Lm, La, _ = kind_layers(c)
    attn = 4.0 * c["num_attention_heads"] * c["head_dim"] * La \
        * float(sum(contexts))
    return (2.0 * active_dense_params(c) * len(contexts)
            + 2.0 * expert_params(c) * items
            + ssm_update_flops(c, Lm * len(contexts)) + attn)


def prefill_flops(c: Dict, new_tokens: int, ctx_before: int,
                  items: float) -> float:
    Lm, La, _ = kind_layers(c)
    keys = new_tokens * ctx_before + new_tokens * (new_tokens + 1) / 2.0
    attn = 4.0 * c["num_attention_heads"] * c["head_dim"] * La * keys
    return (2.0 * active_dense_params(c) * new_tokens
            + 2.0 * expert_params(c) * items
            + Lm * ssm_scan_flops(c, new_tokens) + attn)


def expert_matmul_bytes(c: Dict, experts_hit: float, items: float) -> float:
    """The grouped matmuls' least traffic: the experts hit once, and each
    item's activations in and out of the two products, in bf16."""
    return expert_bytes(c, experts_hit) + items * 2 * (
        c["moe_latent_size"] + c["moe_intermediate_size"]) * 2


def expert_matmul_flops(c: Dict, items: float) -> float:
    return 2.0 * expert_params(c) * items
