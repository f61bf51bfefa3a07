"""The yardstick's arithmetic for a decoder of latent-attention (MLA) layers
with leading dense layers, a shared expert and a share of the routed experts
held here (``model_type: kimi_k2``): parameters, operations and least bytes
from the configuration file alone, as ``arith.py``, ``arith_moe.py`` and
``arith_hybrid.py`` have them for the other families. Conventions beside
theirs:

- ``n_routed_experts`` of the file is the number of experts HELD HERE; the
  router's width is ``router_outputs``. An expert counts where it is hit,
  and operations of the experts are those of the items computed here (the
  program's ``moe_routed_items_total``), not ``top_k`` a row;
- what a token keeps a layer is ONE row of ``kv_lora_rank +
  qk_rope_head_dim`` numbers; a decode step reads every live row once in
  every layer, whatever the number of heads;
- attention's operations are those of the ABSORBED form (a head's query
  against the row's ``kv_lora_rank + qk_rope_head_dim`` lanes, its
  probabilities against the first ``kv_lora_rank``), which is the cheaper
  one for a decode step; the projections into and out of the latent are
  ``W_kvb``'s parameters and are counted with the matrices.
"""
from __future__ import annotations

from typing import Dict, Sequence


def kind_layers(c: Dict, layers: int = None):
    """(leading dense layers, expert layers) among the first ``layers``."""
    L = c["num_hidden_layers"] if layers is None else layers
    lead = min(c["first_k_dense_replace"], L)
    return lead, L - lead


def row_width(c: Dict) -> int:
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def attn_matrix_params(c: Dict) -> int:
    h, nh = c["hidden_size"], c["num_attention_heads"]
    qr, R = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    return (h * qr + qr * nh * (nope + rope) + h * (R + rope)
            + R * nh * (nope + v) + nh * v * h)


def attn_small_params(c: Dict) -> int:
    """The two inner norms and the layer's two."""
    return c["q_lora_rank"] + c["kv_lora_rank"] + 2 * c["hidden_size"]


def expert_params(c: Dict) -> int:
    """One routed expert's three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_expert_params(c: Dict) -> int:
    return expert_params(c) * c["n_shared_experts"]


def router_params(c: Dict) -> int:
    """The router's matrix and its selection bias."""
    return c["hidden_size"] * c["router_outputs"] + c["router_outputs"]


def dense_ffn_params(c: Dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def dense_layer_params(c: Dict) -> int:
    return attn_matrix_params(c) + attn_small_params(c) + dense_ffn_params(c)


def expert_layer_shared_params(c: Dict) -> int:
    """An expert layer outside its routed experts."""
    return (attn_matrix_params(c) + attn_small_params(c)
            + shared_expert_params(c) + router_params(c))


def num_params(c: Dict, layers: int = None, experts: int = None,
               vocab: int = None) -> int:
    """Every stored parameter (the head is untied) of the first ``layers``
    layers with ``experts`` routed experts a layer and ``vocab`` rows; the
    file's own cut by default."""
    lead, Le = kind_layers(c, layers)
    E = c["n_routed_experts"] if experts is None else experts
    v = c["vocab_size"] if vocab is None else vocab
    h = c["hidden_size"]
    return (lead * dense_layer_params(c)
            + Le * (expert_layer_shared_params(c) + E * expert_params(c))
            + 2 * v * h + h)


def active_params(c: Dict, layers: int = None, vocab: int = None) -> int:
    """Parameters a token touches at ``num_experts_per_tok`` experts a
    layer, both tables counted (the model's name counts them so)."""
    lead, Le = kind_layers(c, layers)
    v = c["vocab_size"] if vocab is None else vocab
    h = c["hidden_size"]
    return (lead * dense_layer_params(c)
            + Le * (expert_layer_shared_params(c)
                    + c["num_experts_per_tok"] * expert_params(c))
            + 2 * v * h + h)


def active_dense_matrix_params(c: Dict) -> int:
    """Matrix parameters that multiply every token, the routed experts
    apart: attention, dense and shared FFN, router and the head."""
    lead, Le = kind_layers(c)
    h = c["hidden_size"]
    return (lead * (attn_matrix_params(c) + dense_ffn_params(c))
            + Le * (attn_matrix_params(c) + shared_expert_params(c)
                    + h * c["router_outputs"])
            + c["vocab_size"] * h)


def shared_weight_bytes(c: Dict, weight_bytes: int = 2) -> float:
    """What every program reads once whatever it routes: everything but the
    routed experts and the embedding table; the router in float32."""
    lead, Le = kind_layers(c)
    h = c["hidden_size"]
    rest = (lead * dense_layer_params(c)
            + Le * (expert_layer_shared_params(c) - router_params(c))
            + c["vocab_size"] * h + h)
    return rest * weight_bytes + Le * router_params(c) * 4


def expert_bytes(c: Dict, experts_hit: float, weight_bytes: int = 2) -> float:
    return experts_hit * expert_params(c) * weight_bytes


def latent_row_bytes(c: Dict, cache_bytes: int = 2) -> int:
    """What one token keeps in one layer."""
    return row_width(c) * cache_bytes


def latent_live_bytes(c: Dict, contexts: Sequence[int],
                      cache_bytes: int = 2) -> float:
    return (c["num_hidden_layers"] * float(sum(contexts))
            * latent_row_bytes(c, cache_bytes))


def step_bytes(c: Dict, contexts: Sequence[int], programs: int,
               experts_hit: float, chunk_ctx: int = 0) -> float:
    """Least bytes of one scheduler step: the shared weights once a
    program, the held experts hit, one embedding row a sequence, the live
    latents of every decoding row, and the latents a chunk program's row
    has behind it (``chunk_ctx`` tokens, read once a layer)."""
    return (programs * shared_weight_bytes(c) + expert_bytes(c, experts_hit)
            + len(contexts) * c["hidden_size"] * 2
            + latent_live_bytes(c, contexts)
            + latent_live_bytes(c, [chunk_ctx]))


def attention_flops(c: Dict, keys: float) -> float:
    """The absorbed form over ``keys`` (query, cached token) pairs a layer,
    all layers: scores over the row's lanes, values over the latent's."""
    return (2.0 * c["num_attention_heads"]
            * (row_width(c) + c["kv_lora_rank"]) * keys
            * c["num_hidden_layers"])


def decode_flops(c: Dict, contexts: Sequence[int], items: float) -> float:
    """2 per dense matrix parameter and row and per expert parameter and
    item computed here, and the absorbed attention over the live rows."""
    return (2.0 * active_dense_matrix_params(c) * len(contexts)
            + 2.0 * expert_params(c) * items
            + attention_flops(c, float(sum(contexts))))


def prefill_flops(c: Dict, new_tokens: int, ctx_before: int,
                  items: float) -> float:
    keys = new_tokens * ctx_before + new_tokens * (new_tokens + 1) / 2.0
    return (2.0 * active_dense_matrix_params(c) * new_tokens
            + 2.0 * expert_params(c) * items + attention_flops(c, keys))


def latent_attention_bytes(c: Dict, contexts: Sequence[int],
                           cache_bytes: int = 2) -> float:
    """The decode kernel's least traffic over all layers: every live row
    once, and a row's queries in (all heads, the row's width) and results
    out (the latent's), in bf16."""
    nh = c["num_attention_heads"]
    io = len(contexts) * c["num_hidden_layers"] * nh * 2 \
        * (row_width(c) + c["kv_lora_rank"])
    return latent_live_bytes(c, contexts, cache_bytes) + io


def latent_attention_flops(c: Dict, contexts: Sequence[int]) -> float:
    return attention_flops(c, float(sum(contexts)))


def expert_matmul_bytes(c: Dict, experts_hit: float, items: float) -> float:
    """The grouped matmuls' least traffic: the held experts hit once, and
    each item's activations in and out of the three products (gate and up
    read H and write I each, down reads I and writes H), in bf16."""
    h, i = c["hidden_size"], c["moe_intermediate_size"]
    return expert_bytes(c, experts_hit) + items * (3 * h + 3 * i) * 2


def expert_matmul_flops(c: Dict, items: float) -> float:
    return 2.0 * expert_params(c) * items
