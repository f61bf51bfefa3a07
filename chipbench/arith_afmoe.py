"""The yardstick's arithmetic for a K/V decoder with gated attention, a
4,096-key window on its sliding layers and full layers beside them, leading
dense layers, a shared expert and a share of the routed experts held here
(``model_type: afmoe``): parameters, operations and least bytes from the
configuration file alone, as ``arith_moe.py`` and ``arith_mla.py`` have them
for the other families. Conventions beside theirs:

- ``num_experts`` of the file is the number of experts HELD HERE; the
  router's width is ``router_outputs``. An expert counts where it is hit,
  and operations of the experts are those of the items computed here (the
  program's ``moe_routed_items_total``), not ``top_k`` a row;
- a key in one layer is ``2 * num_key_value_heads * head_dim`` numbers (its
  key and value); a sliding layer's query reads ``min(context, window)`` of
  them, a full layer's the whole context (what the engine's
  ``window_keys_attended_total`` / ``full_keys_attended_total`` count);
- the layers run are ``first_layer_run .. + num_hidden_layers`` of the
  published ``layer_types``; those below ``num_dense_layers`` are dense.
"""
from __future__ import annotations

from typing import Dict, Sequence

from . import arith_moe


def layers_run(c: Dict, layers: int = None):
    """(dense layers, expert layers) among the layers run, or among the
    first ``layers`` of the model from layer 0 when given."""
    if layers is not None:
        lead = min(c["num_dense_layers"], layers)
        return lead, layers - lead
    L = c["num_hidden_layers"]
    lead = min(max(c["num_dense_layers"] - c["first_layer_run"], 0), L)
    return lead, L - lead


def kind_layers(c: Dict):
    """(sliding layers, full layers) among the layers run."""
    first = c["first_layer_run"]
    kinds = c["layer_types"][first:first + c["num_hidden_layers"]]
    n = sum(1 for t in kinds if t == "sliding_attention")
    return n, len(kinds) - n


def attn_matrix_params(c: Dict) -> int:
    """``W_q``, ``W_k``, ``W_v``, ``W_o`` and the output gate ``W_gate``."""
    h, nh, nkv, hd = (c["hidden_size"], c["num_attention_heads"],
                      c["num_key_value_heads"], c["head_dim"])
    gate = h * nh * hd if c["attn_output_gate"] else 0
    return 2 * h * nh * hd + 2 * h * nkv * hd + gate


def attn_small_params(c: Dict) -> int:
    """The layer's norms: input, pre-MLP, the two sandwich norms and the
    two QK-norm gains."""
    h, hd = c["hidden_size"], c["head_dim"]
    return ((4 if c["sandwich_norms"] else 2) * h
            + (2 * hd if c["qk_norm"] else 0))


def expert_params(c: Dict) -> int:
    """One routed expert's three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_expert_params(c: Dict) -> int:
    return expert_params(c) * c["num_shared_experts"]


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
    """Every stored parameter (the head is untied): the layers run with
    the file's experts and vocabulary by default; the model's first
    ``layers`` layers, ``experts`` routed experts a layer and ``vocab``
    rows when given."""
    lead, Le = layers_run(c, layers)
    E = c["num_experts"] if experts is None else experts
    v = c["vocab_size"] if vocab is None else vocab
    h = c["hidden_size"]
    return (lead * dense_layer_params(c)
            + Le * (expert_layer_shared_params(c) + E * expert_params(c))
            + 2 * v * h + h)


def active_params(c: Dict, layers: int = None, vocab: int = None) -> int:
    """Parameters a token multiplies at ``num_experts_per_tok`` experts a
    layer: every layer's, the final norm and the head (the embedding is a
    lookup of one row)."""
    lead, Le = layers_run(c, layers)
    v = c["vocab_size"] if vocab is None else vocab
    h = c["hidden_size"]
    return (lead * dense_layer_params(c)
            + Le * (expert_layer_shared_params(c)
                    + c["num_experts_per_tok"] * expert_params(c))
            + v * h + h)


def active_dense_matrix_params(c: Dict) -> int:
    """Matrix parameters that multiply every token, the routed experts
    apart: attention, dense and shared FFN, router and the head."""
    lead, Le = layers_run(c)
    h = c["hidden_size"]
    return (lead * (attn_matrix_params(c) + dense_ffn_params(c))
            + Le * (attn_matrix_params(c) + shared_expert_params(c)
                    + h * c["router_outputs"])
            + c["vocab_size"] * h)


def shared_weight_bytes(c: Dict, weight_bytes: int = 2) -> float:
    """What every program reads once whatever it routes: everything but the
    routed experts and the embedding table; the router in float32."""
    lead, Le = layers_run(c)
    h = c["hidden_size"]
    rest = (lead * dense_layer_params(c)
            + Le * (expert_layer_shared_params(c) - router_params(c))
            + c["vocab_size"] * h + h)
    return rest * weight_bytes + Le * router_params(c) * 4


def expert_bytes(c: Dict, experts_hit: float, weight_bytes: int = 2) -> float:
    return experts_hit * expert_params(c) * weight_bytes


def kv_row_bytes(c: Dict, kv_bytes: int = 2) -> int:
    """Key and value of one position in one layer: 4,096 B at the
    published widths."""
    return arith_moe.kv_row_bytes(c, kv_bytes)


def keys_seen(c: Dict, contexts: Sequence[int]):
    """Keys the rows of a decode step read, summed over rows and layers of
    each kind: (sliding, full)."""
    ns, nf = kind_layers(c)
    w = c["sliding_window"]
    return (ns * float(sum(min(x, w) for x in contexts)),
            nf * float(sum(contexts)))


def chunk_keys(c: Dict, new_tokens: int, ctx_before: int):
    """(query, key) pairs of a chunk of ``new_tokens`` after ``ctx_before``
    cached ones, summed over layers of each kind: (sliding, full)."""
    ns, nf = kind_layers(c)
    w = c["sliding_window"]
    seen = range(ctx_before + 1, ctx_before + new_tokens + 1)
    return (ns * float(sum(min(x, w) for x in seen)),
            nf * float(sum(seen)))


def step_bytes(c: Dict, contexts: Sequence[int], programs: int,
               experts_hit: float, chunk_ctx: int = 0) -> float:
    """Least bytes of one scheduler step: the shared weights once a
    program, the held experts hit, one embedding row a sequence, the keys
    and values every decoding row reads, and those a chunk program's row
    has behind it (``chunk_ctx`` tokens, a window at most in a sliding
    layer, read once a layer)."""
    ns, nf = kind_layers(c)
    chunk = (ns * min(chunk_ctx, c["sliding_window"]) + nf * chunk_ctx)
    return (programs * shared_weight_bytes(c) + expert_bytes(c, experts_hit)
            + len(contexts) * c["hidden_size"] * 2
            + (sum(keys_seen(c, contexts)) + chunk) * kv_row_bytes(c))


def attention_flops(c: Dict, keys: float) -> float:
    """4 per query head lane and (query, key) pair: scores and values."""
    return 4.0 * c["num_attention_heads"] * c["head_dim"] * keys


def decode_flops(c: Dict, contexts: Sequence[int], items: float) -> float:
    """2 per dense matrix parameter and row and per expert parameter and
    item computed here, and attention over the keys the rows read."""
    return (2.0 * active_dense_matrix_params(c) * len(contexts)
            + 2.0 * expert_params(c) * items
            + attention_flops(c, sum(keys_seen(c, contexts))))


def prefill_flops(c: Dict, new_tokens: int, ctx_before: int,
                  items: float) -> float:
    return (2.0 * active_dense_matrix_params(c) * new_tokens
            + 2.0 * expert_params(c) * items
            + attention_flops(c, sum(chunk_keys(c, new_tokens, ctx_before))))


def paged_attention_bytes(c: Dict, contexts: Sequence[int],
                          kv_bytes: int = 2) -> float:
    """The decode kernel's least traffic over all layers: every key and
    value a row reads once, and a row's queries in and results out (all
    heads), in bf16."""
    nh, hd = c["num_attention_heads"], c["head_dim"]
    io = len(contexts) * c["num_hidden_layers"] * nh * hd * 2 * kv_bytes
    return sum(keys_seen(c, contexts)) * kv_row_bytes(c, kv_bytes) + io


def paged_attention_flops(c: Dict, contexts: Sequence[int]) -> float:
    return attention_flops(c, sum(keys_seen(c, contexts)))


def expert_matmul_bytes(c: Dict, experts_hit: float, items: float) -> float:
    """The grouped matmuls' least traffic: the held experts hit once, and
    each item's activations in and out of the three products (gate and up
    read H and write I each, down reads I and writes H), in bf16."""
    h, i = c["hidden_size"], c["moe_intermediate_size"]
    return expert_bytes(c, experts_hit) + items * (3 * h + 3 * i) * 2


def expert_matmul_flops(c: Dict, items: float) -> float:
    return 2.0 * expert_params(c) * items
