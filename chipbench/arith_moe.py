"""The yardstick's arithmetic for a decoder with routed experts and with
sliding-window and full attention layers mixed (``model_type: mellum``):
parameters, operations and least bytes from the configuration file alone,
as ``arith.py`` has them for the plain decoder. Conventions beside
``arith.py``'s:

- an expert counts where it is HIT: a program that routes its rows to n
  distinct experts of a layer has to read n experts' matrices there, not
  all of them and not ``top_k`` a row; the count is the program's own
  (``moe_experts_hit_total``), summed over layers and programs;
- operations of the experts are those of the routed items: ``top_k`` a row
  and layer, whatever the grouping;
- a sliding layer's attention reads and multiplies ``min(context, window)``
  keys a row, a full layer's the whole context.
"""
from __future__ import annotations

from typing import Dict, Sequence


def layer_kinds(c: Dict):
    return list(c["layer_types"])[:c["num_hidden_layers"]]


def kind_layers(c: Dict):
    """(sliding layers, full layers)."""
    kinds = layer_kinds(c)
    n = sum(1 for t in kinds if t == "sliding_attention")
    return n, len(kinds) - n


def attn_params(c: Dict) -> int:
    h, nh, nkv, hd = (c["hidden_size"], c["num_attention_heads"],
                      c["num_key_value_heads"], c["head_dim"])
    return h * nh * hd + 2 * h * nkv * hd + nh * hd * h


def expert_params(c: Dict) -> int:
    """One expert's three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: Dict) -> int:
    return c["hidden_size"] * c["num_experts"]


def num_params(c: Dict) -> int:
    """Every stored parameter (the head is untied)."""
    h, v, L = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    layer = (attn_params(c) + router_params(c)
             + c["num_experts"] * expert_params(c) + 2 * h)
    return L * layer + 2 * v * h + h


def active_matrix_params(c: Dict) -> int:
    """Parameters that multiply one token: attention, router and ``top_k``
    experts a layer, and the head."""
    layer = (attn_params(c) + router_params(c)
             + c["num_experts_per_tok"] * expert_params(c))
    return c["num_hidden_layers"] * layer + c["vocab_size"] * c["hidden_size"]


def kv_row_bytes(c: Dict, kv_bytes: int = 2) -> int:
    """Keys and values of one position in one layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * kv_bytes


def keys_seen(c: Dict, contexts: Sequence[int]):
    """Keys a decode step's rows read, summed over rows and layers of each
    kind: (sliding, full)."""
    ns, nf = kind_layers(c)
    w = c["sliding_window"]
    return (ns * float(sum(min(x, w) for x in contexts)),
            nf * float(sum(contexts)))


def kv_live_bytes(c: Dict, contexts: Sequence[int], kv_bytes: int = 2) -> float:
    return sum(keys_seen(c, contexts)) * kv_row_bytes(c, kv_bytes)


def shared_weight_bytes(c: Dict, weight_bytes: int = 2) -> float:
    """What every program reads once whatever it routes: attention, norms,
    the head; the router in float32."""
    h, L = c["hidden_size"], c["num_hidden_layers"]
    return ((L * (attn_params(c) + 2 * h) + c["vocab_size"] * h + h)
            * weight_bytes + L * router_params(c) * 4)


def expert_bytes(c: Dict, experts_hit: float, weight_bytes: int = 2) -> float:
    return experts_hit * expert_params(c) * weight_bytes


def step_bytes(c: Dict, contexts: Sequence[int], programs: int,
               experts_hit: float) -> float:
    """Least bytes of one scheduler step: the shared weights once a program
    (the decode program, and a chunk program where the step ran one), the
    experts hit, one embedding row a sequence, the live keys and values."""
    return (programs * shared_weight_bytes(c) + expert_bytes(c, experts_hit)
            + len(contexts) * c["hidden_size"] * 2
            + kv_live_bytes(c, contexts))


def decode_flops(c: Dict, contexts: Sequence[int]) -> float:
    """2 per active matrix parameter and row, 4 per head lane and key seen."""
    attn = 4.0 * c["num_attention_heads"] * c["head_dim"] \
        * sum(keys_seen(c, contexts))
    return 2.0 * active_matrix_params(c) * len(contexts) + attn


def prefill_flops(c: Dict, new_tokens: int, ctx_before: int) -> float:
    """A chunk of ``new_tokens`` positions after ``ctx_before`` cached ones:
    the active matrices for the new positions; attention over what each
    sees, a window at most in the sliding layers."""
    ns, nf = kind_layers(c)
    w = c["sliding_window"]
    full = new_tokens * ctx_before + new_tokens * (new_tokens + 1) / 2.0
    slide = sum(min(ctx_before + i + 1, w) for i in range(new_tokens))
    attn = 4.0 * c["num_attention_heads"] * c["head_dim"] \
        * (nf * full + ns * slide)
    return 2.0 * active_matrix_params(c) * new_tokens + attn


def expert_matmul_bytes(c: Dict, experts_hit: float, items: float) -> float:
    """The grouped matmuls' least traffic: the experts hit once, and each
    routed item's activations in and out of the three products (gate and up
    read H and write I each, down reads I and writes H), in bf16."""
    h, i = c["hidden_size"], c["moe_intermediate_size"]
    return expert_bytes(c, experts_hit) + items * (3 * h + 3 * i) * 2


def expert_matmul_flops(c: Dict, items: float) -> float:
    return 2.0 * expert_params(c) * items
