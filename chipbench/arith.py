"""The yardstick's arithmetic: parameters, operations and bytes of a
decoder-only model, computed from its configuration file alone.

Copied in meaning from ``paddle_tpu/models/llama.py``
(``LlamaConfig.num_params`` / ``flops_per_token``) so that no PR to the
program can move a share of a peak by changing what the work is said to
be. Conventions, all stated once here:

- recomputed operations (remat) are not credited;
- the input embedding is a gather, not a matrix product: its table is not
  in the matrix parameters unless the embeddings are tied, in which case
  the one table is the output head;
- causal attention is counted once: half of the full ``S x S`` product.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Sequence

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str) -> Dict[str, float]:
    """The peak table's row for this ``device_kind``; a device that is not
    in ``peaks.json`` is an error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    kind = device_kind.lower()
    for key, row in table.items():
        if key in kind:
            return row
    raise ValueError(f"chipbench: no peaks on record for device kind "
                     f"{device_kind!r} (chipbench/peaks.json)")


def head_dim(c: Dict) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def layer_matrix_params(c: Dict) -> int:
    h, i = c["hidden_size"], c["intermediate_size"]
    nh, nkv, hd = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    return h * nh * hd + 2 * h * nkv * hd + nh * hd * h + 3 * h * i


def num_params(c: Dict) -> int:
    """Every stored parameter (a tied table once)."""
    h, v, L = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    emb = v * h * (1 if c["tie_word_embeddings"] else 2)
    return L * (layer_matrix_params(c) + 2 * h) + emb + h


def matrix_params(c: Dict) -> int:
    """Parameters that take part in a matrix product for every token: the
    layers' matrices and the output head."""
    return (c["num_hidden_layers"] * layer_matrix_params(c)
            + c["vocab_size"] * c["hidden_size"])


def train_flops_per_token(c: Dict, seq_len: int) -> float:
    """Forward and backward: 6 per matrix parameter, and causal attention
    (QK and PV, forward 4 and backward 8 per head lane and key, halved)."""
    attn = 6.0 * c["num_hidden_layers"] * c["num_attention_heads"] \
        * head_dim(c) * seq_len
    return 6.0 * matrix_params(c) + attn


def flash_fwd_flops(c: Dict, batch: int, seq_len: int) -> float:
    """One forward call of causal flash attention over a whole batch, one
    layer: QK and PV, 2 FLOPs a multiply-add, half the square."""
    return 4.0 * batch * c["num_attention_heads"] * head_dim(c) \
        * seq_len * seq_len / 2.0


def flash_bwd_flops(c: Dict, batch: int, seq_len: int) -> float:
    """One backward pass (dq and dkv kernels together): five products
    (recomputed QK, dP, dV, dQ, dK), half the square."""
    return 2.5 * flash_fwd_flops(c, batch, seq_len)


def kv_bytes_per_token(c: Dict, kv_bytes: int = 2) -> int:
    """Bytes of keys and values one position holds over all layers."""
    return 2 * c["num_hidden_layers"] * c["num_key_value_heads"] \
        * head_dim(c) * kv_bytes


def decode_step_bytes(c: Dict, contexts: Sequence[int],
                      weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Least bytes one decode step has to read: every matrix and norm once,
    one embedding row a sequence, and the live keys and values."""
    h, L = c["hidden_size"], c["num_hidden_layers"]
    weights = (matrix_params(c) + L * 2 * h + h) * weight_bytes
    rows = len(contexts) * h * weight_bytes
    return weights + rows + kv_bytes_per_token(c, kv_bytes) * float(sum(contexts))


def decode_step_flops(c: Dict, contexts: Sequence[int]) -> float:
    """2 per matrix parameter and sequence, and 4 per head lane and key."""
    attn = 4.0 * c["num_hidden_layers"] * c["num_attention_heads"] \
        * head_dim(c) * float(sum(contexts))
    return 2.0 * matrix_params(c) * len(contexts) + attn


def prefill_flops(c: Dict, new_tokens: int, ctx_before: int) -> float:
    """Prefill of ``new_tokens`` positions after ``ctx_before`` cached ones:
    matrices for the new positions, causal attention over what each sees."""
    keys = new_tokens * ctx_before + new_tokens * (new_tokens + 1) / 2.0
    attn = 4.0 * c["num_hidden_layers"] * c["num_attention_heads"] \
        * head_dim(c) * keys
    return 2.0 * matrix_params(c) * new_tokens + attn


def least_seconds(flops: float, nbytes: float, peaks: Dict[str, float]) -> float:
    """The roofline's floor: the larger of operations over the peak rate
    and bytes over the peak bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def share(numerator: float, denominator: float, what: str,
          ceiling: float = 105.0) -> float:
    """A share of a peak or a roofline in percent. One above ``ceiling``
    means the work was counted too high or the time leaves work out: that
    fails the run; it is never clipped."""
    if denominator <= 0:
        raise ValueError(f"chipbench: {what}: nothing to divide by")
    pct = 100.0 * numerator / denominator
    if pct > ceiling:
        raise ValueError(f"chipbench: {what} reads {pct:.2f}%, above "
                         f"{ceiling}%: operations or bytes counted too "
                         f"high, or time left out")
    return pct
