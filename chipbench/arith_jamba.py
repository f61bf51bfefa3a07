"""The yardstick's arithmetic for a Jamba model (``model_type: jamba``):
Mamba-1 and attention mixers, each followed by a dense MLP, the head tied
to the embedding. Parameters, operations and least bytes from the
configuration file alone, as ``arith.py`` has them for the decoders.
Conventions beside theirs:

- a decode step reads every parameter once (the head is the embedding
  table, read whole), AND reads and writes every live row's recurrent
  state in every Mamba-1 layer (float32) and its convolution tail (bf16);
  a chunk program does so for its one row;
- the recurrence's update is 7 operations a state element and token:
  ``dt A``, its ``exp``, the decay's product with the state, ``dt x B``,
  their sum, the product with C and its sum;
- attention layers apply no positional embedding and read the whole
  context of their one key/value head.
"""
from __future__ import annotations

from typing import Dict, Sequence

#: operations a state element and token of the recurrence
SCAN_OPS = 7.0


def widths(c: Dict):
    """(hidden, channels D, states N, step rank R, conv width K, head dim)."""
    h = c["hidden_size"]
    return (h, c["mamba_expand"] * h, c["mamba_d_state"], c["mamba_dt_rank"],
            c["mamba_d_conv"], h // c["num_attention_heads"])


def kind_layers(c: Dict):
    """(Mamba-1 layers, attention layers): layer i attends iff i %
    attn_layer_period == attn_layer_offset."""
    L = c["num_hidden_layers"]
    La = sum(1 for i in range(L)
             if i % c["attn_layer_period"] == c["attn_layer_offset"])
    return L - La, La


def mamba_matrix_params(c: Dict) -> int:
    """W_in, W_x, W_dt and W_out."""
    h, D, N, R, _, _ = widths(c)
    return h * 2 * D + D * (R + 2 * N) + R * D + D * h


def mamba_small_params(c: Dict) -> int:
    """The convolution and its bias, the three inner norms, W_dt's bias,
    A_log (D x N) and D."""
    _, D, N, R, K, _ = widths(c)
    return K * D + D + R + 2 * N + D + D * N + D


def mamba_f32_params(c: Dict) -> int:
    """What the program keeps in float32: dt_bias, A_log and D."""
    _, D, N, _, _, _ = widths(c)
    return D + D * N + D


def mixer_params(c: Dict) -> int:
    """One Mamba-1 mixer, its pre-norm apart."""
    return mamba_matrix_params(c) + mamba_small_params(c)


def attn_params(c: Dict) -> int:
    h, _, _, _, _, hd = widths(c)
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    return h * nh * hd + 2 * h * nkv * hd + nh * hd * h


def mlp_params(c: Dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def num_params(c: Dict) -> int:
    """Every stored parameter: each layer's mixer, MLP and two norms, the
    final norm, and the embedding once if the head is tied."""
    h, v = c["hidden_size"], c["vocab_size"]
    Lm, La = kind_layers(c)
    per = mlp_params(c) + 2 * h
    emb = v * h * (1 if c["tie_word_embeddings"] else 2)
    return (Lm * (mixer_params(c) + per) + La * (attn_params(c) + per)
            + emb + h)


def matrix_params(c: Dict) -> int:
    """Matrix parameters that multiply every token: the mixers'
    projections, the MLPs and the head."""
    Lm, La = kind_layers(c)
    return (Lm * mamba_matrix_params(c) + La * attn_params(c)
            + (Lm + La) * mlp_params(c)
            + c["vocab_size"] * c["hidden_size"])


def weight_bytes(c: Dict, nbytes: int = 2) -> float:
    """Every parameter read once, bf16 but for the float32 leaves."""
    f32 = kind_layers(c)[0] * mamba_f32_params(c)
    return (num_params(c) - f32) * nbytes + f32 * 4


def state_row_bytes(c: Dict) -> int:
    """One row's state in one Mamba-1 layer: the recurrence's (N x D
    float32) and the convolution's last K - 1 columns (bf16)."""
    _, D, N, _, K, _ = widths(c)
    return N * D * 4 + (K - 1) * D * 2


def kv_row_bytes(c: Dict, kv_bytes: int = 2) -> int:
    """A token's key and value in one attention layer."""
    return 2 * c["num_key_value_heads"] * widths(c)[5] * kv_bytes


def kv_live_bytes(c: Dict, contexts: Sequence[int]) -> float:
    return kind_layers(c)[1] * float(sum(contexts)) * kv_row_bytes(c)


def step_bytes(c: Dict, contexts: Sequence[int], programs: int,
               chunk_rows: int = 0, chunk_ctx: int = 0) -> float:
    """Least bytes of one scheduler step: the weights once a program, one
    embedding row a sequence, every decoding row's state (and the chunk's
    row's) read and written in every Mamba-1 layer, the live keys and
    values, and the context the chunk's row attends over."""
    rows = len(contexts) + chunk_rows
    return (programs * weight_bytes(c)
            + len(contexts) * c["hidden_size"] * 2
            + 2 * rows * kind_layers(c)[0] * state_row_bytes(c)
            + kv_live_bytes(c, list(contexts) + [chunk_ctx]))


def state_update_flops(c: Dict, row_layers: float) -> float:
    _, D, N, _, _, _ = widths(c)
    return SCAN_OPS * N * D * row_layers


def state_update_bytes(c: Dict, row_layers: float) -> float:
    """The one-token update over the pool: the state read and written in
    float32; ``dt``, ``x`` in and ``y`` out a channel, B and C a state, all
    float32; ``A`` once a layer is not counted (it is a row-layer's
    sixteenth at 16 rows and less beyond)."""
    _, D, N, _, _, _ = widths(c)
    return row_layers * 4.0 * (2 * N * D + 3 * D + 2 * N)


def scan_flops(c: Dict, tokens: float) -> float:
    """The chunk scan, a token and Mamba-1 layer."""
    _, D, N, _, _, _ = widths(c)
    return SCAN_OPS * N * D * tokens


def scan_bytes(c: Dict, tokens: float, calls: float) -> float:
    """The chunk scan's least traffic: ``dt`` and ``x`` in and ``y`` out a
    token and channel, B and C a token and state, all float32; the state in
    and out and ``A`` once a call (a row's chunk in one layer)."""
    _, D, N, _, _, _ = widths(c)
    return 4.0 * (tokens * (3 * D + 2 * N) + calls * 3 * N * D)


def _mixer_flops(c: Dict, tokens: float) -> float:
    """What a token costs in the layers beyond 2 a matrix parameter: the
    convolution, the recurrence."""
    h, D, N, _, K, _ = widths(c)
    Lm, _ = kind_layers(c)
    return Lm * tokens * (2.0 * K * D + SCAN_OPS * N * D)


def decode_flops(c: Dict, contexts: Sequence[int]) -> float:
    """2 per matrix parameter and row, the convolution and the state update,
    4 per head lane and key seen."""
    _, La = kind_layers(c)
    attn = 4.0 * c["num_attention_heads"] * widths(c)[5] * La \
        * float(sum(contexts))
    return (2.0 * matrix_params(c) * len(contexts)
            + _mixer_flops(c, len(contexts)) + attn)


def prefill_flops(c: Dict, new_tokens: int, ctx_before: int) -> float:
    """A chunk of ``new_tokens`` after ``ctx_before``: the layers for every
    token, the head for the last one only."""
    _, La = kind_layers(c)
    keys = new_tokens * ctx_before + new_tokens * (new_tokens + 1) / 2.0
    attn = 4.0 * c["num_attention_heads"] * widths(c)[5] * La * keys
    head = c["vocab_size"] * c["hidden_size"]
    return (2.0 * (matrix_params(c) - head) * new_tokens + 2.0 * head
            + _mixer_flops(c, new_tokens) + attn)
