"""Parameters, operations and least bytes of the Trinity-Large-Preview
configuration against hand-worked numbers, and the readers over them on
made-up records."""
import json
import os

import pytest

from chipbench import arith_afmoe as aa
from chipbench.readers import afmoe as readers

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(HERE, "configs", "trinity-large-preview.json")) as f:
    TRINITY = json.load(f)
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


#: the published ``config.json`` (the configuration's ``source``), every
#: key but ``layer_types``: 45 sliding and 15 full layers, the full one
#: every fourth
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 3072, "intermediate_size": 12288,
    "load_balance_coeff": 5e-05, "max_position_embeddings": 262144,
    "model_type": "afmoe", "moe_intermediate_size": 3072,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 48,
    "num_dense_layers": 6, "num_expert_groups": 1, "num_experts": 256,
    "num_experts_per_tok": 4, "num_hidden_layers": 60,
    "num_key_value_heads": 8, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.448,
    "score_func": "sigmoid", "sliding_window": 4096,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}


def test_the_file_keeps_every_published_width_and_states_its_cut():
    assert TRINITY["source"] == ("https://huggingface.co/arcee-ai/"
                                 "Trinity-Large-Preview/blob/main/config.json")
    differ = sorted(k for k, v in PUBLISHED.items() if TRINITY.get(k) != v)
    assert differ == sorted(TRINITY["reduced"]) == \
        ["num_experts", "num_hidden_layers", "vocab_size"]
    assert TRINITY["published"] == {k: PUBLISHED[k] for k in differ}
    assert TRINITY["layer_types"] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 15
    assert TRINITY["router_outputs"] == 256
    assert TRINITY["num_experts_per_tok"] == 4
    assert TRINITY["sliding_window"] == 4096
    assert TRINITY["num_experts"] == 32 and TRINITY["first_expert_held"] == 0
    # layers 5-9: the last dense layer, then one whole period
    assert TRINITY["layer_types"][5:10] == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    assert aa.layers_run(TRINITY) == (1, 4)
    assert aa.kind_layers(TRINITY) == (4, 1)
    assert "deployment" in TRINITY and len(TRINITY["assumed"]) >= 12


def test_parameter_counts():
    # W_q, W_o and the gate 3072 x 6144 each, W_k and W_v 3072 x 1024
    assert aa.attn_matrix_params(TRINITY) == 3 * 3072 * 6144 \
        + 2 * 3072 * 1024 == 62_914_560
    assert aa.expert_params(TRINITY) == 3 * 3072 * 3072 == 28_311_552
    assert round(aa.expert_layer_shared_params(TRINITY) / 1e6, 2) == 92.03
    assert round(aa.dense_layer_params(TRINITY) / 1e6, 2) == 176.17
    assert aa.layers_run(TRINITY, 60) == (6, 54)
    # the published model: "400B", "A13B" (the head, not the lookup table)
    assert round(aa.num_params(TRINITY, 60, 256, 200192) / 1e9, 1) == 398.6
    assert round(aa.active_params(TRINITY, 60, 200192) / 1e9, 1) == 12.8
    # no chip holds one expert layer whole: 14.5 GB of experts
    assert round(256 * aa.expert_params(TRINITY) * 2 / 1e9, 1) == 14.5
    # the cut: the dense layer and four expert layers of 32 experts, an
    # eighth of the vocabulary: 4.322 B parameters, 8.64 GB in bf16
    assert round(aa.num_params(TRINITY) / 1e9, 3) == 4.322
    assert round(aa.num_params(TRINITY) * 2 / 1e9, 2) == 8.64


def test_bytes_and_operations_a_step():
    assert aa.kv_row_bytes(TRINITY) == 4096
    # a row at 13,000 cached tokens: four windowed layers and one full
    assert aa.keys_seen(TRINITY, [13000, 100]) == (4 * (4096 + 100),
                                                   13100)
    shared = aa.shared_weight_bytes(TRINITY)
    assert shared == 2 * (aa.dense_layer_params(TRINITY) + 4 * (
        aa.expert_layer_shared_params(TRINITY)
        - aa.router_params(TRINITY)) + 25024 * 3072 + 3072) \
        + 4 * 4 * aa.router_params(TRINITY)
    assert aa.step_bytes(TRINITY, [13000, 100], 2, 50, 6000) == \
        2 * shared + 50 * 28_311_552 * 2 + 2 * 3072 * 2 \
        + (4 * 4196 + 13100 + 4 * 4096 + 6000) * 4096
    # a chunk of 3 tokens after 4,095 cached: 4,096 .. 4,098 keys each
    assert aa.chunk_keys(TRINITY, 3, 4095) == (4 * 3 * 4096,
                                               4096 + 4097 + 4098)
    assert aa.decode_flops(TRINITY, [10], 8) == \
        2 * aa.active_dense_matrix_params(TRINITY) + 2 * 28_311_552 * 8 \
        + 4 * 48 * 128 * 50
    assert aa.paged_attention_bytes(TRINITY, [10]) == \
        50 * 4096 + 5 * 48 * 128 * 2 * 2


def _record(steps, trace_steps, contexts=(13000,) * 32, window_s=1.0):
    recs = [{"contexts": list(contexts), "prefill_width": 0, "t0": 0, "t1": 1,
             "moe": [i * 100, i * 50, 0, i * 4]} for i in range(steps)]
    return {"config": TRINITY, "peaks": V5E, "steps": recs,
            "trace_steps": trace_steps,
            "trace": {"window_s": window_s, "ops": {
                "%paged_attention.3 = bf16[32,48,128] custom-call(": 0.5,
                "%grouped_expert_matmul.4 = bf16[100,3072] custom-call(":
                0.5}, "counts": {
                "%paged_attention.3 = bf16[32,48,128] custom-call(": 1,
                "%grouped_expert_matmul.4 = bf16[100,3072] custom-call(":
                1}}}


def test_the_readers_on_a_made_up_record():
    rec = _record(5, (1, 5), window_s=0.1)
    mfu = readers.decode_step_mfu(rec, {"name": "m"})
    least = 4 * max(
        aa.decode_flops(TRINITY, [13000] * 32, 100) / 197e12,
        aa.step_bytes(TRINITY, [13000] * 32, 1, 50) / 819e9)
    assert mfu == pytest.approx(100 * least / 0.1)
    spec = {"name": "k", "op_pattern": r"^%paged_attention[\w.\-]* = "}
    roof = readers.paged_attention_roofline(rec, spec)
    assert roof == pytest.approx(
        100 * 4 * aa.paged_attention_bytes(TRINITY, [13000] * 32) / 819e9
        / 0.5)
    spec = {"name": "e",
            "op_pattern": r"^%grouped_expert_matmul[\w.\-]* = "}
    e = readers.expert_matmul_roofline(rec, spec)
    assert e == pytest.approx(100 * aa.expert_matmul_bytes(
        TRINITY, 200, 400) / 819e9 / 0.5)
    # nothing traced, nothing read
    assert readers.decode_step_mfu(dict(rec, trace=None), {"name": "m"}) \
        is None
    assert readers.paged_attention_roofline(
        dict(rec, trace_steps=None), spec) is None


def test_the_held_experts_share_reads_the_held_count():
    rec = {"config": TRINITY,
           "stats_open": {"moe_experts_hit_total": 0,
                          "moe_layer_steps_total": 0},
           "stats_close": {"moe_experts_hit_total": 64,
                           "moe_layer_steps_total": 4}}
    assert readers.experts_hit_share(rec, {}) == pytest.approx(50.0)
    assert readers.experts_hit_share(
        dict(rec, stats_close={}), {}) is None
