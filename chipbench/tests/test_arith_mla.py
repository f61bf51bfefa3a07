"""Parameters, operations and least bytes of the Kimi-K2-Instruct
configuration against hand-worked numbers, and the readers over them on
made-up records."""
import json
import os
import re

import pytest

from chipbench import arith_mla as am
from chipbench.readers import hybrid as shares
from chipbench.readers import mla as readers

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(HERE, "configs", "kimi-k2-instruct.json")) as f:
    KIMI = json.load(f)
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_file_keeps_every_published_width_and_states_its_cut():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-K2-Instruct")
    assert KIMI["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if KIMI.get(k) != v)
    assert differ == sorted(KIMI["reduced"]) == \
        ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert KIMI["published"] == {k: row["config"][k] for k in differ}
    assert KIMI["router_outputs"] == 384 and KIMI["num_experts_per_tok"] == 8
    assert KIMI["n_routed_experts"] == 12 and KIMI["first_expert_held"] == 0
    assert am.row_width(KIMI) == 576
    assert "deployment" in KIMI and len(KIMI["assumed"]) >= 9


def test_parameter_counts():
    # 7168*1536 + 1536*64*192 + 7168*576 + 512*64*256 + 8192*7168
    assert am.attn_matrix_params(KIMI) == 101_122_048
    assert am.expert_params(KIMI) == 3 * 7168 * 2048 == 44_040_192
    assert am.router_params(KIMI) == 7168 * 384 + 384
    assert am.dense_ffn_params(KIMI) == 3 * 7168 * 18432
    # an expert layer outside its routed experts: 147.93 M
    assert am.expert_layer_shared_params(KIMI) == 101_122_048 + 44_040_192 \
        + 2_752_896 + 1536 + 512 + 2 * 7168
    assert round(am.dense_layer_params(KIMI) / 1e6, 1) == 497.5
    assert am.kind_layers(KIMI) == (1, 6) and am.kind_layers(KIMI, 61) == (1, 60)
    # the published model: "1.04T", "A32B"
    assert round(am.num_params(KIMI, 61, 384, 163840) / 1e9, 1) == 1026.4
    assert round(am.active_params(KIMI, 61, 163840) / 1e9, 1) == 32.9
    # no chip holds one expert layer whole: 33.8 GB of experts
    assert round(384 * am.expert_params(KIMI) * 2 / 1e9, 1) == 33.8
    # the cut: the dense layer and six expert layers of 12 experts, an
    # eighth of the vocabulary: 9.70 GB in bf16; a seventh expert layer 11.05
    assert am.num_params(KIMI) == 4_849_591_552
    assert round(am.num_params(KIMI, 8) * 2 / 1e9, 2) == 11.05
    # the floor of a training cut (4 expert layers of 8 experts) at 16 bytes
    assert round(am.num_params(KIMI, 5, 8) * 16 / 1e9, 1) == 44.7


def test_bytes_a_step():
    assert am.latent_row_bytes(KIMI) == 1152
    # keys and values for 64 heads would be 32,768 bytes a token a layer
    assert 2 * 64 * 128 * 2 == 32_768
    assert am.latent_live_bytes(KIMI, [5000, 300]) == 7 * 5300 * 1152
    shared = am.shared_weight_bytes(KIMI)
    assert shared == 2 * (am.dense_layer_params(KIMI) + 6 * (
        am.expert_layer_shared_params(KIMI) - am.router_params(KIMI))
        + 20480 * 7168 + 7168) + 6 * 4 * am.router_params(KIMI)
    assert am.step_bytes(KIMI, [5000, 300], 2, 30, 4352) == \
        2 * shared + 30 * 44_040_192 * 2 + 2 * 7168 * 2 \
        + 7 * 5300 * 1152 + 7 * 4352 * 1152
    # the issue's step: 32 rows at 8k, 5.9 of 12 experts hit in 6 layers
    step = am.step_bytes(KIMI, [8000] * 32, 1, 5.9 * 6)
    assert round(step / 1e9, 1) == 8.3
    assert round(am.latent_live_bytes(KIMI, [8000] * 32) / 1e9, 1) == 2.1


def test_flops_and_kernels():
    dense = am.active_dense_matrix_params(KIMI)
    assert dense == (101_122_048 + 3 * 7168 * 18432) \
        + 6 * (101_122_048 + 44_040_192 + 7168 * 384) + 20480 * 7168
    attn = 2.0 * 64 * (576 + 512) * 5300 * 7
    assert am.decode_flops(KIMI, [5000, 300], 11) == \
        2.0 * dense * 2 + 2.0 * 44_040_192 * 11 + attn
    assert am.latent_attention_flops(KIMI, [5000, 300]) == attn
    assert am.latent_attention_bytes(KIMI, [5000, 300]) == \
        7 * 5300 * 1152 + 2 * 7 * 64 * 2 * (576 + 512)
    # 121 operations a byte of latents: half the v5e's ridge of 240
    assert round(attn / (7 * 5300 * 1152)) == 121
    assert am.prefill_flops(KIMI, 4, 2000, 0) == 2.0 * dense * 4 \
        + 2.0 * 64 * (576 + 512) * (8000 + 10) * 7
    assert am.expert_matmul_bytes(KIMI, 7, 40) == \
        7 * 44_040_192 * 2 + 40 * (3 * 7168 + 3 * 2048) * 2
    assert am.expert_matmul_flops(KIMI, 40) == 2.0 * 44_040_192 * 40


def _record(**kw):
    steps = [{"contexts": [], "prefill_width": 0, "moe": [0, 0, 0, 0]},
             {"contexts": [5000, 300], "prefill_width": 0,
              "moe": [11, 9, 3, 6]},
             {"contexts": [5001, 301], "prefill_width": 256,
              "prefill_ctx": 512, "moe": [500, 70, 40, 18]}]
    rec = {"config": KIMI, "peaks": V5E, "steps": steps,
           "trace_steps": (1, 3),
           "trace": {"window_s": 0.05, "ops": {
               "%paged_latent_attention.3 = bf16[2,64,512] custom-call(": (0.0004, 14),
               "%paged_attention.1 = bf16[2,64,128] custom-call(": (0.5, 14),
               "%grouped_expert_matmul.1 = bf16[44,2048] custom-call(": (0.02, 20)}},
           "stats_open": {"moe_routed_items_total": 0,
                          "moe_items_elsewhere_total": 0,
                          "moe_experts_hit_total": 0,
                          "moe_layer_steps_total": 0},
           "stats_close": {"moe_routed_items_total": 500,
                           "moe_items_elsewhere_total": 15500,
                           "moe_experts_hit_total": 70,
                           "moe_layer_steps_total": 18,
                           "latent_pool_bytes": 4.11e9,
                           "latent_pool_used_peak": 5376, "num_usable": 7168}}
    rec.update(kw)
    return rec


def _spec(name):
    with open(os.path.join(HERE, "metrics", name + ".longdoc.json")) as f:
        return json.load(f)


def test_readers_over_a_made_up_record(monkeypatch):
    from chipbench import trace_reduce
    monkeypatch.setattr(
        trace_reduce, "op_seconds",
        lambda red, pat: next(((s, n) for name, (s, n) in red["ops"].items()
                               if re.search(pat, name)), (0.0, 0)))
    rec = _record()
    # the kernel's own pattern, which the K/V kernel's name does not meet
    spec = _spec("kernel.paged_latent_attention_roofline")
    want = sum(max(am.latent_attention_flops(KIMI, c) / 197e12,
                   am.latent_attention_bytes(KIMI, c) / 819e9)
               for c in ([5000, 300], [5001, 301])) / 0.0004 * 100
    assert readers.paged_latent_attention_roofline(rec, spec) == \
        pytest.approx(want)
    spec = _spec("kernel.expert_matmul_roofline")
    want = am.expert_matmul_bytes(KIMI, 70, 500) / 819e9 / 0.02 * 100
    assert readers.expert_matmul_roofline(rec, spec) == pytest.approx(want)
    least = (am.step_bytes(KIMI, [5000, 300], 1, 9)
             + am.step_bytes(KIMI, [5001, 301], 2, 61, 768)) / 819e9
    assert readers.decode_step_mfu(rec, {"name": "x"}) == \
        pytest.approx(100 * least / 0.05)
    assert readers.latent_pool_used_peak_share(rec, {}) == 75.0
    # the two shares of a share of the experts are the hybrid family's readers
    assert _spec("moe.experts_hit_share")["reader"].startswith("hybrid:")
    assert shares.experts_hit_share(rec, {}) == \
        pytest.approx(100 * 70 / (18 * 12))
    assert shares.items_held_share(rec, {}) == 3.125


def test_a_share_over_its_ceiling_fails_the_run(monkeypatch):
    from chipbench import trace_reduce
    monkeypatch.setattr(trace_reduce, "op_seconds",
                        lambda red, pat: (1e-6, 14))
    with pytest.raises(ValueError, match="above 105"):
        readers.paged_latent_attention_roofline(
            _record(), _spec("kernel.paged_latent_attention_roofline"))


def test_readers_find_nothing_on_a_program_without_the_counters():
    rec = _record(stats_close={}, stats_open={}, trace=None, peaks=None)
    for s in rec["steps"]:
        del s["moe"]
    for fn in (readers.decode_step_mfu,
               readers.paged_latent_attention_roofline,
               readers.expert_matmul_roofline,
               readers.latent_pool_used_peak_share):
        assert fn(rec, {"name": "x", "op_pattern": "x"}) is None
