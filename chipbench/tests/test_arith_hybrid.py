"""Parameters, operations and least bytes of the Nemotron-3-Super
configuration against hand-worked numbers, and the readers over them on
made-up records."""
import json
import os

import pytest

from chipbench import arith_hybrid as ah
from chipbench.readers import hybrid as readers

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(HERE, "configs", "nemotron3-super-120b-a12b.json")) as f:
    NEMO = json.load(f)
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_file_keeps_every_published_width_and_states_its_cut():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert NEMO["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if NEMO.get(k) != v)
    assert differ == sorted(NEMO["reduced"]) == \
        ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert NEMO["published"] == {k: row["config"][k] for k in differ}
    assert NEMO["router_outputs"] == 512 and NEMO["num_experts_per_tok"] == 22
    assert NEMO["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert "deployment" in NEMO and len(NEMO["assumed"]) >= 10


def test_parameter_counts():
    # in_proj 4096 * (8192 + 10240 + 128) = 76,021,760; out 8192*4096
    assert ah.mamba_matrix_params(NEMO) == 76_021_760 + 33_554_432
    # conv 4*10240 + 10240, three vectors of 128, gated norm 8192, norm 4096
    assert ah.mamba_small_params(NEMO) == 40_960 + 10_240 + 384 + 8192 + 4096
    # q and o 4096*4096 each, k and v 4096*256 each
    assert ah.attn_params(NEMO) == 35_651_584
    assert ah.expert_params(NEMO) == 2 * 1024 * 2688 == 5_505_024
    # latent projections 2*4096*1024, shared expert 2*4096*5376
    assert ah.expert_layer_matrix_params(NEMO) == 8_388_608 + 44_040_192
    assert ah.kind_layers(NEMO) == (5, 1, 5)
    assert ah.kind_layers(NEMO, 88) == (40, 8, 40)
    # the published model: 120.7 B
    assert round(ah.num_params(NEMO, 88, 512, 131072) / 1e9, 1) == 120.7
    # a gated (three-matrix) expert would not give the name's 120 B
    gated = ah.num_params(NEMO, 88, 512, 131072) + 40 * 512 * 1024 * 2688
    assert round(gated / 1e9) == 177
    # the cut: one period, 128 experts a layer, a quarter of the vocabulary
    assert ah.num_params(NEMO) == 4_648_163_712      # 9.30 GB in bf16
    # what a token touches in the published model: top-22
    active = (ah.num_params(NEMO, 88, 22, 131072))
    assert round(active / 1e9, 1) == 12.8


def test_bytes_a_step():
    # a row-layer of state: 128*64*128 float32 and 3 columns of 10240 bf16
    assert ah.state_row_bytes(NEMO) == 4_194_304 + 61_440
    assert ah.kv_row_bytes(NEMO) == 2 * 2 * 128 * 2
    assert ah.kv_live_bytes(NEMO, [5000, 300]) == 1 * 5300 * 1024
    shared = ah.shared_weight_bytes(NEMO)
    # everything but the routed experts and the embedding table: 5 Mamba-2
    # layers 548 M, attention 36 M, 5 expert layers' outside 262 M and the
    # head 134 M in bf16, the routers in float32: 2.0 GB
    assert shared == 2 * (5 * 109_640_064 - 5 * 384 + 35_651_584 + 4096
                          + 5 * (52_428_800 + 4096) + 32768 * 4096 + 4096) \
        + 4 * (5 * 384 + 5 * (4096 * 512 + 512))
    assert round(shared / 1e9, 2) == 2.0
    # all 128 held experts of all 5 layers: 7.05 GB
    assert round(ah.expert_bytes(NEMO, 5 * 128) / 1e9, 2) == 7.05
    # 128 rows' state read and written in 5 layers: 5.45 GB
    assert round(2 * 128 * 5 * ah.state_row_bytes(NEMO) / 1e9, 2) == 5.45
    got = ah.step_bytes(NEMO, [5000, 300], 2, 700, chunk_rows=1)
    assert got == 2 * shared + 700 * 5_505_024 * 2 + 2 * 4096 * 2 \
        + 2 * 3 * 5 * 4_255_744 + 5300 * 1024
    # a full decode step's floor at the chip's bandwidth, about 18 ms
    full = ah.step_bytes(NEMO, [6000] * 128, 1, 640)
    assert 17e-3 < full / V5E["hbm_bytes_per_s"] < 19e-3


def test_flops_and_kernels():
    dense = ah.active_dense_params(NEMO)
    assert dense == 5 * 109_576_192 + 35_651_584 \
        + 5 * (52_428_800 + 4096 * 512) + 32768 * 4096
    assert ah.decode_flops(NEMO, [5000, 300], 11) == \
        2.0 * dense * 2 + 2.0 * 5_505_024 * 11 \
        + 5.0 * 128 * 64 * 128 * 10 + 4.0 * 32 * 128 * 5300
    assert ah.ssm_update_bytes(NEMO, 3) == 3 * 4.0 * (
        2 * 1_048_576 + 2 * 8192 + 2 * 16384 + 128)
    assert ah.expert_matmul_bytes(NEMO, 7, 40) == \
        7 * 5_505_024 * 2 + 40 * 2 * (1024 + 2688) * 2
    assert ah.expert_matmul_flops(NEMO, 40) == 2.0 * 5_505_024 * 40
    assert ah.prefill_flops(NEMO, 4, 2000, 0) == 2.0 * dense * 4 \
        + 5 * ah.ssm_scan_flops(NEMO, 4) + 4.0 * 32 * 128 * (8000 + 10)


def _record(**kw):
    steps = [{"contexts": [], "prefill_width": 0, "moe": [0, 0, 0, 0]},
             {"contexts": [5000, 300], "prefill_width": 0,
              "moe": [11, 9, 3, 5]},
             {"contexts": [5001, 301], "prefill_width": 256,
              "prefill_ctx": 512, "moe": [1500, 420, 40, 15]}]
    rec = {"config": NEMO, "peaks": V5E, "steps": steps,
           "trace_steps": (1, 3),
           "trace": {"window_s": 0.05, "ops": {
               "%ssm_state_update.3 = (f32[2,64,128]) custom-call(": (0.0002, 10),
               "%grouped_expert_matmul.1 = bf16[44,2688] custom-call(": (0.02, 20)}},
           "stats_open": {"moe_routed_items_total": 0,
                          "moe_items_elsewhere_total": 0,
                          "moe_experts_hit_total": 0,
                          "moe_layer_steps_total": 0},
           "stats_close": {"moe_routed_items_total": 1500,
                           "moe_items_elsewhere_total": 4500,
                           "moe_experts_hit_total": 420,
                           "moe_layer_steps_total": 15,
                           "state_slots": 128, "state_slots_used_peak": 96}}
    rec.update(kw)
    return rec


def test_readers_over_a_made_up_record(monkeypatch):
    from chipbench import trace_reduce
    monkeypatch.setattr(
        trace_reduce, "op_seconds",
        lambda red, pat: next(((s, n) for name, (s, n) in red["ops"].items()
                               if __import__("re").search(pat, name)),
                              (0.0, 0)))
    rec = _record()
    spec = {"name": "x", "op_pattern": "^%ssm_state_update[\\w.\\-]* = .*custom-call\\("}
    # 4 row-steps in 5 layers
    want = ah.ssm_update_bytes(NEMO, 20) / 819e9 / 0.0002 * 100
    assert readers.ssm_state_update_roofline(rec, spec) == pytest.approx(want)
    spec = {"name": "x", "op_pattern": "^%grouped_expert_matmul[\\w.\\-]* = .*custom-call\\("}
    want = ah.expert_matmul_bytes(NEMO, 420, 1500) / 819e9 / 0.02 * 100
    assert readers.expert_matmul_roofline(rec, spec) == pytest.approx(want)
    least = (ah.step_bytes(NEMO, [5000, 300], 1, 9)
             + ah.step_bytes(NEMO, [5001, 301], 2, 411, 1)) / 819e9
    assert readers.decode_step_mfu(rec, {"name": "x"}) == \
        pytest.approx(100 * least / 0.05)
    assert readers.state_slots_used_peak_share(rec, {}) == 75.0
    assert readers.experts_hit_share(rec, {}) == \
        pytest.approx(100 * 420 / (15 * 128))
    assert readers.items_held_share(rec, {}) == 25.0


def test_readers_find_nothing_on_a_program_without_the_counters():
    rec = _record(stats_close={}, stats_open={}, trace=None, peaks=None)
    for s in rec["steps"]:
        del s["moe"]
    for fn in (readers.decode_step_mfu, readers.ssm_state_update_roofline,
               readers.expert_matmul_roofline,
               readers.state_slots_used_peak_share,
               readers.experts_hit_share, readers.items_held_share):
        assert fn(rec, {"name": "x", "op_pattern": "x"}) is None
