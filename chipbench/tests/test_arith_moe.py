"""Parameters, operations and least bytes of the Mellum2 configuration
against hand-worked numbers, and the readers over them on made-up records."""
import json
import os

import pytest

from chipbench import arith_moe
from chipbench.readers import moe as readers

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(HERE, "configs", "mellum2-12b-a2.5b.json")) as f:
    MELLUM = json.load(f)
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_parameter_counts():
    # attention 2304*4096 (q) + 2*2304*512 (k, v) + 4096*2304 (o) = 21,233,664
    assert arith_moe.attn_params(MELLUM) == 21_233_664
    # an expert 3*2304*896 = 6,193,152; 64 of them 396,361,728; router 147,456
    assert arith_moe.expert_params(MELLUM) == 6_193_152
    layer = 21_233_664 + 147_456 + 64 * 6_193_152 + 2 * 2304
    assert layer == 417_747_456
    # twelve layers, two tables of 98,304*2,304, the final norm
    assert arith_moe.num_params(MELLUM) == 12 * layer + 2 * 226_492_416 + 2304
    assert round(arith_moe.num_params(MELLUM) * 2 / 1e9, 2) == 10.93
    full = dict(MELLUM, num_hidden_layers=28)
    assert round(arith_moe.num_params(full) / 1e9, 2) == 12.15
    # a token multiplies attention, router, 8 experts a layer and the head
    assert arith_moe.active_matrix_params(MELLUM) == \
        12 * (21_233_664 + 147_456 + 8 * 6_193_152) + 226_492_416
    assert arith_moe.kind_layers(MELLUM) == (9, 3)


def test_keys_and_bytes_a_step():
    # a row at 5,000 reads 1,024 keys in 9 sliding layers, all in 3 full
    # ones; a row at 300 reads 300 everywhere
    assert arith_moe.keys_seen(MELLUM, [5000, 300]) == \
        (9 * (1024 + 300), 3 * 5300)
    assert arith_moe.kv_row_bytes(MELLUM) == 2 * 4 * 128 * 2
    assert arith_moe.kv_live_bytes(MELLUM, [5000, 300]) == \
        (9 * 1324 + 3 * 5300) * 2048
    # the shared weights: attention, norms and head in bf16, router float32
    shared = (12 * (21_233_664 + 4608) + 226_492_416 + 2304) * 2 \
        + 12 * 147_456 * 4
    assert arith_moe.shared_weight_bytes(MELLUM) == shared
    # 700 experts hit over the step's layers and programs
    assert arith_moe.step_bytes(MELLUM, [5000, 300], 2, 700) == \
        2 * shared + 700 * 6_193_152 * 2 + 2 * 2304 * 2 \
        + (9 * 1324 + 3 * 5300) * 2048
    # all 64 experts of all 12 layers are 9.5 of a decode step's bytes
    assert round(arith_moe.expert_bytes(MELLUM, 12 * 64) / 1e9, 2) == 9.51


def test_flops():
    act = arith_moe.active_matrix_params(MELLUM)
    assert arith_moe.decode_flops(MELLUM, [5000, 300]) == \
        2.0 * act * 2 + 4.0 * 32 * 128 * (9 * 1324 + 3 * 5300)
    # a chunk of 4 after 2,000: full layers see 2000*4 + 10 keys, sliding
    # ones the window for each of the four
    assert arith_moe.prefill_flops(MELLUM, 4, 2000) == \
        2.0 * act * 4 + 4.0 * 32 * 128 * (3 * 8010 + 9 * 4 * 1024)
    assert arith_moe.prefill_flops(MELLUM, 4, 0) == \
        2.0 * act * 4 + 4.0 * 32 * 128 * (3 * 10 + 9 * 10)
    assert arith_moe.expert_matmul_flops(MELLUM, 256) == 2.0 * 6_193_152 * 256
    assert arith_moe.expert_matmul_bytes(MELLUM, 63, 256) == \
        63 * 6_193_152 * 2 + 256 * (3 * 2304 + 3 * 896) * 2


def record(**over):
    steps = [{"contexts": [], "prefill_width": 0, "moe": [0, 0, 0, 0]}]
    for i in range(1, 4):
        steps.append({"contexts": [4000] * 32, "prefill_width": 256,
                      "prefill_ctx": 1024,
                      "moe": [i * 27_648, i * 1500, i * 400, i * 24]})
    rec = {"trace": {"window_s": 0.150, "ops": {
        "%ragged-dot-none.3 = bf16[256,896] custom-call(...)": 0.100,
        "%ragged-dot-metadata.1 = (s32[65]) custom-call(...)": 0.001,
        "%paged_attention.7 = bf16[32,32,128] custom-call(...)": 0.008},
        "counts": {}}, "trace_steps": (1, 4), "steps": steps, "peaks": V5E,
        "config": MELLUM, "stats_open": {"spans": {}},
        "stats_close": {"spans": {}}}
    rec["trace"]["counts"] = {k: 1.0 for k in rec["trace"]["ops"]}
    rec.update(over)
    return rec


def spec(name):
    with open(os.path.join(HERE, "metrics", name + ".json")) as f:
        return json.load(f)


def test_rooflines_and_the_step_share_on_a_made_up_trace():
    rec = record()
    # the experts: 4,500 hit and 82,944 items in the traced steps, 100 ms
    least = max(2.0 * 6_193_152 * 82_944 / 197e12,
                (4500 * 6_193_152 * 2 + 82_944 * 9600 * 2) / 819e9)
    got = readers.expert_matmul_roofline(
        rec, spec("kernel.expert_matmul_roofline.repo"))
    assert got == pytest.approx(100 * least / 0.100)
    live = 3 * 32 * (9 * 1024 + 3 * 4000) * 2048
    got = readers.paged_attention_roofline(
        rec, spec("kernel.paged_attention_roofline.repo"))
    assert got == pytest.approx(100 * live / 819e9 / 0.008)
    step = max((arith_moe.decode_flops(MELLUM, [4000] * 32)
                + arith_moe.prefill_flops(MELLUM, 256, 1024)) / 197e12,
               arith_moe.step_bytes(MELLUM, [4000] * 32, 2, 1500) / 819e9)
    got = readers.decode_step_mfu(rec, spec("engine.decode_step_mfu.repo"))
    assert got == pytest.approx(100 * 3 * step / 0.150)


def test_a_program_without_the_counters_reads_nothing():
    rec = record()
    for s in rec["steps"]:
        del s["moe"]
    for name, fn in (("engine.decode_step_mfu.repo", readers.decode_step_mfu),
                     ("kernel.expert_matmul_roofline.repo",
                      readers.expert_matmul_roofline),
                     ("moe.experts_hit_share.repo", readers.experts_hit_share),
                     ("moe.max_expert_load_ratio.repo",
                      readers.max_expert_load_ratio),
                     ("cache.window_pages_released_per_step.repo",
                      readers.window_pages_released_per_step),
                     ("cache.full_pool_used_peak_share.repo",
                      readers.full_pool_used_peak_share)):
        assert fn(rec, spec(name)) is None, name
    # no peaks: a CPU rehearsal reports no share of one
    assert readers.paged_attention_roofline(
        record(peaks=None), spec("kernel.paged_attention_roofline.repo")) is None


def test_a_share_above_105_percent_fails_the_run():
    rec = record()
    rec["trace"]["ops"] = {k: v / 100 for k, v in rec["trace"]["ops"].items()}
    with pytest.raises(ValueError, match="above"):
        readers.expert_matmul_roofline(
            rec, spec("kernel.expert_matmul_roofline.repo"))


def test_counter_readers():
    rec = record(
        stats_open={"moe_experts_hit_total": 100, "moe_layer_steps_total": 10,
                    "moe_max_expert_load_total": 50,
                    "moe_routed_items_total": 1000,
                    "window_pages_released_total": 5,
                    "spans": {"sched.step": {"count": 10, "ns": 0}}},
        stats_close={"moe_experts_hit_total": 100 + 63 * 24,
                     "moe_layer_steps_total": 10 + 24,
                     "moe_max_expert_load_total": 50 + 24 * 9,
                     "moe_routed_items_total": 1000 + 24 * 256,
                     "window_pages_released_total": 5 + 11,
                     "full_pool_used_peak": 5000, "num_usable": 5120,
                     "spans": {"sched.step": {"count": 32, "ns": 0}}})
    assert readers.experts_hit_share(rec, {}) == pytest.approx(100 * 63 / 64)
    assert readers.max_expert_load_ratio(rec, {}) == pytest.approx(9 * 64 / 256)
    assert readers.window_pages_released_per_step(rec, {}) == pytest.approx(0.5)
    assert readers.full_pool_used_peak_share(rec, {}) == \
        pytest.approx(100 * 5000 / 5120)
