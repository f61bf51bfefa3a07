"""Parameters, operations and least bytes of the AI21-Jamba2-3B
configuration against hand-worked numbers, and the readers over them on
made-up records."""
import json
import math
import os

import pytest

from chipbench import arith_jamba as aj
from chipbench.archs import jamba as arch
from chipbench.readers import jamba as readers

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(HERE, "configs", "ai21-jamba2-3b.json")) as f:
    JAMBA = json.load(f)
with open(os.path.join(HERE, "traffic", "longthink-overload.json")) as f:
    ENGINE = json.load(f)["engine"]
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


#: the published config.json's numbers and switches
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 8192, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
    "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}


def test_the_file_keeps_every_published_number():
    assert JAMBA["source"] == ("https://huggingface.co/ai21labs/"
                               "AI21-Jamba2-3B/blob/main/config.json")
    assert {k: JAMBA.get(k) for k in PUBLISHED} == PUBLISHED
    assert JAMBA["reduced"] == []
    assert "deployment" in JAMBA and len(JAMBA["assumed"]) >= 8


def test_parameter_counts():
    # W_in 2560 x 10240, conv 4 x 5120 + 5120, W_x 5120 x 192, the inner
    # norms 160 + 16 + 16, W_dt 160 x 5120 + 5120, A_log 5120 x 16, D 5120,
    # W_out 5120 x 2560
    assert aj.mixer_params(JAMBA) == 41_241_792
    assert aj.mlp_params(JAMBA) == 62_914_560
    assert aj.attn_params(JAMBA) == 13_762_560
    assert aj.kind_layers(JAMBA) == (26, 2)
    assert aj.num_params(JAMBA) == 3_029_337_472
    # the program's own tree holds exactly these
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)

    def count(tree):
        if is_leaf(tree):
            return math.prod(tree[0])
        return sum(count(v) for v in tree.values())
    assert count(arch.shapes(JAMBA)) == aj.num_params(JAMBA)
    assert round(aj.weight_bytes(JAMBA) / 1e9, 2) == 6.06


def test_state_and_kv_bytes_a_row():
    # 16 x 5120 float32 and 3 columns of 5120 bf16 a Mamba-1 layer
    assert aj.state_row_bytes(JAMBA) == 327_680 + 30_720
    assert 26 * aj.state_row_bytes(JAMBA) == 9_318_400
    # a token's key and value: one head of 128 in bf16, two layers
    assert aj.kv_row_bytes(JAMBA) == 512
    assert aj.kv_live_bytes(JAMBA, [1000, 24]) == 2 * 1024 * 512
    # the cell: 256 slots of state, 49,153 pages of 64 tokens
    rows, pages = ENGINE["max_batch"], ENGINE["num_pages"]
    assert round(rows * 26 * aj.state_row_bytes(JAMBA) / 1e9, 2) == 2.39
    kv = pages * ENGINE["page_size"] * 2 * aj.kv_row_bytes(JAMBA)
    assert round(kv / 1e9, 2) == 3.22
    assert pages * ENGINE["page_size"] >= rows * ENGINE["max_len"]


def test_bytes_and_operations_a_step():
    w = aj.weight_bytes(JAMBA)
    got = aj.step_bytes(JAMBA, [5000, 300], 2, chunk_rows=1, chunk_ctx=700)
    assert got == 2 * w + 2 * 2560 * 2 + 2 * 3 * 26 * 358_400 \
        + 2 * 6000 * 512
    # a full decode step: the weights, 256 rows' state read and written
    # (4.77 GB) and their keys; about 14 ms at the chip's bandwidth
    full = aj.step_bytes(JAMBA, [5000] * 256, 1)
    assert round(2 * 256 * 26 * 358_400 / 1e9, 2) == 4.77
    assert 13e-3 < full / V5E["hbm_bytes_per_s"] < 15e-3
    mat = aj.matrix_params(JAMBA)
    assert mat == 26 * (26_214_400 + 983_040 + 819_200 + 13_107_200) \
        + 2 * 13_762_560 + 28 * 62_914_560 + 65536 * 2560
    per = 26 * (2 * 4 * 5120 + 7 * 16 * 5120)
    assert aj.decode_flops(JAMBA, [5000, 300]) == \
        2.0 * mat * 2 + per * 2 + 4.0 * 20 * 128 * 2 * 5300
    head = 65536 * 2560
    assert aj.prefill_flops(JAMBA, 4, 2000) == \
        2.0 * (mat - head) * 4 + 2.0 * head + per * 4 \
        + 4.0 * 20 * 128 * 2 * (8000 + 10)
    assert aj.state_update_bytes(JAMBA, 3) == \
        3 * 4.0 * (2 * 81920 + 3 * 5120 + 32)
    assert aj.scan_bytes(JAMBA, 256, 2) == \
        4.0 * (256 * (3 * 5120 + 32) + 2 * 3 * 81920)


def _record(**kw):
    steps = [{"contexts": [], "prefill_width": 0},
             {"contexts": [5000, 300], "prefill_width": 0},
             {"contexts": [5001, 301], "prefill_width": 256,
              "prefill_ctx": 512}]
    rec = {"config": JAMBA, "peaks": V5E, "steps": steps,
           "trace_steps": (1, 3),
           "trace": {"window_s": 0.05, "ops": {
               "%mamba1_state_update.3 = (f32[256,1,5120]) custom-call(":
                   (0.0004, 10),
               "%mamba1_chunk_scan.7 = (f32[256,5120]) custom-call(":
                   (0.002, 13)}},
           "stats_open": {}, "stats_close": {"state_slots": 256,
                                             "state_slots_used_peak": 192}}
    rec.update(kw)
    return rec


def _op_seconds(monkeypatch):
    from chipbench import trace_reduce
    monkeypatch.setattr(
        trace_reduce, "op_seconds",
        lambda red, pat: next(((s, n) for name, (s, n) in red["ops"].items()
                               if __import__("re").search(pat, name)),
                              (0.0, 0)))


def _spec(name):
    with open(os.path.join(HERE, "metrics", name + ".longthink.json")) as f:
        return json.load(f)


def test_readers_over_a_made_up_record(monkeypatch):
    _op_seconds(monkeypatch)
    rec = _record()
    # 4 row-steps in 26 layers
    want = aj.state_update_bytes(JAMBA, 104) / 819e9 / 0.0004 * 100
    assert readers.mamba1_state_update_roofline(
        rec, _spec("kernel.mamba1_state_update_roofline")) == \
        pytest.approx(want)
    want = aj.scan_bytes(JAMBA, 26 * 256, 26) / 819e9 / 0.002 * 100
    assert readers.mamba1_chunk_scan_roofline(
        rec, _spec("kernel.mamba1_chunk_scan_roofline")) == \
        pytest.approx(want)
    least = (aj.step_bytes(JAMBA, [5000, 300], 1)
             + aj.step_bytes(JAMBA, [5001, 301], 2, 1, 768)) / 819e9
    assert readers.decode_step_mfu(rec, {"name": "x"}) == \
        pytest.approx(100 * least / 0.05)


def test_readers_find_nothing_on_a_program_without_the_kernels():
    rec = _record(trace=None, peaks=None)
    for fn in (readers.decode_step_mfu, readers.mamba1_state_update_roofline,
               readers.mamba1_chunk_scan_roofline):
        assert fn(rec, {"name": "x", "op_pattern": "x"}) is None


def test_the_cell_and_its_metrics_are_declared():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "ai21-jamba2-3b.longthink-overload"
    assert [w["chips"] for w in bench["workloads"] if w["name"] == cell] == [1]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert cell in e2e["serve_tokens_per_s"]["workloads"]
    mine = [m for m in bench["per_layer"] if cell in m.get("workloads", [])]
    assert {m["name"] for m in mine} >= {
        "kernel.mamba1_state_update_roofline.longthink",
        "engine.decode_step_mfu.longthink",
        "engine.mamba1_row_layers_per_decode.longthink"}
    for m in mine:
        assert _spec(m["name"][:-len(".longthink")])["name"] == m["name"]
