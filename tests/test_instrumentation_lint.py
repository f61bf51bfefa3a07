"""Fast tier-1 guard of two conditions of the serving design
(tools/check_instrumentation.py): every declared fault site is threaded
through a hot-path module, and the dispatch path holds no device-to-host
read."""
import importlib.util
import os

import pytest


def _load_checker():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "tools", "check_instrumentation.py")
    spec = importlib.util.spec_from_file_location(
        "check_instrumentation", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, root


def test_fault_sites_threaded_and_dispatch_path_sync_free():
    mod, root = _load_checker()
    problems = mod.check(root)
    assert problems == [], "\n".join(problems)


def _plant_unthreaded_site(tree):
    (tree / "paddle_tpu/serving/resilience.py").write_text(
        'ENGINE_SITES = ("decode_step", "never_threaded")\n'
        'CLUSTER_SITES = ()\n')
    (tree / "paddle_tpu/inference/predictor.py").write_text(
        'fault_point("decode_step")\n')
    return "never_threaded"


def _plant_sync_in_dispatch(tree):
    (tree / "paddle_tpu/inference/predictor.py").write_text(
        "class E:\n"
        "    def decode_dispatch(self):\n"
        "        nxt = np.asarray(self._step(tok))\n"
        "    def _decode_commit(self):\n"
        "        return np.asarray(nxt)\n")
    return "decode_dispatch"


def _plant_unfed_counter(tree):
    (tree / "paddle_tpu/inference/predictor.py").write_text(
        'count("ssm_state_rows_total", 1)\ncount("ssm_chunk_tokens_total")\n'
        'count("moe_items_elsewhere_total")\n')
    return "ssm_state_rebuilds_total"


def _plant_unfed_latent_counter(tree):
    (tree / "paddle_tpu/inference/predictor.py").write_text(
        'count("latent_tokens_attended_total", 1)\n'
        'count("latent_decode_rows_total")\n')
    return "latent_chunk_tokens_total"


def _plant_lost_latent_scope(tree):
    (tree / "paddle_tpu/ops/pallas").mkdir(parents=True)
    (tree / "paddle_tpu/ops/pallas/paged_latent_attention.py").write_text(
        'name="paged_latent_attention"\n')
    return 'named_scope("paged_latent_attention")'


def _plant_unfed_chunk_keys_counter(tree):
    (tree / "paddle_tpu/inference/predictor.py").write_text(
        'count("latent_chunk_tokens_total", take)\n')
    return "latent_chunk_keys_attended_total"


def _plant_lost_chunk_kernel_name(tree):
    (tree / "paddle_tpu/ops/pallas").mkdir(parents=True)
    (tree / "paddle_tpu/ops/pallas/paged_latent_attention.py").write_text(
        'named_scope("latent_chunk_attention")\n'
        'name="paged_latent_attention"\n')
    return 'name="latent_chunk_attention"'


def _plant_lost_kv_chunk_scope(tree):
    (tree / "paddle_tpu/ops/pallas").mkdir(parents=True)
    (tree / "paddle_tpu/ops/pallas/paged_chunk_attention.py").write_text(
        'name="kv_chunk_attention"\n')
    return 'named_scope("kv_chunk_attention")'


def _plant_lost_chunk_step_counter(tree):
    (tree / "paddle_tpu/serving/scheduler.py").write_text(
        'sp.count("steps_committing_chunk_total", 1)\n')
    return "steps_committing_chunk_ns_total"


def _plant_lost_stall_total(tree):
    (tree / "paddle_tpu/observability").mkdir(parents=True)
    (tree / "paddle_tpu/observability/spans.py").write_text(
        'self._counters["stalls_total"] += 1\n')
    return "stall_ns_total"


def _plant_unfed_chunk_window_keys(tree):
    (tree / "paddle_tpu/inference/predictor.py").write_text(
        'count("window_keys_attended_total")\n'
        'count("full_keys_attended_total")\n'
        'count("chunk_full_keys_attended_total")\n')
    return "chunk_window_keys_attended_total"


def _plant_lost_gate_scope(tree):
    (tree / "paddle_tpu/models").mkdir(parents=True)
    (tree / "paddle_tpu/models/generate.py").write_text(
        'def _gate(o, u, lp, heads):\n    return o\n')
    return 'named_scope("attention_output_gate")'


def _plant_lost_mamba1_kernel_name(tree):
    (tree / "paddle_tpu/ops/pallas").mkdir(parents=True)
    (tree / "paddle_tpu/ops/pallas/mamba1.py").write_text(
        'named_scope("mamba1_state_update")\n'
        'named_scope("mamba1_chunk_scan")\n'
        'name="mamba1_chunk_scan"\n')
    return 'name="mamba1_state_update"'


@pytest.mark.parametrize("rule, plant", [
    ("check_hybrid_names", _plant_lost_mamba1_kernel_name),
    ("check_hybrid_names", _plant_unfed_chunk_window_keys),
    ("check_hybrid_names", _plant_lost_gate_scope),
    ("check_fault_sites", _plant_unthreaded_site),
    ("check_sync_points", _plant_sync_in_dispatch),
    ("check_hybrid_names", _plant_unfed_counter),
    ("check_hybrid_names", _plant_unfed_latent_counter),
    ("check_hybrid_names", _plant_lost_latent_scope),
    ("check_hybrid_names", _plant_unfed_chunk_keys_counter),
    ("check_hybrid_names", _plant_lost_chunk_kernel_name),
    ("check_hybrid_names", _plant_lost_kv_chunk_scope),
    ("check_hybrid_names", _plant_lost_chunk_step_counter),
    ("check_hybrid_names", _plant_lost_stall_total)])
def test_checker_flags_a_planted_violation(tmp_path, rule, plant):
    """The lint itself must fail, and name the culprit, when a declared
    site loses its fault_point or a dispatch function reads the device."""
    mod, _ = _load_checker()
    for d in ("paddle_tpu/serving", "paddle_tpu/inference"):
        (tmp_path / d).mkdir(parents=True)
    culprit = plant(tmp_path)
    hits = [p for p in getattr(mod, rule)(str(tmp_path)) if culprit in p]
    assert len(hits) == 1, hits
