#!/usr/bin/env python
"""Three conditions of the serving design, checked on the source text.

``check_fault_sites``: every site in ``serving/resilience.py``'s
``ENGINE_SITES`` / ``CLUSTER_SITES`` has a ``fault_point("<site>")``
call in a hot-path module, or the chaos coverage claims sites it never
exercised. ``check_sync_points``: the scheduler and the engine's
dispatch-path functions hold no device-to-host read, or the overlapped
step falls back to a synchronous chain. ``check_hybrid_names``: the
counters, gauges and named scopes of the recurrent-state pool, the latent
pool and the expert share, the chunk-step counters and the stall totals
are fed where the tracing says. Runs in tier-1
(tests/test_instrumentation_lint.py); standalone:

    python tools/check_instrumentation.py
"""
from __future__ import annotations

import os
import sys


#: modules allowed to host fault-injection call sites (the serving hot
#: path) — the site-coverage rule greps these
_FAULT_SITE_MODULES = (
    "paddle_tpu/serving/paged_cache.py",
    "paddle_tpu/serving/scheduler.py",
    "paddle_tpu/serving/host_tier.py",
    "paddle_tpu/serving/cluster.py",
    "paddle_tpu/serving/adapters.py",
    "paddle_tpu/serving/wal.py",
    "paddle_tpu/serving/rpc.py",
    "paddle_tpu/serving/fabric.py",
    "paddle_tpu/inference/predictor.py",
)


def check_fault_sites(root: str) -> list:
    """ISSUE 8 rule: every FaultInjector site name declared in
    ``serving/resilience.py``'s ``SITES`` tuple must have a matching
    ``fault_point("<site>")`` call threaded through a hot-path module —
    a declared-but-unthreaded site would silently produce NO
    ``serving_fault_*{site=...}`` counter label, and chaos coverage of
    that site would be a no-op that still claims the site was
    exercised."""
    import re
    problems = []
    res_path = os.path.join(root, "paddle_tpu/serving/resilience.py")
    if not os.path.exists(res_path):
        return [f"paddle_tpu/serving/resilience.py: file missing"]
    with open(res_path, encoding="utf-8") as f:
        src = f.read()
    # SITES is composed from the engine-plane and cluster-plane
    # tuples (ISSUE 13) — collect the declared names from both
    sites = []
    for name in ("ENGINE_SITES", "CLUSTER_SITES"):
        m = re.search(rf"^{name}\s*=\s*\(([^)]*)\)", src, re.M)
        if not m:
            return [f"paddle_tpu/serving/resilience.py: {name} "
                    f"tuple missing"]
        sites += re.findall(r"\"([a-z_]+)\"", m.group(1))
    if not sites:
        return ["paddle_tpu/serving/resilience.py: SITES tuples empty"]
    hot = ""
    for rel in _FAULT_SITE_MODULES:
        path = os.path.join(root, rel)
        if os.path.exists(path):
            with open(path, encoding="utf-8") as f:
                hot += f.read()
    for site in sites:
        if f'fault_point("{site}")' not in hot:
            problems.append(
                f"paddle_tpu/serving/resilience.py: SITES declares "
                f"{site!r} but no hot-path module calls "
                f"fault_point(\"{site}\") — the serving_fault_* "
                f"counters would never carry that site label")
    return problems


#: the sync-point discipline of the overlapped runtime (ISSUE 12):
#: module -> function names whose bodies must stay FREE of device→host
#: sync idioms (single-argument ``np.asarray(...)`` fetches and
#: ``block_until_ready``). None = the whole module. The scheduler's
#: host plane and the engine's DISPATCH-path functions plan and launch
#: only — every fetch of a step result belongs in the commit helpers
#: (_decode_commit / _spec_commit / _commit_chunk), or the overlap
#: pipeline silently degrades back to a synchronous chain.
_SYNC_FREE = {
    "paddle_tpu/serving/scheduler.py": None,
    # _tree_dispatch launches the one-forward tree verify and must not
    # fetch its logits or KV rows (both ride the InFlightStep to
    # _tree_commit); _propose_model_drafts is deliberately NOT listed —
    # the draft loop is sequential by construction (each draft token
    # feeds the next step), so its per-step logits fetch is the design,
    # not a regression
    "paddle_tpu/inference/predictor.py": (
        "decode_dispatch", "spec_dispatch", "prefill_dispatch",
        "ready_mask", "propose_drafts", "_rows_on_device",
        "_tree_dispatch"),
    # the tracing layer (ISSUE 16) runs INSIDE the hot path on every
    # span close — it must never fetch a device value or fence; its
    # zero-device-syncs contract is what lets call sites fire between
    # dispatch and commit
    "paddle_tpu/observability/tracing.py": None,
    # the RPC layer (ISSUE 19) frames host bytes only — it must never
    # import jax or fetch a device value; KV payloads reach it already
    # exported as host numpy views, and keeping it device-blind is
    # what lets the fabric server run as a jax-free process
    "paddle_tpu/serving/rpc.py": None,
    # a hybrid model's forwards run inside the dispatched programs: no
    # host read of a device value anywhere in them
    "paddle_tpu/models/hybrid.py": None,
}

#: device-sync idioms: a bare one-argument np.asarray (dtype-annotated
#: conversions of host arrays pass — they never touch device values on
#: these paths) and any block_until_ready
_SYNC_RE = (r"(?<!j)np\.asarray\([^,()]*(\([^()]*\))?[^,()]*\)(?!\s*,)",
            r"block_until_ready")


def _function_bodies(src: str, names) -> str:
    """Concatenate the bodies of the named top-level-in-class defs
    (selected by indentation: a body line is any line more indented
    than its ``def``)."""
    import re
    out = []
    lines = src.splitlines()
    for name in names:
        for i, line in enumerate(lines):
            m = re.match(rf"(\s*)def {re.escape(name)}\(", line)
            if not m:
                continue
            indent = len(m.group(1))
            j = i + 1
            while j < len(lines):
                ln = lines[j]
                if ln.strip() and (len(ln) - len(ln.lstrip())) <= indent:
                    break
                out.append(ln)
                j += 1
    return "\n".join(out)


def check_sync_points(root: str) -> list:
    """ISSUE 12 rule: no ``np.asarray`` / ``block_until_ready`` on
    step results outside the commit helpers in the scheduler/predictor
    hot paths. The textual heuristic flags single-argument
    ``np.asarray(x)`` (the device-fetch idiom) and any
    ``block_until_ready`` inside the :data:`_SYNC_FREE` scopes —
    dtype-annotated conversions (``np.asarray(x, np.int32)``) are
    host-side and pass."""
    import re
    problems = []
    for rel, names in _SYNC_FREE.items():
        path = os.path.join(root, rel)
        if not os.path.exists(path):
            problems.append(f"{rel}: file missing")
            continue
        with open(path, encoding="utf-8") as f:
            src = f.read()
        scope = src if names is None else _function_bodies(src, names)
        where = ("module" if names is None
                 else "dispatch-path functions " + "/".join(names))
        for pat in _SYNC_RE:
            for m in re.finditer(pat, scope):
                problems.append(
                    f"{rel}: device-sync idiom {m.group(0)!r} in the "
                    f"{where} — step results must be fetched only in "
                    f"the commit helpers (the overlapped runtime's "
                    f"single-fence contract, ISSUE 12)")
    return problems


#: the names the recurrent-state pool, the latent pool and the expert share
#: are read by (``engine.stats()``, the device trace): each has to be fed
#: somewhere in the modules listed, or a per-layer metric reads a total that
#: never moves
_HYBRID_NAMES = {
    "ssm_state_rows_total": "paddle_tpu/inference/predictor.py",
    "ssm_chunk_tokens_total": "paddle_tpu/inference/predictor.py",
    "ssm_state_rebuilds_total": "paddle_tpu/inference/predictor.py",
    "moe_items_elsewhere_total": "paddle_tpu/inference/predictor.py",
    "ssm_state_resets_total": "paddle_tpu/serving/paged_cache.py",
    "state_slots_used_peak": "paddle_tpu/serving/paged_cache.py",
    "state_pool_bytes": "paddle_tpu/serving/paged_cache.py",
    'named_scope("ssm_state_update")': "paddle_tpu/ops/pallas/ssm.py",
    'named_scope("ssm_chunk_scan")': "paddle_tpu/models/hybrid.py",
    # the same two counters by the kind of state-space layer, and the
    # Mamba-1 kernels' scopes and custom-call names
    'f"{self._state_kind}_state_rows_total"':
        "paddle_tpu/inference/predictor.py",
    'f"{self._state_kind}_chunk_tokens_total"':
        "paddle_tpu/inference/predictor.py",
    'named_scope("mamba1_state_update")': "paddle_tpu/ops/pallas/mamba1.py",
    'name="mamba1_state_update"': "paddle_tpu/ops/pallas/mamba1.py",
    'named_scope("mamba1_chunk_scan")': "paddle_tpu/ops/pallas/mamba1.py",
    'name="mamba1_chunk_scan"': "paddle_tpu/ops/pallas/mamba1.py",
    'named_scope("mamba1_conv_step")': "paddle_tpu/ops/pallas/mamba1.py",
    'name="mamba1_conv_step"': "paddle_tpu/ops/pallas/mamba1.py",
    "latent_tokens_attended_total": "paddle_tpu/inference/predictor.py",
    "latent_decode_rows_total": "paddle_tpu/inference/predictor.py",
    "latent_chunk_tokens_total": "paddle_tpu/inference/predictor.py",
    "latent_chunk_keys_attended_total": "paddle_tpu/inference/predictor.py",
    "latent_pool_bytes": "paddle_tpu/serving/paged_cache.py",
    "latent_pool_used_peak": "paddle_tpu/serving/paged_cache.py",
    'named_scope("paged_latent_attention")':
        "paddle_tpu/ops/pallas/paged_latent_attention.py",
    'named_scope("latent_chunk_attention")':
        "paddle_tpu/ops/pallas/paged_latent_attention.py",
    'name="latent_chunk_attention"':
        "paddle_tpu/ops/pallas/paged_latent_attention.py",
    # the K/V chunk's attention over the row's pages (its kernel and twin)
    'named_scope("kv_chunk_attention")':
        "paddle_tpu/ops/pallas/paged_chunk_attention.py",
    'name="kv_chunk_attention"':
        "paddle_tpu/ops/pallas/paged_chunk_attention.py",
    # the keys a windowed config's programs read, by kind, and the
    # attention's output gate in the serving programs
    "window_keys_attended_total": "paddle_tpu/inference/predictor.py",
    "full_keys_attended_total": "paddle_tpu/inference/predictor.py",
    "chunk_window_keys_attended_total": "paddle_tpu/inference/predictor.py",
    "chunk_full_keys_attended_total": "paddle_tpu/inference/predictor.py",
    'named_scope("attention_output_gate")': "paddle_tpu/models/generate.py",
    # the steps that committed a chunk and the stall totals (PR 36)
    "steps_committing_chunk_total": "paddle_tpu/serving/scheduler.py",
    "steps_committing_chunk_ns_total": "paddle_tpu/serving/scheduler.py",
    "stalls_total": "paddle_tpu/observability/spans.py",
    "stall_ns_total": "paddle_tpu/observability/spans.py",
}


def check_hybrid_names(root: str) -> list:
    """Counters, gauges and named scopes of the state pool, the latent pool
    and the expert share exist where the tracing says they are fed."""
    problems = []
    for name, rel in _HYBRID_NAMES.items():
        path = os.path.join(root, rel)
        if not os.path.exists(path):
            problems.append(f"{rel}: file missing")
            continue
        with open(path, encoding="utf-8") as f:
            if name not in f.read():
                problems.append(f"{rel}: nothing feeds {name!r}")
    return problems


def check(root: str) -> list:
    """Returns a list of human-readable violation strings (empty = ok)."""
    return (check_fault_sites(root) + check_sync_points(root)
            + check_hybrid_names(root))


def main() -> int:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    problems = check(root)
    if problems:
        for p in problems:
            print(f"check_instrumentation: {p}", file=sys.stderr)
        return 1
    print("check_instrumentation: fault sites, sync points and the state "
          "and latent pools' names ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
