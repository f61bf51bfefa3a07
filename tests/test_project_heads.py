"""``generate._project_heads`` changes no value.

The helper keeps the head split out of the projection's dot so that the
compiled serving programs read ``wq`` / ``wk`` / ``wv`` (and ``wq_b``) as
the parameter tree stores them (the compiled text is held by
``tests/test_v5e_aot.py``). Here, on the CPU at small sizes: the chunk and
decode programs' logits, and the engine's tokens, are BIT-identical (``==``)
to what the spelling it replaces gives, ``(x @ w [+ delta]).reshape(...)``,
for a dense, a windowed-MoE, a hybrid and a latent-attention config, at
bfloat16 and float32, with quantised leaves, an int8 KV pool, a
tensor-parallel mesh, an adapter, speculation's verify program and the
non-paged ``generate``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.distributed.mesh import serving_mesh
from paddle_tpu.models import generate as gen, llama
from paddle_tpu.serving import AdapterPool, AdapterRegistry, init_lora
# the three families' configuration files at a small size, as their own
# tests against the plain references make them
from test_hybrid_ssm import SMALL as NEMOTRON
from test_latent_attention import SMALL as KIMI
from test_window_moe import small_config

PAGE, CHUNK = 8, 16


def _replaced_spelling(calls):
    def project(x, w, heads, delta=None):
        calls.append(heads)
        y = x @ w
        if delta is not None:
            y = y + delta
        return y.reshape(x.shape[:-1] + (heads, -1))
    return project


def _both(monkeypatch, run):
    """``run()`` with the helper and with the spelling it replaces; the
    second must have gone through the replacement."""
    new = run()
    calls = []
    monkeypatch.setattr(gen, "_project_heads", _replaced_spelling(calls))
    old = run()
    assert calls
    return new, old


def _dense(dtype):
    cfg = llama.LlamaConfig.tiny(num_layers=2, max_seq_len=64, dtype=dtype)
    return cfg, llama.init_params(jax.random.key(0), cfg)


def _model(family, dtype):
    """``(cfg, params)`` of a small model of ``family`` in ``dtype``."""
    if family == "dense":
        return _dense(dtype)
    if family == "windowed_moe":
        from chipbench.archs import mellum as arch
        c = small_config()
    elif family == "latent":
        from chipbench.archs import kimi_k2 as arch
        c = KIMI
    else:
        from chipbench.archs import nemotron_h as arch
        c = NEMOTRON
    cfg = dataclasses.replace(arch.program_config(c, 64, remat=False),
                              dtype=dtype)
    return cfg, arch.weights(jax.random.key(3), c, dtype=dtype)


def _serve_logits(params, cfg, kv=None):
    """A prompt of 21 tokens as two chunks of the 16-wide chunk program
    (the second against the first's rows), then three decode steps of a
    batch of two with row 0 idle: every program's logits."""
    rs = np.random.default_rng(5)
    prompt = rs.integers(3, cfg.vocab_size, (21,)).astype(np.int32)
    kw, dkw, ckw = {}, {}, {}
    table = jnp.asarray([1, 2, 3, 4], jnp.int32)
    tables = jnp.stack([jnp.zeros_like(table), table])
    if "sliding" in cfg.period:
        # a pool as long as the full one: no page slides out here
        kw["window_pages"] = 5
        ckw["window_table"], dkw["window_tables"] = table, tables
    if cfg.hybrid is not None:
        kw["state_slots"] = 2
        ckw["state_slot"] = 1
    pool = gen.init_paged_cache(cfg, 5, PAGE, kv_dtype=kv, **kw)
    out, done = [], 0
    while done < prompt.size:
        take = min(CHUNK, prompt.size - done)
        toks = np.zeros((1, CHUNK), np.int32)
        toks[0, :take] = prompt[done:done + take]
        lg, pool = gen.paged_prefill_chunk(
            params, jnp.asarray(toks), pool, table, cfg, ctx_cap=CHUNK,
            ctx_len=done, chunk_len=take, **ckw)[:2]
        out.append(np.asarray(lg, np.float32))
        done += take
    active = jnp.asarray([False, True])
    for i in range(3):
        tok = int(np.argmax(out[-1][-1]))
        lg, pool = gen.paged_decode_forward(
            params, jnp.asarray([0, tok], jnp.int32), pool, tables,
            jnp.asarray([0, prompt.size + i], jnp.int32), cfg, active=active,
            **dkw)[:2]
        out.append(np.asarray(lg, np.float32)[1:])
    return out


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("family", ["dense", "windowed_moe", "hybrid",
                                    "latent"])
def test_chunk_and_decode_logits_are_the_replaced_spellings(
        monkeypatch, family, dtype):
    cfg, params = _model(family, jnp.dtype(dtype))
    new, old = _both(monkeypatch, lambda: _serve_logits(params, cfg))
    assert len(new) == 5
    for a, b in zip(new, old):
        assert a.shape == b.shape and np.isfinite(a).all()
        assert (a == b).all()


@pytest.mark.parametrize("tier", ["w8", "int4", "kv8", "w8_kv8"])
def test_quantised_tiers_logits_are_the_replaced_spellings(monkeypatch,
                                                           tier):
    """``_w`` dequantises the leaf before the helper sees it."""
    cfg, params = _dense(jnp.bfloat16)
    if tier != "kv8":
        params = gen.quantize_weights(params, cfg,
                                      bits=4 if tier == "int4" else 8)
        assert "wq_scale" in params["layers"]
    kv = "int8" if "kv8" in tier else None
    new, old = _both(monkeypatch, lambda: _serve_logits(params, cfg, kv=kv))
    for a, b in zip(new, old):
        assert (a == b).all()


def _engine_tokens(params, cfg, registry=None, adapter_ids=(0, 0), tp=None,
                   **kw):
    mesh = serving_mesh(tp) if tp else None
    if registry is not None:
        kw["adapters"] = AdapterPool(cfg, slots=3, rank=4, registry=registry,
                                     mesh=mesh)
    eng = ContinuousBatchingEngine(params, cfg, max_batch=2, page_size=PAGE,
                                   max_len=32, prefill_chunk=PAGE, mesh=mesh,
                                   **kw)
    rs = np.random.RandomState(1)
    reqs = [eng.submit(rs.randint(3, cfg.vocab_size, (n,)).astype(np.int32),
                       max_new_tokens=6, adapter_id=aid)
            for n, aid in zip((5, 11), adapter_ids)]
    eng.run()
    return [np.asarray(r.output) for r in reqs]


@pytest.mark.parametrize("case", ["plain", "tp2", "tp4", "adapter",
                                  "tp2_adapter", "tp2_int4", "verify"])
def test_engine_tokens_are_the_replaced_spellings(monkeypatch, case):
    """The engine's own programs (chunked prefill, decode, speculation's
    verify), single-chip and as shards of a tensor-parallel mesh (``tp4``
    replicates the two KV heads), with an adapter's term added before the
    split and with a quantised leaf."""
    cfg, params = _dense(jnp.float32)
    kw = {"tp": int(case[2]) if case.startswith("tp") else None}
    if "adapter" in case:
        kw["registry"] = AdapterRegistry(cfg)
        kw["registry"].register(1, init_lora(cfg, 4, seed=41))
        kw["adapter_ids"] = (1, 0)
    if "int4" in case:
        kw["weight_bits"] = 4
    if case == "verify":
        kw["spec_k"] = 3
    new, old = _both(monkeypatch, lambda: _engine_tokens(params, cfg, **kw))
    for a, b, n in zip(new, old, (5, 11)):
        assert a.size == n + 6          # the prompt and the six new tokens
        np.testing.assert_array_equal(a, b)


def test_non_paged_generate_is_the_replaced_spelling(monkeypatch):
    cfg, params = _dense(jnp.bfloat16)
    prompt = jnp.asarray(np.random.default_rng(2).integers(
        3, cfg.vocab_size, (2, 9)), jnp.int32)
    new, old = _both(monkeypatch, lambda: np.asarray(gen.generate(
        params, prompt, cfg, max_new_tokens=5)))
    np.testing.assert_array_equal(new, old)
