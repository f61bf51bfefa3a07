"""Serving subsystem: paged KV cache + continuous batching + the
SLO-aware scheduler.

- :mod:`paddle_tpu.serving.paged_cache` — global page pools, per-request
  block tables, the host-side :class:`BlockAllocator` (refcounted pages,
  alloc/free/defrag stats), the :class:`PrefixCache` hash-trie and the
  :class:`PagedKVCache` bundle (incl. the ``evict_for_preempt`` API).
- :mod:`paddle_tpu.serving.policy` — :class:`Priority` classes,
  structured :class:`FinishReason`, the :class:`TokenBudgetPlanner`
  step packer and the :class:`PreemptionPolicy` victim selector.
- :mod:`paddle_tpu.serving.scheduler` — :class:`ServingScheduler`, the
  priority/deadline/preemption control plane over the engine.
- :mod:`paddle_tpu.serving.speculative` — :class:`NgramProposer`
  (model-free prompt-lookup drafting), :class:`Speculator` (per-row
  acceptance-rate EMA + adaptive draft length) and the greedy
  :func:`longest_accepted_prefix` acceptance rule for the engine's
  batched-verify ``spec_step``.
- :mod:`paddle_tpu.serving.resilience` — fault-tolerant serving:
  :class:`FaultInjector` (deterministic seeded fault injection at the
  named hot-path :data:`~paddle_tpu.serving.resilience.SITES`),
  :class:`EngineSupervisor` (write-ahead :class:`RequestJournal`,
  token-identical crash recovery via the resume replay, circuit
  breaker + degraded-mode ladder, drain/restore with prefix-trie
  persistence).
- :mod:`paddle_tpu.serving.host_tier` — the hierarchical KV tier
  (ISSUE 10): :class:`HostPageStore` (host-numpy page pool with an
  optional standing on-disk layer) and :class:`TieredKVCache`
  (preemption swap-out/swap-in under the allocator, prefix-trie
  demote/promote, write-through prefix persistence across restarts).
- :mod:`paddle_tpu.serving.cluster` / :mod:`paddle_tpu.serving.router`
  — the disaggregated serving tier (ISSUE 9): :class:`ServingCluster`
  (N supervised replicas, prefill→decode KV handoff over the page
  export/import APIs, failover and rolling drain/upgrade) routed by
  :class:`ClusterRouter` (prefix-affinity placement, load/SLO-aware
  dispatch, per-tenant fair share + :class:`TenantQuota` rate limits).
- :mod:`paddle_tpu.serving.traffic` — the trace-driven traffic harness
  (ISSUE 13): :func:`synth_trace` (seeded open-loop traces — tenant
  prefix families, bursty/diurnal arrivals, mixed priority/deadline/
  length), :class:`FakeClock`, and :func:`run_trace` →
  :class:`SLOReport` (p99 TTFT, per-token latency, deadline-met
  fraction, goodput-under-SLO). The cluster side adds
  :class:`~paddle_tpu.serving.router.AdmissionController`
  (deadline-infeasible submissions shed at the door) and
  :class:`~paddle_tpu.serving.cluster.ClusterAutoscaler` (hysteresis
  scale up/down through the ``retire_replica`` drain path).
- :mod:`paddle_tpu.serving.adapters` — the multi-tenant adapter plane
  (ISSUE 14): :class:`AdapterRegistry` (the tenant population's packed
  q/o LoRA factors), :class:`AdapterPool` (device-resident refcounted
  slots with LRU reclaim, host-tier demote/promote, rank-bucketed
  compile keys, tp column-sharded B factors) and the
  :func:`init_lora` / :func:`merge_lora` reference helpers — one
  engine serves thousands of fine-tuned variants with the base
  weights loaded once.
- :mod:`paddle_tpu.serving.constraints` — grammar/JSON-schema
  constrained decoding: :class:`TokenDFA` (+ the
  :func:`dfa_from_sequences` / :func:`dfa_from_regex` /
  :func:`json_schema_dfa` compilers) applied as per-row logit masks in
  the engine's sampling step, with :class:`ConstraintState` advancing
  at commit.
- sampled speculation (ISSUE 14) lives in
  :mod:`paddle_tpu.serving.speculative`:
  :func:`rejection_sample_tokens` lifts spec decode's greedy-only
  restriction with standard min(1, p/q) rejection sampling.
- :mod:`paddle_tpu.serving.wal` — the crash-durable journal plane
  (ISSUE 15): :class:`WriteAheadLog` (segmented CRC-framed on-disk
  log under the request journal, configurable fsync ladder,
  incremental checkpoints that compact the log without stopping
  admissions) and :func:`recover_state` (torn-tail truncation +
  checkpoint-plus-suffix replay) — the machinery behind
  :meth:`EngineSupervisor.recover_from_disk` /
  :meth:`ServingCluster.recover_from_disk` cold-restart recovery.
- :mod:`paddle_tpu.serving.rpc` / :mod:`paddle_tpu.serving.node` /
  :mod:`paddle_tpu.serving.fabric` /
  :mod:`paddle_tpu.serving.multiproc` — the multi-PROCESS serving
  cluster (ISSUE 19): a minimal length-prefixed CRC-framed socket RPC
  layer (:class:`RpcClient` / :class:`RpcServer` — torn/corrupt frames
  detected, bounded idempotent retry, typed remote exceptions),
  :class:`~paddle_tpu.serving.node.ReplicaNode` worker processes (one
  supervisor + scheduler each, per-replica WAL dir as durable process
  identity), the shared content-addressed KV fabric
  (:class:`FabricServer` / :class:`FabricClient` — the PR 10 standing
  prefix store as a cluster-wide service, CRC-verified promotes,
  quarantine-on-corrupt) and :class:`MultiProcessCluster` — the
  in-process cluster control plane re-hosted over RPC stubs,
  token-identical to :class:`ServingCluster` on the same trace,
  ``kill -9`` of a replica process handled as WAL-recovering failover.
- the paged attention op lives in
  :mod:`paddle_tpu.ops.pallas.paged_attention` (a Pallas kernel that
  reads each row's live pages, all kv heads of a page at once, from the
  pool as it is stored + a pure-lax fallback) and the
  continuous-batching engine in
  :mod:`paddle_tpu.inference.predictor`
  (:class:`~paddle_tpu.inference.ContinuousBatchingEngine`).
"""
from .paged_cache import (  # noqa: F401
    TRASH_PAGE, BlockAllocator, PagedKVCache, PoolExhausted, PrefixCache,
)
from .policy import (  # noqa: F401
    FinishReason, PreemptionPolicy, Priority, StepPlan,
    TokenBudgetPlanner,
)
from .resilience import (  # noqa: F401
    DEGRADED_MODES, SITES, CorruptionDetected, EngineDead,
    EngineSupervisor, FaultInjector, InjectedFault, RequestJournal,
    StepStalled, fault_point, load_drain_checkpoint,
)
from .scheduler import ServingScheduler  # noqa: F401
from .speculative import (  # noqa: F401
    NgramProposer, Speculator, TreeDraft, build_comb_tree,
    longest_accepted_path, longest_accepted_prefix,
    rejection_sample_tokens, tree_ancestor_matrix, tree_depths,
    tree_rejection_sample,
)
from .adapters import (  # noqa: F401
    AdapterPool, AdapterPoolExhausted, AdapterRegistry, init_lora,
    merge_lora,
)
from .constraints import (  # noqa: F401
    ConstraintState, TokenDFA, dfa_from_regex, dfa_from_sequences,
    json_schema_dfa,
)
from .host_tier import HostPageStore, TieredKVCache  # noqa: F401
from .wal import WriteAheadLog, recover_state  # noqa: F401
from .router import (  # noqa: F401
    AdmissionController, ClusterRouter, TenantQuota,
)
from .cluster import ClusterAutoscaler, ServingCluster  # noqa: F401
from .traffic import (  # noqa: F401
    FakeClock, SLOReport, TraceRequest, run_trace, synth_trace,
)
from .rpc import (  # noqa: F401
    ReplicaUnreachable, RpcClient, RpcClosed, RpcCorruptFrame,
    RpcError, RpcRemoteError, RpcServer, RpcTimeout, RpcTornFrame,
)
from .fabric import FabricClient, FabricServer  # noqa: F401
from .node import ReplicaNode, tiny_llama_engine  # noqa: F401
from .multiproc import (  # noqa: F401
    FabricProcess, MultiProcessCluster, ReplicaProcess,
)
