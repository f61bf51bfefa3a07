"""Replica worker process for the multi-process serving cluster
(ISSUE 19).

One :class:`ReplicaNode` process = one
:class:`~paddle_tpu.serving.EngineSupervisor` (engine + scheduler +
journal) behind an :class:`~paddle_tpu.serving.rpc.RpcServer`. The RPC
surface is the cluster control plane's EXISTING replica vocabulary —
``submit_request`` / ``step`` / ``load_stats`` / the handoff
export/adopt/finish triplet / ``drain`` — so
:class:`~paddle_tpu.serving.multiproc.MultiProcessCluster` re-hosts the
in-process :class:`~paddle_tpu.serving.cluster.ServingCluster` logic
over stubs without changing any of it.

Durable process identity (ISSUE 15): each node owns a per-replica WAL
directory. ``kill -9`` the process and start a replacement with
``recover: true`` on the same directory — it rebuilds through
:meth:`EngineSupervisor.recover_from_disk` (torn tail truncated,
checkpoint + log-suffix replay) and reports the recovered session
records in its hello, so the controller re-anchors its handles and the
replay continues token-identically.

Request state crosses the wire as the journal's OWN record shape
(:meth:`JournalEntry.as_record` / :func:`_session_from_record`): the
same records that make sessions durable on disk make them portable
between processes. Token updates ship as per-request APPEND deltas
(tokens only ever grow between journal syncs), so a step reply is a
few ints per live request, not the whole transcript.

The shared KV fabric (:mod:`paddle_tpu.serving.fabric`) attaches at
ENGINE-FACTORY level: the node dials a :class:`FabricClient` and
injects it as the tiered cache's host store, so every rebuild of the
engine — including post-crash recovery — is fabric-warm: prefix
chains another replica demoted PROMOTE here instead of cold
prefilling.

Run a worker with::

    python -m paddle_tpu.serving.node --spec /path/spec.json

where the spec file holds the JSON :func:`ReplicaNode` spec (engine
factory + knobs, WAL dir, fabric endpoint, trace/metrics flags,
``port_file`` handshake path).
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from ..observability import hooks as _obs
from .fabric import FabricClient, entry_from_wire, entry_to_wire, \
    write_endpoint_file
from .resilience import EngineSupervisor, _session_from_record
from .rpc import RpcServer


# ---------------------------------------------------------------------------
# request records on the wire


def request_record(req, now: Optional[float] = None,
                   admitted: bool = False) -> Dict:
    """Controller-side record builder: the
    :meth:`~paddle_tpu.serving.resilience.JournalEntry.as_record`
    shape, produced from a bare request handle (the multi-process
    controller holds no engine, journal or clock epoch shared with the
    node — deadlines ship as REMAINING seconds for the same reason
    drain records do). ``admitted=True`` marks a rehomed in-flight
    session, which the node-side rebuild resumes with the preempted
    replay semantics."""
    remaining = None
    if req.deadline_at is not None and now is not None:
        remaining = float(req.deadline_at - now)
    eos = req.eos_token_id
    return {"rid": int(req.rid),
            "prompt": np.asarray(req.prompt).reshape(-1).tolist(),
            "max_new_tokens": int(req.max_new_tokens),
            "eos_token_id": None if eos is None else int(eos),
            "priority": int(req.priority),
            "deadline_remaining_s": remaining,
            "tokens": [int(t) for t in req.tokens],
            "admitted": bool(admitted),
            "preemptions": int(req.preemptions),
            "swapped": bool(getattr(req, "swapped", False)),
            "adapter_id": int(getattr(req, "adapter_id", 0)),
            "constraint": None}


# ---------------------------------------------------------------------------
# default engine factory


def tiny_llama_engine(num_layers: int = 2, max_seq_len: int = 64,
                      seed: int = 0, kv_cache_dtype: Optional[str] = None,
                      host_tier: Optional[bool] = None,
                      host_capacity_pages: Optional[int] = None,
                      store=None, **engine_kw):
    """Factory BUILDER for the tiny-llama engine the gates run on:
    returns the zero-arg ``engine_factory`` the supervisor calls at
    construction and after every teardown. Params derive from
    ``jax.random.key(seed)`` alone, so every process in the cluster —
    and the in-process reference cluster in the identity gate —
    materializes bit-identical weights from the spec, no weight
    shipping. ``store`` (a dialed :class:`FabricClient`) routes the
    host tier through the shared fabric."""
    import jax

    from ..inference.predictor import ContinuousBatchingEngine
    from ..models import llama

    cfg = llama.LlamaConfig.tiny(num_layers=num_layers,
                                 max_seq_len=max_seq_len)
    params = llama.init_params(jax.random.key(seed), cfg)
    engine_kw.setdefault("max_batch", 2)
    engine_kw.setdefault("page_size", 8)
    engine_kw.setdefault("max_len", 32)
    engine_kw.setdefault("prefill_chunk", 8)
    tiered = host_tier if host_tier is not None else store is not None
    hkw: Dict = {}
    if host_capacity_pages is not None:
        hkw["host_capacity_pages"] = host_capacity_pages
    if store is not None:
        hkw["store"] = store

    def make():
        return ContinuousBatchingEngine(
            params, cfg, kv_cache_dtype=kv_cache_dtype,
            host_tier=tiered, host_tier_kw=hkw or None, **engine_kw)
    return make


def _resolve_factory(spec: Dict, store):
    """``"module:attr"`` factory-builder resolution; the builder gets
    ``factory_kw`` (plus the fabric ``store`` when the node dialed
    one) and returns the supervisor's zero-arg engine factory."""
    name = spec.get("factory") or \
        "paddle_tpu.serving.node:tiny_llama_engine"
    mod, _, attr = name.partition(":")
    builder = getattr(importlib.import_module(mod), attr)
    kw = dict(spec.get("factory_kw") or {})
    if store is not None:
        kw["store"] = store
    return builder(**kw)


def wait_endpoint(path: str, timeout_s: float = 60.0,
                  process=None) -> Dict:
    """Poll for a worker's ``{"port", "pid"}`` handshake file
    (:func:`~paddle_tpu.serving.fabric.write_endpoint_file`). Raises
    if the deadline lapses or the subprocess exits first."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if process is not None and process.poll() is not None:
            raise RuntimeError(
                f"worker exited rc={process.returncode} before "
                f"publishing its endpoint ({path})")
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError):
            time.sleep(0.02)
    raise TimeoutError(f"no endpoint handshake at {path} within "
                       f"{timeout_s}s")


# ---------------------------------------------------------------------------
# the worker


class ReplicaNode:
    """One replica process: supervisor + scheduler behind RPC.

    Spec keys: ``replica_id``, ``factory`` (``"module:attr"`` builder),
    ``factory_kw``, ``supervisor_kw``, ``wal_dir`` (the durable
    process identity), ``recover`` (rebuild from the WAL dir —
    replacement-after-kill), ``fabric`` (``{"host", "port"}`` of the
    shared KV fabric), ``trace`` (enable the ISSUE 16 tracer and ship
    span batches), ``port_file`` (endpoint handshake path)."""

    def __init__(self, spec: Dict):
        self.spec = dict(spec)
        self.replica_id = int(spec.get("replica_id", 0))
        fab = spec.get("fabric")
        self.fabric: Optional[FabricClient] = None
        if fab:
            page = int((spec.get("factory_kw") or {})
                       .get("page_size", 8))
            self.fabric = FabricClient.dial(
                fab["host"], int(fab["port"]), page_size=page)
        factory = _resolve_factory(spec, self.fabric)
        skw = dict(spec.get("supervisor_kw") or {})
        wal_dir = spec.get("wal_dir")
        recover = bool(spec.get("recover")) and wal_dir \
            and os.path.isdir(wal_dir) and os.listdir(wal_dir)
        if recover:
            self.sup = EngineSupervisor.recover_from_disk(
                factory, wal_dir, **skw)
        else:
            self.sup = EngineSupervisor(factory, wal_dir=wal_dir,
                                        **skw)
        self.sup.replica_id = self.replica_id
        # live handles this node owns; cursors mark the token count /
        # span count the controller has already received
        self._reqs: Dict[int, object] = {}
        self._cursor: Dict[int, int] = {}
        self._spans: Dict[int, int] = {}
        for rid in sorted(getattr(self.sup, "restored", {})):
            self._track(self.sup.restored[rid])
        self.rpc = RpcServer(self, host=spec.get("host", "127.0.0.1"),
                             port=int(spec.get("port", 0)))

    def _track(self, req) -> None:
        self._reqs[req.rid] = req
        self._cursor[req.rid] = len(req.tokens)
        self._spans[req.rid] = 0

    def _untrack(self, rid: int) -> None:
        self._reqs.pop(rid, None)
        self._cursor.pop(rid, None)
        self._spans.pop(rid, None)

    # ---- lifecycle ------------------------------------------------

    @property
    def port(self) -> int:
        return self.rpc.port

    def serve_forever(self) -> None:
        if self.spec.get("port_file"):
            write_endpoint_file(self.spec["port_file"], self.port)
        self.rpc.serve_forever()

    def start(self) -> "ReplicaNode":
        self.rpc.start()
        return self

    def shutdown(self) -> None:
        self.rpc.shutdown()
        if self.fabric is not None:
            self.fabric.close()

    # ---- RPC surface ----------------------------------------------

    def rpc_hello(self, data, blobs):
        """Identity + recovery manifest: the records of every session
        the WAL scan requeued (the controller re-anchors its handles
        to these and lets the deterministic replay re-produce any
        group-commit-lagged tokens)."""
        now = self.sup.clock()
        recovered = [e.as_record(now, None)
                     for e in self.sup.journal.live_entries()] \
            if getattr(self.sup, "restored", None) else []
        return {"replica_id": self.replica_id, "pid": os.getpid(),
                "page_size": int(self.sup.engine.cache.page_size),
                "health": self.sup.health,
                "recovered": recovered}

    def rpc_submit_request(self, data, blobs):
        """Journaled intake of a request record — fresh dispatch and
        failover rehome alike (``admitted`` in the record selects the
        preempted-resume rebuild, exactly as recovery does)."""
        rec = data["record"]
        req = _session_from_record(self.sup, rec, None)
        if data.get("trace") is not None:
            _obs.serving_trace_submit(req, replica=self.replica_id)
        self.sup.submit_request(req)
        if not req.done:
            self._track(req)
        return {"done": bool(req.done),
                "finish_reason": req.finish_reason}

    def rpc_step(self, data, blobs):
        """One supervised scheduler step; the reply carries per-request
        token APPEND deltas past each controller cursor, final
        done/finish states, and — with tracing on — the span dicts
        recorded since the last ship (the cross-process stitch)."""
        has_work = self.sup.step()
        updates: List[Dict] = []
        spans: List[Dict] = []
        finished: List[int] = []
        for rid, req in self._reqs.items():
            cur = self._cursor[rid]
            if len(req.tokens) < cur:
                # a recovery rewound committed-but-unsynced tokens;
                # resync the controller with a full replacement
                updates.append({"rid": rid, "reset": True,
                                "tokens": [int(t) for t in req.tokens],
                                "done": bool(req.done),
                                "finish_reason": req.finish_reason})
                self._cursor[rid] = len(req.tokens)
            elif len(req.tokens) > cur or req.done:
                updates.append(
                    {"rid": rid,
                     "tokens": [int(t) for t in req.tokens[cur:]],
                     "done": bool(req.done),
                     "finish_reason": req.finish_reason})
                self._cursor[rid] = len(req.tokens)
            tr = getattr(req, "trace", None)
            if tr is not None:
                all_spans = list(tr.spans)
                seen = self._spans.get(rid, 0)
                if len(all_spans) < seen:        # ring wrapped
                    seen = 0
                for s in all_spans[seen:]:
                    d = s.to_dict()
                    d["rid"] = rid
                    spans.append(d)
                self._spans[rid] = len(all_spans)
            if req.done:
                finished.append(rid)
        for rid in finished:
            self._untrack(rid)
        return {"has_work": bool(has_work), "health": self.sup.health,
                "updates": updates, "spans": spans}

    def rpc_load_stats(self, data, blobs):
        return self.sup.load_stats()

    def rpc_handoff_ready(self, data, blobs):
        """Rids whose prefill completed and whose slot is not
        mid-chunk — the prefill side of the harvest scan."""
        rids = [int(r.rid) for r in self.sup.engine.handoff_candidates()
                if r.rid in self._reqs]
        return {"rids": rids}

    def rpc_export_prefilled(self, data, blobs):
        """Pure-read export of a running slot's live pages; the KV
        entry rides as blobs. The reply also carries the node's
        CURRENT token list — the adopt record must be built from the
        exporter's exact state, not the controller's possibly-older
        view."""
        req = self._reqs[int(data["rid"])]
        payload = self.sup.engine.export_prefilled(req, with_kv=True)
        out, oblobs = {}, None
        out["slot"] = int(payload["slot"])
        out["length"] = int(payload["length"])
        out["last"] = int(payload["last"])
        out["tokens"] = [int(t) for t in req.tokens]
        # the controller takes this list whole: tokens the export's
        # fence read just now must not come again as a step's delta
        self._cursor[req.rid] = len(req.tokens)
        kv_data, oblobs = entry_to_wire(payload["kv"])
        out["kv"] = kv_data
        return out, oblobs

    def rpc_adopt_prefilled(self, data, blobs):
        """Decode-side import + journal adoption in ONE exchange:
        rebuild a clean handle from the record, install the shipped
        pages (CRC-verified before any scatter — a corrupt payload
        raises ``CorruptionDetected`` as a typed envelope and commits
        nothing), then ``adopt_running``. ``ok=False`` means no free
        slot — the controller offers the payload elsewhere."""
        rec = dict(data["record"])
        rec["admitted"] = False     # adopt_running journals admission
        req = _session_from_record(self.sup, rec, None)
        # node-local trace so decode-side spans record here and ship
        # to the controller's stitched trace
        _obs.serving_trace_submit(req, replica=self.replica_id)
        payload = {"rid": int(rec["rid"]), "slot": int(data["slot"]),
                   "length": int(data["length"]),
                   "last": int(data["last"]),
                   "kv": entry_from_wire(data["kv"], blobs)}
        if not self.sup.engine.import_prefilled(req, payload):
            return {"ok": False}
        self.sup.adopt_running(req)
        self._track(req)
        return {"ok": True, "slot": int(req.slot)}

    def rpc_finish_handoff(self, data, blobs):
        """Prefill-side detach after a successful adopt elsewhere:
        durable journal tombstone first, then slot-clear +
        page-release (the same clear-before-release ordering the
        in-process handoff relies on)."""
        rid = int(data["rid"])
        req = self._reqs.get(rid)
        if req is None:
            return {"ok": False}
        self.sup.journal.forget(rid)
        self.sup.engine.finish_handoff(req, int(data["slot"]))
        self._untrack(rid)
        return {"ok": True}

    def rpc_forget(self, data, blobs):
        """Durably drop a session this node must NOT serve (the
        controller's post-recovery dedupe: the handle already finished
        elsewhere, or a rehomed copy supersedes this one)."""
        rid = int(data["rid"])
        req = self._reqs.get(rid)
        self.sup.journal.forget(rid)
        if req is not None:
            try:
                self.sup.engine.cancel_request(req, "superseded")
            except Exception:  # noqa: BLE001 - already gone is fine
                pass
            self._untrack(rid)
        return {"ok": True}

    def rpc_drain(self, data, blobs):
        """Retirement: checkpoint to ``path`` and hand back the live
        session records for the controller to rehome. Drain FIRST —
        it commits the decode pipeline's steps in flight and syncs the
        journal, so the records carry every token the device already
        produced."""
        summary = self.sup.drain(data["path"])
        now = self.sup.clock()
        summary["records"] = [e.as_record(now, None)
                              for e in self.sup.journal.live_entries()]
        return summary

    def rpc_tier_stats(self, data, blobs):
        cache = self.sup.engine.cache
        out = {"tier": cache.tier_stats()
               if hasattr(cache, "tier_stats") else {}}
        alloc = cache.allocator
        if data.get("drop_prefix") and cache.prefix is not None:
            # the balanced-allocator gate (chaos soak): standing
            # prefix-trie pages are intentionally resident — release
            # them so num_used == 0 is assertable after a drain
            cache.prefix.drop_all(cache._trie_alloc)
        out["allocator"] = alloc.stats()
        if self.fabric is not None:
            out["fabric_client"] = {
                "puts_total": self.fabric.puts_total,
                "hits_total": self.fabric.hits_total,
                "misses_total": self.fabric.misses_total,
                "quarantined_total": self.fabric.quarantined_total,
                "unreachable_total": self.fabric.unreachable_total}
        return out

    def rpc_ping(self, data, blobs):
        return {"ok": True, "pid": os.getpid(),
                "health": self.sup.health}

    def rpc_shutdown(self, data, blobs):
        import threading
        threading.Timer(0.05, self.shutdown).start()
        return {"ok": True}


# ---------------------------------------------------------------------------
# worker-process entry


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="paddle_tpu serving replica worker")
    p.add_argument("--spec", required=True,
                   help="path to the JSON ReplicaNode spec")
    args = p.parse_args(argv)
    with open(args.spec) as f:
        spec = json.load(f)
    # workers compile the same programs as each other and as their
    # predecessors on the same WAL directory: share the persistent cache
    from .._core.compile_cache import enable_compile_cache
    enable_compile_cache()
    if spec.get("trace"):
        from ..observability import tracing
        tracing.enable()
    if spec.get("metrics"):
        from .. import observability as obs
        obs.enable()
    node = ReplicaNode(spec)
    node.serve_forever()
    return 0


if __name__ == "__main__":  # pragma: no cover - subprocess entry
    sys.exit(main())
