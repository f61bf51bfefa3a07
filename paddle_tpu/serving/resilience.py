"""Fault-tolerant serving: fault injection, supervised recovery, and
drain/restore over the continuous-batching engine (ISSUE 8).

The PR 2–7 serving stack assumes every device step succeeds: one raised
exception, stalled transfer, or poisoned compile kills the engine and
every in-flight session with it. This module closes that gap with three
pieces, all HOST-side (no new device programs):

- :class:`FaultInjector` — a deterministic, seeded injector with NAMED
  sites threaded through the hot path (:data:`SITES`: allocator
  alloc/free, decode / prefill-chunk / verify step execution,
  device→host transfer, scheduler tick). Each firing can ``raise``,
  ``stall`` past a watchdog deadline, or model a detected-corruption
  (``corrupt``: the payload never commits — the checksum caught it).
  Hot paths call :func:`fault_point`; when no injector is installed the
  cost is one module-attribute read.

- :class:`EngineSupervisor` — wraps a fresh
  :class:`~paddle_tpu.inference.ContinuousBatchingEngine` (built by an
  ``engine_factory`` so it can be rebuilt from scratch) behind a
  :class:`~paddle_tpu.serving.ServingScheduler`, keeping a host-side
  write-ahead :class:`RequestJournal`: admission params are journaled at
  submit time (before anything executes) and every committed token after
  each successful step. On a failed — or watchdog-stalled — step the
  supervisor tears the poisoned engine down, rebuilds pools from
  scratch, and restores every in-flight session through the PR 4
  ``resume_sequence`` replay path, so recovery is TOKEN-IDENTICAL to an
  uninterrupted run at fp and int8-KV, including under tp sharding
  (gated in tests/test_resilience.py). Between "healthy" and "dead" sit
  bounded exponential-backoff retries, a circuit breaker on repeated
  failures, and a pressure-ordered DEGRADED-MODE ladder
  (:data:`DEGRADED_MODES`: disable spec decode → shrink the prefill
  chunk → shed LOW-priority admissions with a structured
  ``rejected_overload`` finish reason), published to the PR 1 metrics
  registry as the ``serving_degraded_mode`` gauge (the future router's
  replica-health signal).

- **drain/restore** — :meth:`EngineSupervisor.drain` stops admissions
  and checkpoints every in-flight session (journal records) PLUS the
  prefix-cache trie — structure AND page KV bytes
  (:meth:`~paddle_tpu.serving.PagedKVCache.checkpoint_prefix`) — to one
  ``.npz`` file; :meth:`EngineSupervisor.restore` rebuilds a fresh
  engine, writes the trie pages back into the new pool, and requeues
  the sessions — so shared system prompts survive restarts as prefix
  HITS (ROADMAP item 4's persistence ask) and interrupted decodes
  finish token-identically.

Recovery cost model: the journal replays ``prompt + tokens[:-1]``
through the continuation-prefill program — exactly the PR 4 resume
cost — so recovery time is proportional to RESIDENT tokens, not to the
wall-clock already served (not measured on a chip).

Determinism note: greedy decode (``temperature == 0``) is bit-identical
across recovery by construction (replay never re-samples). For sampled
decode the supervisor snapshots the engine's PRNG key at each step
commit, so the stream also survives recovery at STEP granularity; a
fault after an intra-step key split replays with the committed
snapshot (the failed attempt's split is discarded with the engine).

Stall caveat: a watchdog-stalled step's thread is abandoned with the
poisoned engine (its slot table is cleared as a best-effort fence). An
injected ``stall`` always raises when it wakes — it never commits. A
REAL stalled device program that later completes could still race a
token append; the journal is authoritative (recovery resets every
request to its journaled tokens), which bounds the damage to a
transiently wrong ``req.tokens`` tail on an already-poisoned handle.
"""
from __future__ import annotations

import json
import os
import tempfile
import threading
import time
import zlib
from typing import Callable, Dict, List, Optional

import numpy as np

from ..observability import hooks as _obs
from .policy import FinishReason, Priority

#: the named injection sites threaded through the serving hot path —
#: tools/check_instrumentation.py enforces that every name here has a
#: matching ``fault_point("<site>")`` call site (and therefore a
#: matching ``site=`` label on the serving_fault_* counters)
#: "dispatch" fires AFTER a decode/verify program launches (the
#: in-flight handle is lost with the fault — nothing committed, the
#: journal replays); "commit" fires at the top of the commit half,
#: before the device→host fetch — the two seams the decode pipeline
#: opens between a launch and the commit of what hangs on its tokens
ENGINE_SITES = ("alloc", "free", "decode_step", "prefill_chunk",
                "verify_step", "transfer", "sched_tick", "swap_out",
                "swap_in", "dispatch", "commit",
                # adapter plane, ISSUE 14 — both fire BEFORE anything
                # installs: a fresh registry load / a host-store
                # promotion that faults commits nothing, and the
                # retried admission finds the same sources intact.
                # NB keep this comment paren-free: check_fault_sites
                # parses the tuple with a non-greedy paren match
                "adapter_load", "adapter_promote",
                # durable journal plane, ISSUE 15: wal_append fires
                # BEFORE a frame is written, wal_fsync before the
                # fsync, checkpoint_write before the checkpoint file —
                # none commits anything, and the crash-point sweep
                # kills the process after each and recovers from disk
                "wal_append", "wal_fsync", "checkpoint_write",
                # draft-model + tree speculation, ISSUE 20 — both fire
                # BEFORE any commit: draft_propose before the draft
                # model's catch-up/propose forwards touch its pool,
                # tree_verify before the one-forward tree verify
                # launches. Draft-pool state is disposable, so a fault
                # at either recovers by rebuilding it cold.
                # NB keep this comment paren-free: check_fault_sites
                # parses the tuple with a non-greedy paren match
                "draft_propose", "tree_verify")

#: cluster-plane sites (ISSUE 13): the prefill→decode handoff's two
#: byte-moving halves and the autoscaler's control tick. They only
#: execute inside a :class:`~paddle_tpu.serving.cluster.ServingCluster`
#: — the single-engine chaos soak covers :data:`ENGINE_SITES`, the
#: traffic soak (tools/chaos_soak.py --traffic) covers these
CLUSTER_SITES = ("handoff_export", "handoff_import", "autoscale_tick",
                 # multi-process plane, ISSUE 19 — all four fire BEFORE
                 # any commit: rpc_send before a frame hits the socket,
                 # rpc_recv before a reply is decoded, fabric_put before
                 # a payload ships to the fabric server, fabric_get
                 # before a fetched payload is verified or installed.
                 # NB keep this comment paren-free: check_fault_sites
                 # parses the tuple with a non-greedy paren match
                 "rpc_send", "rpc_recv", "fabric_put", "fabric_get")

SITES = ENGINE_SITES + CLUSTER_SITES

#: the pressure-ordered degraded-mode ladder (index == level): each
#: recovery escalates one rung, sustained healthy steps climb back down
DEGRADED_MODES = ("healthy", "no_spec", "small_chunks", "shed_low")


def _draft_identity(engine):
    """The journaled DRAFT-MODEL identity (ISSUE 20): draft-pool
    STATE is disposable — never checkpointed, never journaled — so
    recovery only needs ``[draft_layers]`` (linear draft) or
    ``[draft_layers, tree_width, tree_depth]`` (tree speculation) to
    prove the replacement engine re-drafts token-identically; the
    rebuilt pool then refills cold through the catch-up forward.
    ``None`` for engines without a draft model."""
    dl = getattr(engine, "draft_layers", None)
    if dl is None:
        return None
    tree = getattr(engine, "spec_tree", None)
    return [int(dl)] + ([int(tree[0]), int(tree[1])] if tree else [])


class InjectedFault(RuntimeError):
    """A fault fired by the :class:`FaultInjector` (``site`` / ``mode``
    carry the classification through to the supervisor's counters)."""

    def __init__(self, site: str, mode: str = "raise", detail: str = ""):
        self.site = site
        self.mode = mode
        super().__init__(
            f"injected {mode} fault at site {site!r}"
            + (f": {detail}" if detail else ""))


class CorruptionDetected(InjectedFault):
    """A byte payload failed its checksum verification BEFORE install
    (ISSUE 13: every exported payload — handoff export/import, host-tier
    swap, standing-store ``.npz`` — carries per-array CRCs that are
    verified before any scatter). The corrupted bytes are NEVER
    committed to host or device state, so the caller either quarantines
    the entry and falls back to the gated replay path (swap/prefix
    payloads) or keeps the request on its exporting replica (handoff).
    Also raised by the injector's corrupt-and-detect mode, which models
    the same detection without real bytes."""

    def __init__(self, site: str, detail: str = ""):
        super().__init__(site, "corrupt",
                         detail or "checksum mismatch on fetched "
                         "payload; data discarded before commit")


class StepStalled(RuntimeError):
    """The supervisor's watchdog gave up on a step that exceeded its
    deadline (a hung transfer / wedged device program)."""

    def __init__(self, seconds: float):
        self.site = "watchdog"
        self.mode = "stall"
        super().__init__(f"engine step exceeded the {seconds:.3f}s "
                         f"watchdog deadline")


class EngineDead(RuntimeError):
    """The circuit breaker opened: repeated step failures exhausted the
    recovery budget and the supervisor will not retry further."""


#: the installed injector — hot paths read this ONE module attribute;
#: None (the default) costs nothing beyond the read
_ACTIVE: Optional["FaultInjector"] = None


def fault_point(site: str) -> None:
    """Hot-path injection site: no-op unless a :class:`FaultInjector`
    is installed (:func:`install` / ``with injector:``)."""
    inj = _ACTIVE
    if inj is not None:
        inj.fire(site)


def tamper_point(site: str) -> bool:
    """Payload-corruption injection site (ISSUE 13): True when the
    installed injector has an armed TAMPER shot due at ``site`` — the
    caller then flips real bytes in the payload it is about to verify,
    so the CHECKSUM path (not the injector) raises
    :class:`CorruptionDetected`. Unlike :func:`fault_point` this never
    raises: the whole point is that detection happens downstream, in
    the verifier the tamper exists to exercise."""
    inj = _ACTIVE
    return inj is not None and inj.tamper(site)


def run_with_deadline(fn: Callable, seconds: Optional[float]):
    """Run ``fn()`` under a watchdog deadline (the
    :meth:`EngineSupervisor._guarded` pattern, reusable for the
    cluster's handoff imports — ISSUE 13): raises :class:`StepStalled`
    past ``seconds``; ``None`` runs inline. The abandoned thread is
    daemonic — same contract (and same caveat) as the supervisor's
    step watchdog."""
    if seconds is None:
        return fn()
    box: Dict = {}

    def run():
        try:
            box["r"] = fn()
        except BaseException as e:  # noqa: BLE001 — relayed below
            box["e"] = e

    t = threading.Thread(target=run, daemon=True,
                         name="deadline-guarded-call")
    t.start()
    t.join(seconds)
    if t.is_alive():
        raise StepStalled(seconds)
    if "e" in box:
        raise box["e"]
    return box.get("r")


def install(injector: Optional["FaultInjector"]) -> None:
    """Install ``injector`` globally (``None`` uninstalls)."""
    global _ACTIVE
    _ACTIVE = injector


def uninstall() -> None:
    install(None)


class FaultInjector:
    """Deterministic, seeded fault source for the named serving sites.

    Two firing styles compose:

    - **armed** (on demand): :meth:`arm` schedules a fault on the n-th
      FUTURE call at a site — the unit tests' way of killing the engine
      at an exact point (e.g. mid-decode, during a spec-verify step).
    - **rate** (chaos): every :func:`fault_point` call at an enabled
      site draws from a seeded RNG; at most ``max_faults`` total fire.
      Same seed + same call sequence => same faults, every run.

    ``modes`` picks what a rate-fired fault does: ``"raise"`` (raise
    :class:`InjectedFault`), ``"stall"`` (sleep ``stall_s`` — past the
    supervisor's watchdog deadline — then raise, so a stalled site never
    commits), ``"corrupt"`` (raise :class:`CorruptionDetected`,
    modeling a checksum catching a corrupted transfer before commit).

    Every firing is counted per site (``fired``), logged
    (``log``: ``(site, mode, call_index)``) and emitted to the
    ``serving_fault_injected_total{site,mode}`` counter.
    """

    def __init__(self, seed: int = 0, rate: float = 0.0,
                 sites: Optional[List[str]] = None,
                 modes=("raise",), stall_s: float = 0.1,
                 max_faults: Optional[int] = None):
        bad = set(sites or ()) - set(SITES)
        if bad:
            raise ValueError(
                f"FaultInjector: unknown site(s) {sorted(bad)}; "
                f"valid sites: {SITES}")
        bad = set(modes) - {"raise", "stall", "corrupt"}
        if bad:
            raise ValueError(f"FaultInjector: unknown mode(s) "
                             f"{sorted(bad)}")
        self.rate = float(rate)
        self.sites = tuple(sites) if sites is not None else SITES
        self.modes = tuple(modes)
        self.stall_s = float(stall_s)
        self.max_faults = max_faults
        self._rng = np.random.RandomState(seed)
        self.calls: Dict[str, int] = {s: 0 for s in SITES}
        self.fired: Dict[str, int] = {s: 0 for s in SITES}
        self.fired_total = 0
        self.log: List[tuple] = []
        self._armed: Dict[str, List[tuple]] = {}
        # payload-corruption shots (ISSUE 13): consumed by
        # tamper_point(), never by fire() — a tamper must flow through
        # the caller's checksum verifier, not raise here
        self._tamper_armed: Dict[str, List[int]] = {}
        self.tamper_calls: Dict[str, int] = {s: 0 for s in SITES}
        # stalls in flight, not yet attributed by a supervisor: the
        # watchdog only ever sees a StepStalled, so the supervisor asks
        # the installed injector whether the stall was its own (keeps
        # the injected-vs-real counter split exact under chaos)
        self.pending_stalls: List[str] = []

    def arm(self, site: str, mode: str = "raise", nth: int = 1) -> None:
        """Schedule one fault on the ``nth`` future call at ``site``
        (1 = the very next call). Armed faults fire regardless of
        ``rate``/``max_faults`` — they are the on-demand kill switch."""
        if site not in SITES:
            raise ValueError(f"arm: unknown site {site!r}")
        self._armed.setdefault(site, []).append(
            (self.calls[site] + int(nth), mode))

    def arm_tamper(self, site: str, nth: int = 1) -> None:
        """Schedule one PAYLOAD CORRUPTION on the ``nth`` future
        :func:`tamper_point` visit at ``site`` (ISSUE 13): the hot path
        then flips real bytes in the payload it is about to verify, so
        the checksum — not the injector — detects the corruption. The
        end-to-end detect→quarantine→replay path is what gets
        exercised, which a raised :class:`CorruptionDetected` (the
        ``corrupt`` mode) cannot do."""
        if site not in SITES:
            raise ValueError(f"arm_tamper: unknown site {site!r}")
        self._tamper_armed.setdefault(site, []).append(
            self.tamper_calls[site] + int(nth))

    def tamper(self, site: str) -> bool:
        """One :func:`tamper_point` visit: True when an armed tamper
        shot is due — counted, logged and metered like any firing
        (mode ``"tamper"``), but the caller corrupts its own payload
        instead of this method raising."""
        self.tamper_calls[site] = n = self.tamper_calls[site] + 1
        armed = self._tamper_armed.get(site)
        if not armed:
            return False
        for i, target in enumerate(armed):
            if n >= target:
                del armed[i]
                self.fired[site] += 1
                self.fired_total += 1
                self.log.append((site, "tamper", n))
                _obs.serving_fault(site, "tamper", injected=True)
                return True
        return False

    def fire(self, site: str) -> None:
        """One hot-path visit to ``site``: decide (armed schedule, then
        seeded rate) and inject. Raises on injection; returns silently
        otherwise."""
        self.calls[site] = n = self.calls[site] + 1
        mode = None
        armed = self._armed.get(site)
        if armed:
            for i, (target, m) in enumerate(armed):
                if n >= target:
                    mode = m
                    del armed[i]
                    break
        if (mode is None and self.rate > 0.0 and site in self.sites
                and (self.max_faults is None
                     or self.fired_total < self.max_faults)
                and self._rng.random_sample() < self.rate):
            mode = self.modes[self._rng.randint(len(self.modes))]
        if mode is None:
            return
        self.fired[site] += 1
        self.fired_total += 1
        self.log.append((site, mode, n))
        _obs.serving_fault(site, mode, injected=True)
        if mode == "stall":
            # sleep past the supervisor's watchdog, then raise — the
            # stalled site never commits, so the abandoned step thread
            # cannot race the recovery that replaced it. Registered
            # BEFORE the sleep: the watchdog fires mid-sleep and the
            # supervisor attributes the StepStalled to this injection
            self.pending_stalls.append(site)
            time.sleep(self.stall_s)
            raise InjectedFault(site, "stall",
                                f"stalled {self.stall_s}s past deadline")
        if mode == "corrupt":
            raise CorruptionDetected(site)
        raise InjectedFault(site)

    def stats(self) -> Dict:
        return {"fired_total": self.fired_total,
                "fired": {s: n for s, n in self.fired.items() if n},
                "calls": {s: n for s, n in self.calls.items() if n}}

    # installable as a context manager: ``with injector: ...``
    def __enter__(self) -> "FaultInjector":
        install(self)
        return self

    def __exit__(self, *exc) -> None:
        uninstall()


class JournalEntry:
    """One request's journaled state (the supervisor's recovery unit).

    ``swapped`` (ISSUE 10) records whether the request's KV currently
    lives in the HOST tier (a swap-out payload exists for its rid) —
    host-resident state survives an engine teardown, so recovery SWAPS
    such sessions back in instead of charging them the replay prefill."""
    __slots__ = ("req", "rid", "prompt", "max_new_tokens",
                 "eos_token_id", "priority", "deadline_at",
                 "submitted_at", "tokens", "admitted", "preemptions",
                 "swapped", "adapter_id", "constrained",
                 "wal_submitted", "wal_tokens", "wal_prem",
                 "wal_swapped", "wal_admitted")

    def __init__(self, req):
        self.req = req
        self.rid = req.rid
        self.prompt = req.prompt[0].copy()
        self.max_new_tokens = req.max_new_tokens
        self.eos_token_id = req.eos_token_id
        self.priority = int(req.priority)
        self.deadline_at = req.deadline_at
        self.submitted_at = req.submitted_at
        self.tokens: List[int] = list(req.tokens)
        self.admitted = False
        self.preemptions = int(req.preemptions)
        self.swapped = False
        # the LoRA variant serving this request (ISSUE 14): journaled
        # so recovery/restore re-admissions re-pin the same adapter
        # (the handle carries it in-process; the drain record needs it
        # explicitly). Grammar-constraint STATE rides the live handle
        # only — a drain checkpoint does not serialize host DFA
        # objects, so constrained requests must finish before a drain
        # (drain() refuses while any are live; the flag is how it
        # knows).
        self.adapter_id = int(getattr(req, "adapter_id", 0))
        self.constrained = getattr(req, "constraint", None) is not None
        # durable-WAL cursors (ISSUE 15): what of this entry already
        # reached the on-disk log — sync() appends only the deltas, and
        # a failed append just leaves the cursor behind for the next
        # successful sync to heal
        self.wal_submitted = False
        self.wal_tokens = 0
        self.wal_prem = self.preemptions
        self.wal_swapped = False
        self.wal_admitted = False

    def as_record(self, now: Optional[float] = None,
                  grammars: Optional[Dict] = None) -> Dict:
        """JSON-able checkpoint record (drain/restore). Deadlines are
        serialized as REMAINING seconds against ``now`` (the draining
        supervisor's clock), never as absolute monotonic stamps — a
        monotonic value from the draining host is meaningless on the
        restoring one (different boot epoch), and would either freeze
        the SLO for days or expire still-valid requests instantly.
        Restore re-anchors against its own clock."""
        remaining = None
        if self.deadline_at is not None and now is not None:
            remaining = self.deadline_at - now
        constraint = None
        cs = getattr(self.req, "constraint", None) \
            if self.req is not None else None
        if cs is not None:
            # grammar state serializes (ISSUE 15 satellite): dense DFA
            # table + state id + violation counters — a mid-grammar
            # session survives drain/restore and cold restarts, so the
            # old drain() refusal is gone. ``grammars`` dedupes the
            # table across sessions sharing one grammar (MBs at real
            # vocab sizes — it must never re-encode per record)
            constraint = cs.to_record(grammars)
        return {"rid": self.rid, "prompt": self.prompt.tolist(),
                "max_new_tokens": self.max_new_tokens,
                "eos_token_id": self.eos_token_id,
                "priority": self.priority,
                "deadline_remaining_s": remaining,
                "tokens": list(self.tokens),
                "admitted": self.admitted,
                "preemptions": self.preemptions,
                "swapped": self.swapped,
                "adapter_id": self.adapter_id,
                "constraint": constraint}


class RequestJournal:
    """Host-side write-ahead journal of every live request.

    Admission params are recorded at SUBMIT time — before any device
    work — and committed tokens are copied in at each successful step
    (:meth:`sync`). The journal, not the engine, is the source of truth
    at recovery: a poisoned engine is discarded wholesale and every
    live request is reset to its journaled state, which is exactly the
    host state as of the last committed step (a failed step committed
    nothing — device results only reach ``req.tokens`` after the
    transfer that would have raised).

    ``wal`` (ISSUE 15) attaches a
    :class:`~paddle_tpu.serving.wal.WriteAheadLog`: admission params
    append at submit time (write-ahead — on disk before anything can
    execute), per-step committed-token deltas / preempt-swap ownership
    transitions / constraint-state deltas append at each :meth:`sync`,
    and finish / handoff-forget tombstones retire sessions from the
    log. The in-memory journal stays the in-process recovery source;
    the WAL is what a COLD restart replays
    (:meth:`EngineSupervisor.recover_from_disk`)."""

    def __init__(self, wal=None):
        self._entries: Dict[int, JournalEntry] = {}
        self.finished_total = 0
        self.wal = wal
        # finish tombstones awaiting the next due delta pass (the
        # group-commit cadence batches step deltas; a finished entry
        # leaves _entries immediately, so its tombstone must queue)
        self._pending_fin: List[tuple] = []
        # grammar tables already durably appended (hash set): many
        # sessions share one grammar, and the dense table is MBs at
        # serving vocab sizes — it goes to disk ONCE per hash, and
        # per-session records carry only the hash. Cleared at every
        # checkpoint (which carries its own grammar dict), so a
        # post-checkpoint submit re-appends tables the pruning may
        # have compacted away.
        self._wal_grammars: set = set()

    def _wal_submit(self, e: JournalEntry,
                    now: Optional[float] = None) -> None:
        grammars: Dict[str, Dict] = {}
        rec = e.as_record(now, grammars=grammars)
        rec["admitted"] = e.admitted
        for h, dfa_rec in grammars.items():
            if h not in self._wal_grammars:
                self.wal.append("grammar", {"hash": h, "dfa": dfa_rec})
        # flush=True: the write-ahead ACK — an accepted submission is
        # OS-durable before the caller gets its handle back
        self.wal.append("submit", rec, flush=True)
        # mark only after BOTH appends landed: a submit that failed
        # after its grammar record leaves the hash unmarked, and the
        # retry harmlessly re-appends it (last-wins at replay)
        self._wal_grammars.update(grammars)
        e.wal_submitted = True
        e.wal_tokens = len(e.tokens)
        e.wal_prem = e.preemptions
        e.wal_swapped = e.swapped
        e.wal_admitted = e.admitted

    def record_submit(self, req, now: Optional[float] = None
                      ) -> JournalEntry:
        e = JournalEntry(req)
        if self.wal is not None:
            # WRITE-AHEAD: the admission is on disk before the entry is
            # even registered — a failed append leaves no half-accepted
            # request (the caller sees the error before any execution)
            self._wal_submit(e, now)
        self._entries[req.rid] = e
        return e

    def adopt(self, req, rec: Dict, durable: bool = False,
              now: Optional[float] = None) -> JournalEntry:
        """Re-journal a request rebuilt from a drain checkpoint or a
        cold-restart recovery. ``durable=True`` (the recovery path)
        marks the entry as already on THIS journal's disk — its WAL
        records are the very ones recovery just replayed, so only
        future deltas append. ``now`` (the adopting supervisor's
        clock) keeps a re-anchored deadline durable: without it the
        fresh submit record would serialize the deadline as null and a
        later cold restart would silently stop enforcing the SLO."""
        e = JournalEntry(req)
        e.admitted = bool(rec.get("admitted"))
        if self.wal is not None:
            if durable:
                e.wal_submitted = True
                e.wal_tokens = len(e.tokens)
                e.wal_prem = e.preemptions
                e.wal_swapped = e.swapped
                e.wal_admitted = e.admitted
            else:
                self._wal_submit(e, now)
        self._entries[req.rid] = e
        return e

    def forget(self, rid: int) -> None:
        """Drop a live entry WITHOUT counting it finished — the
        handoff path: a request exported to another replica is that
        replica's journal's to recover now, and recovering it here too
        would decode it twice. With a WAL attached the tombstone is
        durable too, so a cold restart of THIS directory can never
        resurrect the handed-off session."""
        e = self._entries.pop(rid, None)
        if e is not None and self.wal is not None and e.wal_submitted:
            try:
                self.wal.append("forget", {"rid": rid})
            except Exception:
                pass    # in-memory ownership moved; best-effort stone

    def sync(self, swapped_check=None, wal: bool = True,
             force: bool = False) -> None:
        """Copy committed host state from the live request handles;
        finished requests leave the journal (their results live on the
        caller's handle — nothing to recover). ``swapped_check(rid) ->
        bool`` — when the engine runs a host tier — marks entries
        whose KV is host-resident (they recover by swap-in, not
        replay). The in-memory pass always completes FIRST; the WAL
        delta pass (``wal=True``) runs after it on the log's
        group-commit cadence (``force`` runs it regardless — the
        drain/checkpoint path), so an append fault can never leave the
        in-process recovery source stale."""
        finished: List[tuple] = []
        for rid in list(self._entries):
            e = self._entries[rid]
            req = e.req
            if len(e.tokens) != len(req.tokens):
                e.tokens = list(req.tokens)
            e.preemptions = int(req.preemptions)
            if (req.slot is not None or req.tokens
                    or req.preemptions > 0):
                e.admitted = True
            if swapped_check is not None:
                e.swapped = bool(swapped_check(rid))
            if req.done:
                self.finished_total += 1
                finished.append((e, req.finish_reason))
                del self._entries[rid]
        if self.wal is None:
            return
        # finished entries leave _entries NOW but their durable
        # tombstones must queue UNCONDITIONALLY — including on the
        # recovery path's wal=False sync, or a finished session's
        # submit record would stand tombstone-less forever and a later
        # cold restart would resurrect completed work
        for e, reason in finished:
            if e.wal_submitted:
                self._pending_fin.append((e.rid, reason))
        if not wal or not (force or self.wal.delta_due()):
            return
        self.wal.mark_delta()
        deltas: List[Dict] = []
        synced: List[JournalEntry] = []
        for e in list(self._entries.values()) \
                + [f[0] for f in finished]:
            if not e.wal_submitted:
                # a submit-time append failed earlier: heal with the
                # full record (write-ahead degraded to one-step lag)
                self._wal_submit(e)
                continue
            delta = {}
            if len(e.tokens) > e.wal_tokens:
                delta["toks"] = [int(t) for t in
                                 e.tokens[e.wal_tokens:]]
            if e.preemptions != e.wal_prem:
                delta["preemptions"] = e.preemptions
            if e.swapped != e.wal_swapped:
                delta["swapped"] = e.swapped
            if e.admitted != e.wal_admitted:
                delta["admitted"] = e.admitted
            if not delta:
                continue
            cs = getattr(e.req, "constraint", None)
            if cs is not None:
                delta["cstate"] = cs.state_record()
            delta["rid"] = e.rid
            deltas.append(delta)
            synced.append(e)
        fins, self._pending_fin = self._pending_fin, []
        deltas += [{"rid": rid, "fin": reason} for rid, reason in fins]
        if deltas:
            # ONE batched frame per sync: the per-record framing/flush
            # cost is what the durability rider measures per step, so a
            # B-slot commit must not pay it B times (the group-commit
            # amortization argument, applied to the frame too)
            try:
                if len(deltas) == 1 and "fin" not in deltas[0]:
                    self.wal.append("step", deltas[0])
                else:
                    self.wal.append("steps", {"entries": deltas})
            except BaseException:
                # the append committed nothing (frame-boundary
                # rollback): live deltas re-derive from the cursors on
                # the next sync, but the tombstones would be GONE —
                # re-queue them before surfacing the fault
                self._pending_fin = fins + self._pending_fin
                raise
            for e in synced:
                e.wal_tokens = len(e.tokens)
                e.wal_prem = e.preemptions
                e.wal_swapped = e.swapped
                e.wal_admitted = e.admitted

    def live_entries(self) -> List[JournalEntry]:
        return [self._entries[r] for r in sorted(self._entries)]

    @property
    def size(self) -> int:
        return len(self._entries)

    @property
    def token_count(self) -> int:
        return sum(e.prompt.size + len(e.tokens)
                   for e in self._entries.values())


def payload_checksums(arrays: Dict[str, np.ndarray]) -> Dict[str, int]:
    """Per-array CRC32s of a byte payload (ISSUE 13): computed at
    export/put time by every path that materializes KV bytes (handoff
    export, host-tier swap/demote, standing-store writes) and verified
    by :func:`verify_checksums` before any install — a corrupt or torn
    payload becomes a :class:`CorruptionDetected` at the door, never a
    silently-wrong KV page."""
    return {n: zlib.crc32(np.ascontiguousarray(a).tobytes()) & 0xFFFFFFFF
            for n, a in arrays.items()}


def verify_checksums(arrays: Dict[str, np.ndarray],
                     checksums: Optional[Dict[str, int]],
                     site: str) -> None:
    """Verify ``arrays`` against :func:`payload_checksums` output;
    raises :class:`CorruptionDetected` (tagged ``site``) on any
    mismatch or missing array entry. A payload with no checksum dict
    (pre-ISSUE-13 producer) passes — verification is the consumer's
    defense, not a format break."""
    if not checksums:
        return
    lost = set(checksums) - set(arrays)
    if lost:
        # the inverse hole: a checksummed array VANISHED from the
        # payload (partial rewrite / truncation that dropped a whole
        # member) — that is corruption, not a geometry mismatch
        raise CorruptionDetected(
            site, f"payload lost checksummed array(s) {sorted(lost)} "
            f"— truncated payload")
    for name, a in arrays.items():
        want = checksums.get(name)
        if want is None:
            raise CorruptionDetected(
                site, f"payload array {name!r} has no checksum — "
                f"truncated or foreign payload")
        got = zlib.crc32(np.ascontiguousarray(a).tobytes()) & 0xFFFFFFFF
        if got != int(want):
            raise CorruptionDetected(
                site, f"payload array {name!r} checksum mismatch "
                f"(expected {int(want)}, got {got})")


def _np_dtype(name: str) -> np.dtype:
    """Resolve a checkpointed dtype name, including the ml_dtypes
    extension types (bfloat16 & friends) numpy can't look up by
    string."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def load_drain_checkpoint(path: str) -> Dict:
    """Decode a :meth:`EngineSupervisor.drain` ``.npz`` back into host
    data: ``meta`` (sessions, geometry, next_rid), ``key_data`` (PRNG
    snapshot, empty when none) and — when a prefix trie was
    checkpointed — ``prefix`` in the exact dict shape
    :meth:`~paddle_tpu.serving.PagedKVCache.restore_prefix` consumes.
    Shared by :meth:`EngineSupervisor.restore` (whole-supervisor
    restore) and the cluster's rolling upgrade
    (:meth:`~paddle_tpu.serving.cluster.ServingCluster.retire_replica`
    restores ONLY the trie into the replacement replica — the sessions
    were requeued live onto other replicas)."""
    with np.load(path) as data:
        meta = json.loads(bytes(data["meta"]).decode())
        key_data = np.asarray(data["key_data"])
        prefix = None
        if meta["prefix"] is not None:
            pf = meta["prefix"]
            arrays = {
                n: np.frombuffer(
                    bytes(data[f"prefix_{n}"]),
                    _np_dtype(pf["dtypes"][n])).reshape(pf["shapes"][n])
                for n in pf["shapes"]}
            prefix = {"page_ids": pf["page_ids"],
                      "records": pf["records"], "arrays": arrays}
    return {"meta": meta, "key_data": key_data, "prefix": prefix}


def _session_from_record(sup: "EngineSupervisor", rec: Dict,
                         grammars: Optional[Dict] = None):
    """Rebuild one live request handle from a checkpoint/WAL session
    record (shared by :meth:`EngineSupervisor.restore` and
    :meth:`EngineSupervisor.recover_from_disk`): admission params,
    committed tokens, re-anchored deadline, adapter pin, swapped flag
    and — when the session was grammar-constrained — an equivalent
    :class:`~paddle_tpu.serving.constraints.ConstraintState` attached
    through the engine's validated surface."""
    from ..inference.predictor import GenerationRequest
    req = GenerationRequest(
        rec["rid"], np.asarray(rec["prompt"], np.int32),
        rec["max_new_tokens"], rec.get("eos_token_id"))
    req.priority = rec.get("priority", 1)
    req.adapter_id = int(rec.get("adapter_id", 0))
    if rec.get("deadline_remaining_s") is not None:
        # re-anchor the SLO on THIS process's clock (records store
        # remaining seconds, never monotonic stamps from the dead host)
        req.deadline_at = sup.clock() + rec["deadline_remaining_s"]
    req.tokens = list(rec.get("tokens") or ())
    # a swapped-out session's host payload may have died with the
    # process (host RAM) or survived (shared/standing store): the
    # admit-time swap-in probes and falls back to the gated replay
    # resume either way, so the flag is safe to carry verbatim
    req.swapped = bool(rec.get("swapped"))
    if rec.get("admitted"):
        req.preemptions = int(rec.get("preemptions", 0)) + 1
        req.finish_reason = FinishReason.PREEMPTED.value
    if rec.get("constraint") is not None:
        from .constraints import ConstraintState
        sup.engine.attach_constraint(
            req, ConstraintState.from_record(rec["constraint"],
                                             grammars=grammars))
    return req


class EngineSupervisor:
    """Crash-recovering wrapper around engine + scheduler.

    ``engine_factory() -> ContinuousBatchingEngine`` must build a FRESH
    engine with an identical configuration each call — the supervisor
    invokes it at construction and after every teardown ("rebuild pools
    from scratch"). Compiled step programs are carried across rebuilds
    (they are pure functions of their array arguments; only the pools
    and host bookkeeping are poisoned), so a recovery costs journal
    replay, not recompilation.

    Lifecycle knobs:

    - ``watchdog_s``: run each step on a watchdog thread and declare
      :class:`StepStalled` past the deadline (None = no watchdog; a
      genuinely hung step then blocks forever, as before).
    - ``backoff_s`` / ``backoff_max_s``: exponential backoff slept
      between consecutive failures (injectable ``sleep`` for tests).
    - ``circuit_threshold``: consecutive failed step attempts (no
      successful step in between) before the breaker opens — the
      supervisor marks every live request ``engine_dead``, reports
      ``health == "dead"`` and raises :class:`EngineDead`.
    - ``recover_after``: consecutive successful steps per rung of
      degraded-ladder descent.

    Degraded ladder (:data:`DEGRADED_MODES`): every recovery escalates
    one rung — 1: speculative decoding off (the most failure-adjacent
    optional program); 2: prefill chunk shrunk to one page (smallest
    step granularity, fastest fault isolation); 3: LOW-priority
    admissions shed at submit with the structured ``rejected_overload``
    finish reason. The current rung is published to the metrics
    registry (``serving_degraded_mode``) — the signal ROADMAP item 2's
    router will steer replicas by.
    """

    def __init__(self, engine_factory: Callable, *,
                 token_budget: Optional[int] = None,
                 watchdog_s: Optional[float] = None,
                 backoff_s: float = 0.05, backoff_max_s: float = 2.0,
                 circuit_threshold: int = 5, recover_after: int = 32,
                 reuse_compiled: bool = True,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep,
                 scheduler_kw: Optional[Dict] = None,
                 wal_dir: Optional[str] = None,
                 wal_fsync: str = "group",
                 wal_kw: Optional[Dict] = None,
                 checkpoint_every: Optional[int] = None,
                 checkpoint_prefix: bool = False,
                 flight_ticks: int = 256):
        self._factory = engine_factory
        self.token_budget = token_budget
        self.watchdog_s = watchdog_s
        self.backoff_s = float(backoff_s)
        self.backoff_max_s = float(backoff_max_s)
        self.circuit_threshold = int(circuit_threshold)
        self.recover_after = int(recover_after)
        self.reuse_compiled = reuse_compiled
        self.clock = clock
        self._sleep = sleep
        self._sched_kw = dict(scheduler_kw or {})
        # durable journal plane (ISSUE 15): wal_dir attaches an on-disk
        # write-ahead log under the journal — admissions/token commits/
        # ownership transitions become crash-durable, periodic
        # incremental checkpoints compact the log without stopping
        # admissions, and EngineSupervisor.recover_from_disk() rebuilds
        # a cold-started process from the directory alone
        self.wal = None
        self.checkpoint_every = checkpoint_every
        self.checkpoint_prefix = bool(checkpoint_prefix)
        if wal_dir is not None:
            from .wal import WriteAheadLog
            self.wal = WriteAheadLog(wal_dir, fsync=wal_fsync,
                                     **(wal_kw or {}))
        self.journal = RequestJournal(wal=self.wal)
        self.degraded_level = 0
        self.recoveries = 0
        self.injected_faults = 0
        self.real_faults = 0
        self.shed_total = 0
        self.steps_total = 0
        self._consec_failures = 0
        self._successes_since_change = 0
        self._next_rid = 0
        self._key_data: Optional[np.ndarray] = None
        self._spec_shelf = None
        self._chunk_shelf = None
        self._chunk_shrunk = False
        self._dead = False
        self._draining = False
        self.engine = None
        self.scheduler = None
        self.restored: Dict[int, object] = {}
        # crash flight recorder (ISSUE 16): a fixed ring of the last N
        # scheduler ticks, dumped as a CRC-framed black box on
        # EngineDead / any exception escaping step() / on demand.
        # flight_ticks=0 disables the recorder entirely.
        self._replica_id = -1
        self.flight = None
        if flight_ticks:
            from ..observability.flight import FlightRecorder
            self.flight = FlightRecorder(max_ticks=flight_ticks,
                                         meta={"replica": -1})
        self.last_flight_dump: Optional[str] = None
        self._build()
        self._snapshot_key()
        if self.wal is not None:
            # geometry record: cold recovery validates the replacement
            # engine against it (the restore() contract, made durable)
            cache = self.engine.cache
            self.wal.append("meta", {
                "page_size": cache.page_size, "max_len": cache.max_len,
                "max_batch": cache.max_batch,
                "kv_dtype": (str(np.dtype(cache.kv_dtype))
                             if cache.kv_dtype is not None else None),
                "constraints": bool(getattr(self.engine, "constraints",
                                            False)),
                "draft": _draft_identity(self.engine),
                "next_rid": self._next_rid})
            self.wal.commit(force=True)

    # ---- health ----
    @property
    def health(self) -> str:
        if self._dead:
            return "dead"
        return "healthy" if self.degraded_level == 0 else "degraded"

    @property
    def degraded_mode(self) -> str:
        return DEGRADED_MODES[self.degraded_level]

    @property
    def replica_id(self) -> int:
        """Cluster replica index carried by trace spans and flight
        dumps; -1 for a standalone supervisor. The setter propagates to
        the engine (and :meth:`_build` re-stamps across rebuilds), so
        cross-replica handoffs stitch into one trace."""
        return self._replica_id

    @replica_id.setter
    def replica_id(self, value: int) -> None:
        self._replica_id = int(value)
        if self.engine is not None:
            self.engine.replica_id = self._replica_id
        if self.flight is not None:
            self.flight.meta["replica"] = self._replica_id

    def _check_alive(self):
        if self._dead:
            raise EngineDead(
                "circuit breaker open after "
                f"{self.circuit_threshold} consecutive step failures")
        if self._draining:
            raise RuntimeError(
                "EngineSupervisor was drained; restore the checkpoint "
                "into a fresh supervisor (EngineSupervisor.restore)")

    # ---- build / teardown ----
    def _build(self):
        """(Re)build the engine + scheduler pair from scratch. Pools,
        allocator, trie, slots all start empty; compiled step programs
        carry over from the previous engine when configurations match
        (pure functions of their array arguments — only state was
        poisoned, not code)."""
        from .scheduler import ServingScheduler
        old = self.engine
        eng = self._factory()
        if not eng.idle:
            raise ValueError(
                "engine_factory must return a FRESH engine (no queued "
                "or running requests)")
        eng._next_rid = max(eng._next_rid, self._next_rid)
        if (old is not None and self.reuse_compiled
                and old.temperature == eng.temperature
                and old.use_kernel == eng.use_kernel
                and old._tp == eng._tp):
            eng._decode_fn = old._decode_fn
            eng._chunk_fns = old._chunk_fns
            eng._spec_fns = old._spec_fns
            eng.cache._cow_fn = old.cache._cow_fn
            eng.cache._scatter_fn = old.cache._scatter_fn
        if (old is not None
                and hasattr(eng.cache, "adopt_host_tier")
                and hasattr(old.cache, "adopt_host_tier")):
            # hierarchical KV (ISSUE 10): the host tier is HOST state
            # committed only after successful device→host gathers — it
            # survives the poisoned pool, so swapped-out sessions (and
            # the standing prefix store) carry into the rebuilt engine
            # and recovery SWAPS them in instead of replaying
            eng.cache.adopt_host_tier(old.cache)
        pool = getattr(eng, "adapters", None)
        if (pool is not None and old is not None
                and getattr(old, "adapters", None) is pool):
            # the adapter pool rode across the rebuild (the factory
            # closes over one pool, the usual shape): stale pins from
            # the poisoned engine's rows must not leak slots — recovery
            # re-admits every journaled session through acquire(),
            # which re-pins exactly the live set
            pool.reset_pins()
        if self._key_data is not None:
            import jax
            import jax.numpy as jnp
            eng._key = jax.random.wrap_key_data(
                jnp.asarray(self._key_data))
        self.engine = eng
        # re-stamp the replica identity across rebuilds (ISSUE 16) —
        # spans from the recovered engine must land in the same lane
        eng.replica_id = getattr(self, "_replica_id", -1)
        self.scheduler = ServingScheduler(
            eng, token_budget=self.token_budget, clock=self.clock,
            **self._sched_kw)
        self._apply_degraded()

    def _fence(self, old):
        """Best-effort fence on the poisoned engine: an abandoned
        (stalled) step thread that wakes later finds empty slot/pending
        tables and commits nothing. Injected stalls never commit anyway
        (they raise on wake); this narrows the window for real ones."""
        if old is None:
            return
        old._slots = [None] * old.max_batch
        old._pending = {}
        old._queue = []
        # drop dispatched-but-uncommitted work with the poisoned engine
        # (up to two decode steps of the pipeline): the journal holds
        # the last COMMITTED state, so the lost in-flight results are
        # recomputed by the replay — token-identically (the
        # fault-between-dispatch-and-commit gate)
        old.drop_inflight()

    def _snapshot_key(self):
        import jax
        self._key_data = np.asarray(jax.random.key_data(self.engine._key))

    # ---- degraded ladder ----
    def _apply_degraded(self):
        """Impose the current rung on the live engine (called on every
        rebuild and escalation; shelves keep what descent restores)."""
        eng = self.engine
        if self.degraded_level >= 1:
            if eng.spec is not None:
                self._spec_shelf = eng.spec
                eng.spec = None
        elif eng.spec is None and self._spec_shelf is not None:
            eng.spec = self._spec_shelf
            self._spec_shelf = None
        if self.degraded_level >= 2:
            if not self._chunk_shrunk:
                self._chunk_shelf = eng.prefill_chunk
                self._chunk_shrunk = True
            eng.prefill_chunk = eng.cache.page_size
        elif self._chunk_shrunk:
            eng.prefill_chunk = self._chunk_shelf
            self._chunk_shrunk = False
        if self.scheduler is not None:
            # mirror the rung onto the scheduler so load_stats() is a
            # complete health snapshot (the router's signal) even with
            # the metrics registry disabled
            self.scheduler.degraded_level = self.degraded_level
        _obs.serving_degraded(self.degraded_level)

    def _escalate(self):
        if self.degraded_level < len(DEGRADED_MODES) - 1:
            self.degraded_level += 1
        self._successes_since_change = 0
        self._apply_degraded()

    def _deescalate_maybe(self):
        if self.degraded_level == 0:
            return
        self._successes_since_change += 1
        if self._successes_since_change >= self.recover_after:
            self.degraded_level -= 1
            self._successes_since_change = 0
            self._apply_degraded()

    # ---- intake ----
    def submit(self, prompt, max_new_tokens: int = 16, *,
               priority=Priority.NORMAL,
               deadline_s: Optional[float] = None, eos_token_id=None,
               adapter_id: int = 0, constraint=None):
        """Journaled submit (write-ahead: the admission params are on
        the journal before anything can execute). At degraded level 3
        (``shed_low``) LOW-priority requests are rejected immediately
        with the structured ``rejected_overload`` finish reason instead
        of queueing into an engine that keeps failing."""
        self._check_alive()
        req = self.engine.create_request(
            prompt, max_new_tokens=max_new_tokens,
            eos_token_id=eos_token_id, adapter_id=adapter_id,
            constraint=constraint)
        req.priority = int(priority)
        self._next_rid = self.engine._next_rid
        return self.submit_request(req, deadline_s=deadline_s)

    def submit_request(self, req, *, deadline_s: Optional[float] = None):
        """Journaled intake of an EXISTING request handle — the
        cluster router's dispatch (and re-dispatch) path (ISSUE 9).
        The shed-LOW ladder applies only to FRESH requests: a handle
        that already committed tokens (or was preempted) is in-flight
        work being rehomed, and shedding it would lose it."""
        self._check_alive()
        fresh = not req.tokens and req.preemptions == 0
        if (fresh and self.degraded_level >= 3
                and int(req.priority) >= int(Priority.LOW)):
            req.done = True
            req.finish_reason = FinishReason.REJECTED_OVERLOAD.value
            self.shed_total += 1
            _obs.serving_cancelled(1, req.finish_reason)
            return req
        self.engine._next_rid = max(self.engine._next_rid, req.rid + 1)
        self._next_rid = max(self._next_rid, self.engine._next_rid)
        if deadline_s is not None:
            req.deadline_at = self.clock() + float(deadline_s)
        # write-ahead BEFORE the queue: a failed durable append rejects
        # the submission here, with the caller watching — never a
        # request the engine acknowledged but disk never heard of
        try:
            self.journal.record_submit(req, now=self.clock())
        except BaseException as exc:
            # a submit-path death never reaches step()'s dump hook —
            # leave the black box on this exit too (ISSUE 16)
            self._flight_dump_safe(type(exc).__name__, err=str(exc))
            raise
        self.scheduler.requeue(req)
        return req

    def adopt_running(self, req):
        """Journal a request installed DIRECTLY into a running slot
        (the decode side of a prefill→decode handoff —
        :meth:`~paddle_tpu.inference.ContinuousBatchingEngine.import_prefilled`
        bypasses the admission queue): from here this supervisor owns
        its recovery (a crash replays ``prompt + tokens[:-1]`` through
        THIS engine's continuation prefill, token-identically)."""
        self._check_alive()
        self.engine._next_rid = max(self.engine._next_rid, req.rid + 1)
        self._next_rid = max(self._next_rid, self.engine._next_rid)
        e = self.journal.record_submit(req, now=self.clock())
        e.admitted = True
        if self.journal.wal is not None and e.wal_submitted \
                and not e.wal_admitted:
            # the adopt side of a handoff owns recovery from here: make
            # the admitted flag durable with the submit record's lsn
            # neighborhood, not a whole step later
            self.journal.wal.append("step", {"rid": e.rid,
                                             "admitted": True})
            e.wal_admitted = True
        return req

    # ---- stepping ----
    def _guarded(self, fn):
        if self.watchdog_s is None:
            return fn()
        box: Dict = {}

        def run():
            try:
                box["r"] = fn()
            except BaseException as e:  # noqa: BLE001 — relayed below
                box["e"] = e

        t = threading.Thread(target=run, daemon=True,
                             name="supervised-engine-step")
        t.start()
        t.join(self.watchdog_s)
        if t.is_alive():
            raise StepStalled(self.watchdog_s)
        if "e" in box:
            raise box["e"]
        return box.get("r")

    def step(self) -> bool:
        """One supervised scheduler step. A failure triggers teardown +
        journal recovery and the step is retried on the rebuilt engine;
        the circuit breaker bounds consecutive failures. Returns False
        when no work remains. The post-step bookkeeping
        (:meth:`_on_success`: journal sync, WAL append/group-commit,
        incremental checkpoint) is inside the failure domain too — a
        durable-log fault recovers exactly like a device fault, and
        the retried step re-runs against the requeued sessions."""
        self._check_alive()
        try:
            return self._step_supervised()
        except BaseException as exc:
            # black box on the way out (ISSUE 16): EngineDead (circuit
            # open) and anything a failure handler re-raised leave a
            # flight dump next to the journal before propagating —
            # even when _on_failure itself was replaced (the chaos
            # harness's process-kill surrogate raises from inside it)
            self._flight_dump_safe(type(exc).__name__, err=str(exc))
            raise

    def _step_supervised(self) -> bool:
        while True:
            try:
                alive = self._guarded(self.scheduler.step)
                self._on_success()
                self._record_flight_tick()
                if not alive and self.wal is not None:
                    # going idle: force the buffered delta pass + fsync
                    # so a QUIESCENT supervisor is always durably
                    # consistent — the group-commit loss window only
                    # ever spans work actually in flight (a crash
                    # mid-window replays it token-identically; it must
                    # not resurrect work that visibly finished)
                    self._sync_journal(force=True)
                    self.wal.commit(force=True)
            except EngineDead:
                raise
            except Exception as e:  # noqa: BLE001 — classify + recover
                self._on_failure(e)
                continue
            return alive

    def run(self) -> None:
        """Drive steps until every request finished (raises
        :class:`EngineDead` if the circuit opens first)."""
        while self.step():
            pass

    def _sync_journal(self, wal: bool = True, force: bool = False):
        self.journal.sync(swapped_check=getattr(
            self.engine.cache, "has_swapped", None), wal=wal,
            force=force)

    def _on_success(self):
        self.steps_total += 1
        self._consec_failures = 0
        self._sync_journal()
        self._snapshot_key()
        if self.wal is not None:
            if (self.engine.temperature != 0.0
                    and self._key_data is not None):
                # sampled decode: the PRNG snapshot is recovery state
                # (greedy replay never consults it — skip the bytes)
                import base64
                self.wal.append("key", {
                    "data": base64.b64encode(
                        self._key_data.tobytes()).decode(),
                    "dtype": str(self._key_data.dtype),
                    "shape": list(self._key_data.shape)})
            self.wal.commit()       # the group-commit boundary
            if (self.checkpoint_every
                    and self.steps_total % self.checkpoint_every == 0):
                self.checkpoint_now()
        self._deescalate_maybe()
        _obs.serving_journal(self.journal.size, self.journal.token_count)

    # ---- flight recorder (ISSUE 16) ----
    def _record_flight_tick(self, fault: Optional[str] = None) -> None:
        """Fold one scheduler tick into the flight ring: plan summary,
        budget use, degraded rung, failure streak, WAL lsn. One small
        dict append — noise next to the WAL append the tick already
        paid; no-op when the recorder is disabled."""
        if self.flight is None:
            return
        sched = self.scheduler
        plan = sched.last_plan if sched is not None else None
        self.flight.record_tick(
            step=self.steps_total,
            committed=(sched.last_committed if sched is not None else 0),
            planned_tokens=(plan.scheduled_tokens if plan is not None
                            else 0),
            reserved_tokens=(plan.reserved_tokens if plan is not None
                             else 0),
            budget=(plan.budget if plan is not None else None),
            decode_slots=(len(plan.decode_slots) if plan is not None
                          else 0),
            prefills=(len(plan.prefills) if plan is not None else 0),
            queued=(sum(len(q) for q in sched._queues.values())
                    if sched is not None else 0),
            degraded=self.degraded_level,
            failures=self._consec_failures,
            host_frac=(sched.last_host_frac if sched is not None
                       else None),
            wal_lsn=(self.wal.lsn if self.wal is not None else None),
            fault=fault)
        _obs.serving_flight_tick()

    def dump_flight(self, reason: str = "manual",
                    out_dir: Optional[str] = None,
                    err: Optional[str] = None) -> Optional[str]:
        """Write the flight-recorder black box (on demand, and the
        crash paths' exit hatch): the tick ring + request-trace tails
        as a CRC-framed ``flight-<ts>.json`` in ``out_dir`` (default:
        the WAL/journal directory, else the system temp dir). Returns
        the path; None when the recorder is disabled."""
        if self.flight is None:
            return None
        if out_dir is None:
            out_dir = (self.wal.path if self.wal is not None
                       else tempfile.gettempdir())
        extra = {"health": self.health,
                 "degraded_level": self.degraded_level,
                 "consec_failures": self._consec_failures,
                 "recoveries": self.recoveries,
                 "steps_total": self.steps_total}
        if err:
            extra["error"] = err
        path = self.flight.dump(out_dir, reason, extra=extra)
        self.last_flight_dump = path
        _obs.serving_flight_dump(reason, os.path.getsize(path))
        return path

    def _flight_dump_safe(self, reason: str, err: str = "") -> None:
        """Best-effort dump on the crash path — a second failure here
        must never mask the one propagating."""
        try:
            self.dump_flight(reason, err=err)
        except Exception:
            pass

    def checkpoint_now(self) -> Optional[str]:
        """One INCREMENTAL checkpoint (ISSUE 15): snapshot the live
        journal + PRNG key (and, with ``checkpoint_prefix``, the
        prefix-trie pages — the drain machinery) into an atomic
        ``ckpt-<lsn>.npz`` next to the log, then prune the segments it
        covers. Admissions never stop — this is a host-side call
        between steps; cold recovery is checkpoint + log-suffix
        replay."""
        if self.wal is None:
            return None
        now = self.clock()
        cache = self.engine.cache
        grammars: Dict[str, Dict] = {}
        meta = {
            "sessions": [e.as_record(now, grammars=grammars)
                         for e in self.journal.live_entries()],
            "grammars": grammars,
            "next_rid": self._next_rid,
            "page_size": cache.page_size,
            "max_len": cache.max_len,
            "max_batch": cache.max_batch,
            "kv_dtype": (str(np.dtype(cache.kv_dtype))
                         if cache.kv_dtype is not None else None),
            "constraints": bool(getattr(self.engine, "constraints",
                                        False)),
            "draft": _draft_identity(self.engine),
            "prefix": None,
        }
        arrays: Dict[str, np.ndarray] = {
            "key_data": self._key_data if self._key_data is not None
            else np.zeros((0,), np.uint32)}
        if self.checkpoint_prefix:
            ckpt = cache.checkpoint_prefix()
            if ckpt is not None:
                meta["prefix"] = {
                    "page_ids": ckpt["page_ids"],
                    "records": ckpt["records"],
                    "shapes": {n: list(a.shape)
                               for n, a in ckpt["arrays"].items()},
                    "dtypes": {n: str(a.dtype)
                               for n, a in ckpt["arrays"].items()},
                }
                for n, a in ckpt["arrays"].items():
                    arrays[f"prefix_{n}"] = np.frombuffer(
                        np.ascontiguousarray(a).tobytes(), np.uint8)
        path = self.wal.checkpoint(meta, arrays)
        # the checkpoint carries its own grammar dict and pruning may
        # compact away earlier grammar records: future submits must
        # re-append their tables, so the dedupe set resets here
        self.journal._wal_grammars.clear()
        return path

    def _on_failure(self, err: Exception):
        stalled = isinstance(err, StepStalled)
        injected = isinstance(err, InjectedFault)
        site = getattr(err, "site", None) or "step"
        kind = getattr(err, "mode", None) or type(err).__name__
        inj = _ACTIVE
        if stalled and not injected:
            # the watchdog only ever sees a StepStalled — ask the
            # installed injector whether the stall was its own, so
            # chaos runs never inflate the REAL-failure counter (and a
            # genuine stall during a chaos run is at worst attributed
            # to the one pending injection, never silently dropped)
            if inj is not None and inj.pending_stalls:
                site = inj.pending_stalls.pop(0)
                injected = True
        elif injected and kind == "stall":
            # the stall woke BEFORE the watchdog (stall_s < watchdog_s)
            # and raised itself: retire its pending entry, or a later
            # REAL watchdog stall would be misattributed as injected
            if inj is not None and site in inj.pending_stalls:
                inj.pending_stalls.remove(site)
        if injected:
            self.injected_faults += 1
            # the injector already counted itself at fire time
        else:
            self.real_faults += 1
            _obs.serving_fault(site, kind, injected=False)
        self._consec_failures += 1
        # a faulted tick never reached the success-path recorder —
        # fold it in here so the black box shows the firing itself
        self._record_flight_tick(fault=f"{site}:{kind}")
        if self._consec_failures >= self.circuit_threshold:
            self._die(err)
        self._sleep(min(self.backoff_max_s,
                        self.backoff_s
                        * (2 ** (self._consec_failures - 1))))
        self._recover(sync=not stalled)

    def _die(self, err: Exception):
        """Open the circuit: mark every live request with the
        structured ``engine_dead`` reason (nothing is silently lost —
        the journal is retained for post-mortem/drain tooling) and stop
        retrying."""
        self._dead = True
        for e in self.journal.live_entries():
            req = e.req
            if req is not None and not req.done:
                req.done = True
                req.finish_reason = "engine_dead"
        if self.scheduler is not None:
            self.scheduler.degraded_level = len(DEGRADED_MODES)
        _obs.serving_degraded(len(DEGRADED_MODES))  # off-ladder: dead
        raise EngineDead(
            f"circuit breaker open after {self._consec_failures} "
            f"consecutive step failures; last: "
            f"{type(err).__name__}: {err}") from err

    def _recover(self, sync: bool = True):
        """Teardown + rebuild + journal restore. ``sync=False`` for
        stalls: the abandoned thread may still be running, so the
        journal keeps its last-committed state instead of reading the
        handles mid-race."""
        t0 = _obs.generate_begin()
        if sync:
            # in-memory only: the WAL delta pass is deferred to the
            # next successful step's sync — a recovery triggered BY a
            # WAL fault must not re-enter the faulting append mid-
            # recovery (the cursors heal once appends succeed again)
            self._sync_journal(wal=False)
        live = self.journal.live_entries()
        # host-resident sessions (ISSUE 10) swap back in: their resume
        # is one page scatter, not a replay — the recovery bill counts
        # only the sessions that actually re-forward tokens
        replay = sum(e.prompt.size + max(0, len(e.tokens) - 1)
                     for e in live if e.admitted and not e.swapped)
        self._fence(self.engine)
        self._build()
        for e in live:
            req = e.req
            req.slot = None
            req.done = False
            req.tokens = list(e.tokens)
            if e.admitted:
                # a crashed-out session is an eviction the request never
                # asked for: resume semantics (transient reason, replay
                # accounting, deadline exemption) apply verbatim
                req.preemptions = e.preemptions + 1
                req.finish_reason = FinishReason.PREEMPTED.value
            else:
                req.finish_reason = None
            self.scheduler.requeue(req)
        self.recoveries += 1
        self._escalate()
        _obs.serving_fault_recovery(t0, len(live), replay)

    # ---- drain / restore ----
    def drain(self, path: str) -> Dict:
        """Stop admissions and checkpoint to ``path`` (one ``.npz``):
        every live session's journal record, the prefix-cache trie
        (structure + page KV bytes), the PRNG key snapshot and the
        engine geometry for restore-time validation. The supervisor is
        frozen afterwards (submit/step raise) — restore the file into a
        fresh process via :meth:`restore`. Returns a summary dict.

        Live grammar-constrained sessions checkpoint too (ISSUE 15
        satellite — the old refusal is gone): each session record
        carries the serialized DFA table + live state id + violation
        counters, and :meth:`restore` re-attaches an equivalent
        :class:`~paddle_tpu.serving.constraints.ConstraintState`, so a
        mid-grammar session resumes always-valid and token-identical
        (gated in tests/test_wal.py)."""
        self._check_alive()
        t0 = _obs.generate_begin()
        # the decode pipeline holds up to two dispatched-but-
        # uncommitted steps: commit them so sessions checkpoint with
        # every token the device already produced
        self.engine.fence()
        self._sync_journal(force=True)
        self._snapshot_key()
        now = self.clock()
        cache = self.engine.cache
        ckpt = cache.checkpoint_prefix()
        grammars: Dict[str, Dict] = {}
        meta = {
            "sessions": [e.as_record(now, grammars=grammars)
                         for e in self.journal.live_entries()],
            "grammars": grammars,
            "next_rid": self._next_rid,
            "page_size": cache.page_size,
            "max_len": cache.max_len,
            "max_batch": cache.max_batch,
            "kv_dtype": (str(np.dtype(cache.kv_dtype))
                         if cache.kv_dtype is not None else None),
            "prefix": None,
        }
        arrays: Dict[str, np.ndarray] = {
            "key_data": self._key_data if self._key_data is not None
            else np.zeros((0,), np.uint32)}
        if ckpt is not None:
            meta["prefix"] = {
                "page_ids": ckpt["page_ids"],
                "records": ckpt["records"],
                "shapes": {n: list(a.shape)
                           for n, a in ckpt["arrays"].items()},
                "dtypes": {n: str(a.dtype)
                           for n, a in ckpt["arrays"].items()},
            }
            for n, a in ckpt["arrays"].items():
                # raw-byte views round-trip extension dtypes (bf16)
                # that np.savez cannot serialize natively
                arrays[f"prefix_{n}"] = np.frombuffer(
                    np.ascontiguousarray(a).tobytes(), np.uint8)
        with open(path, "wb") as f:
            np.savez(f, meta=np.frombuffer(
                json.dumps(meta).encode(), np.uint8), **arrays)
        # freeze ONLY once the checkpoint is safely on disk: a failed
        # write (bad path, disk full) leaves the supervisor serving —
        # bricking a healthy engine with nothing saved would strand
        # every in-flight session
        self._draining = True
        if self.wal is not None:
            # the drain checkpoint owns these sessions now: tombstone
            # them in the WAL (and fsync) so a cold recovery of this
            # directory can never resurrect what restore() will also
            # revive elsewhere — exactly one recovery owner
            try:
                for e in self.journal.live_entries():
                    if e.wal_submitted:
                        self.wal.append("finish", {"rid": e.rid,
                                                   "reason": "drained"})
                self.wal.commit(force=True)
                self.wal.close()
            except Exception:
                pass        # drain file is authoritative regardless
        nbytes = os.path.getsize(path)
        n_pages = len(meta["prefix"]["page_ids"]) if meta["prefix"] \
            else 0
        _obs.serving_drain_checkpoint(t0, nbytes,
                                      len(meta["sessions"]), n_pages)
        return {"path": path, "bytes": nbytes,
                "sessions": len(meta["sessions"]),
                "trie_pages": n_pages}

    @classmethod
    def restore(cls, engine_factory: Callable, path: str,
                **kw) -> "EngineSupervisor":
        """Build a fresh supervisor and restore a :meth:`drain`
        checkpoint into it: trie pages are written back into the new
        pool FIRST (so session replays — and future admissions — hit
        the restored prefix cache), then every checkpointed session is
        requeued through the resume path. Restored request handles live
        in ``.restored`` (rid -> request)."""
        sup = cls(engine_factory, **kw)
        t0 = _obs.generate_begin()
        ckpt = load_drain_checkpoint(path)
        meta = ckpt["meta"]
        cache = sup.engine.cache
        for knob in ("page_size", "max_len", "max_batch"):
            if meta[knob] != getattr(cache, knob):
                raise ValueError(
                    f"restore: checkpoint {knob}={meta[knob]} does "
                    f"not match the fresh engine's "
                    f"{getattr(cache, knob)} — the factory must "
                    f"rebuild the drained engine's geometry")
        kv = (str(np.dtype(cache.kv_dtype))
              if cache.kv_dtype is not None else None)
        if meta["kv_dtype"] != kv:
            raise ValueError(
                f"restore: checkpoint kv_dtype={meta['kv_dtype']} "
                f"!= engine kv_dtype={kv}")
        draft = _draft_identity(sup.engine)
        if meta.get("draft") != draft:
            raise ValueError(
                f"restore: checkpoint draft identity="
                f"{meta.get('draft')} != engine {draft} — the factory "
                f"must rebuild the same draft_layers/spec_tree (the "
                f"draft pool itself rebuilds cold)")
        key_data = ckpt["key_data"]
        if key_data.size:
            import jax
            import jax.numpy as jnp
            sup._key_data = key_data
            sup.engine._key = jax.random.wrap_key_data(
                jnp.asarray(key_data))
        n_pages = 0
        if ckpt["prefix"] is not None:
            cache.restore_prefix(ckpt["prefix"])
            n_pages = len(ckpt["prefix"]["page_ids"])
        sup._next_rid = int(meta["next_rid"])
        sup.engine._next_rid = max(sup.engine._next_rid, sup._next_rid)
        sup.restored: Dict[int, object] = {}
        for rec in meta["sessions"]:
            req = _session_from_record(sup, rec,
                                       grammars=meta.get("grammars"))
            sup.journal.adopt(req, rec, now=sup.clock())
            sup.scheduler.requeue(req)
            sup.restored[req.rid] = req
        _obs.serving_drain_restore(t0, os.path.getsize(path),
                                   len(meta["sessions"]), n_pages)
        return sup

    # ---- cold-restart recovery (ISSUE 15) ----
    @classmethod
    def recover_from_disk(cls, engine_factory: Callable, wal_dir: str,
                          **kw) -> "EngineSupervisor":
        """Rebuild a supervisor from its durable journal directory
        after WHOLE-PROCESS death (``kill -9``, OOM-kill, host reboot
        — no drain, no in-memory journal): scan the WAL (torn tail
        truncated at the last valid frame, corrupt media quarantined,
        newest VALID checkpoint + log-suffix replay), build a fresh
        engine, and requeue every journaled live session through the
        ``resume_sequence`` replay path — token-identical to an
        uninterrupted run, the same gate the in-process recovery
        carries (tests/test_wal.py crash-point sweep). The recovered
        supervisor keeps appending to the SAME directory, so repeated
        crashes recover repeatedly."""
        from .wal import recover_state
        t0 = _obs.generate_begin()
        state = recover_state(wal_dir, repair=True)
        kw = dict(kw)
        wk = dict(kw.get("wal_kw") or {})
        # the scan just ran (and repaired): hand its lsn to the fresh
        # log so construction doesn't re-read every segment
        wk.setdefault("last_lsn", state["report"]["last_lsn"])
        kw["wal_kw"] = wk
        sup = cls(engine_factory, wal_dir=wal_dir, **kw)
        try:
            sup._install_recovered(state, t0)
        except Exception:
            # a REFUSED recovery (factory geometry / kv tier / draft
            # identity mismatch) must be side-effect-free on the
            # journal: construction above already appended the fresh
            # engine's meta record, so latest-wins would hand the NEXT
            # attempt the wrong factory's identity to validate against
            # — re-append the dead incarnation's geometry so a retry
            # with the correct factory still recovers
            geo = state.get("geometry")
            if geo is not None and sup.wal is not None:
                sup.wal.append("meta", dict(
                    geo, next_rid=int(state.get("next_rid", 0))))
                sup.wal.commit(force=True)
            raise
        # surface the dead incarnation's black box (if it got one out)
        # so post-mortem tooling finds it next to the recovered WAL
        from ..observability import flight as _flight
        dumps = _flight.find_dumps(wal_dir)
        if dumps:
            sup.last_flight_dump = dumps[-1]
        return sup

    def _install_recovered(self, state: Dict, t0: int = 0) -> None:
        """Apply a :func:`~paddle_tpu.serving.wal.recover_state` fold:
        validate geometry, install the PRNG snapshot, requeue every
        live session (durable journal entries — only future deltas
        append)."""
        geo = state.get("geometry")
        cache = self.engine.cache
        if geo is not None:
            for knob in ("page_size", "max_len", "max_batch"):
                if geo.get(knob) is not None \
                        and geo[knob] != getattr(cache, knob):
                    raise ValueError(
                        f"recover_from_disk: journaled {knob}="
                        f"{geo[knob]} does not match the fresh "
                        f"engine's {getattr(cache, knob)} — the "
                        f"factory must rebuild the dead engine's "
                        f"geometry")
            kv = (str(np.dtype(cache.kv_dtype))
                  if cache.kv_dtype is not None else None)
            if geo.get("kv_dtype") != kv:
                raise ValueError(
                    f"recover_from_disk: journaled kv_dtype="
                    f"{geo.get('kv_dtype')} != engine kv_dtype={kv}")
            draft = _draft_identity(self.engine)
            if geo.get("draft") != draft:
                raise ValueError(
                    f"recover_from_disk: journaled draft identity="
                    f"{geo.get('draft')} != engine {draft} — the "
                    f"factory must rebuild the same draft_layers/"
                    f"spec_tree (the draft pool itself rebuilds cold)")
        key_data = state.get("key_data")
        if key_data is not None and key_data.size:
            import jax
            import jax.numpy as jnp
            self._key_data = np.asarray(key_data)
            self.engine._key = jax.random.wrap_key_data(
                jnp.asarray(key_data))
        if state.get("prefix") is not None:
            # checkpoint_prefix payload: write the trie pages back
            # into the fresh pool FIRST, so the session replays below
            # (and future admissions) hit the restored prefix cache —
            # the same ordering restore() uses
            cache.restore_prefix(state["prefix"])
        self._next_rid = max(self._next_rid,
                             int(state.get("next_rid", 0)))
        self.engine._next_rid = max(self.engine._next_rid,
                                    self._next_rid)
        report = state.get("report", {})
        self.restored = {}
        for rid in sorted(state.get("sessions", {})):
            trs = _obs.serving_trace_now()
            rec = state["sessions"][rid]
            req = _session_from_record(self, rec,
                                       grammars=state.get("grammars"))
            self.journal.adopt(req, rec, durable=True)
            # requeue attaches the trace; the replay span lands after
            # so the recovered handle actually records it
            self.scheduler.requeue(req)
            if trs:
                _obs.serving_trace_span(
                    req, "wal_replay", trs,
                    replica=self.replica_id, seq=len(req.tokens))
            self.restored[req.rid] = req
        _obs.serving_wal_recovery(
            t0, len(self.restored),
            int(report.get("replayed_records", 0)),
            int(report.get("torn_tail_truncated", 0)),
            int(report.get("corrupt_quarantined", 0))
            + int(report.get("ckpt_quarantined", 0)))

    # ---- introspection ----
    def load_stats(self) -> Dict:
        """The scheduler's structured load snapshot
        (:meth:`~paddle_tpu.serving.ServingScheduler.load_stats`) plus
        the supervisor's own health/draining state — the per-replica
        signal the cluster router dispatches by."""
        s = (self.scheduler.load_stats()
             if self.scheduler is not None else {
                 "queue_depths": {}, "queued_total": 0,
                 "queued_tokens": 0, "inflight_tokens": 0, "running": 0,
                 "pending_prefills": 0, "free_slots": 0,
                 "oldest_deadline_slack_s": None, "pool_occupancy": 1.0,
                 "pool_free_pages": 0,
                 "degraded_level": len(DEGRADED_MODES),
                 "degraded_mode": "dead"})
        s["health"] = self.health
        s["draining"] = self._draining
        if self.wal is not None:
            # durable-plane lag signal (ISSUE 15): how far the on-disk
            # journal trails host state — a router/autoscaler can keep
            # crash-exposure bounded the same way it reads backlog
            s["wal"] = self.wal.stats()
        return s

    def stats(self) -> Dict:
        s = self.scheduler.stats() if self.scheduler is not None else {}
        s.update({
            "health": self.health,
            "degraded_level": self.degraded_level,
            "degraded_mode": self.degraded_mode,
            "recoveries": self.recoveries,
            "injected_faults": self.injected_faults,
            "real_faults": self.real_faults,
            "shed_total": self.shed_total,
            "supervised_steps": self.steps_total,
            "journal_entries": self.journal.size,
            "journal_tokens": self.journal.token_count,
        })
        return s
