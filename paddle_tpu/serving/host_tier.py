"""Hierarchical KV tier (ISSUE 10): a host-RAM page tier under the
paged allocator, swap-in preemption resume, and a standing prefix store.

The PR 2–9 stack treats HBM as the ONLY KV tier: ``PoolExhausted``
means evict-and-replay — a preempted victim pays a replay prefill
proportional to its resident tokens, and a prefix-trie chain evicted
under pool pressure is simply recomputed on its next admission. This
module adds the tier
below HBM, the same device↔host discipline the training side proves out
in the ZeRO-3 offload path (tests/test_offload.py):

- :class:`HostPageStore` — a host-numpy page pool: entries hold
  raw-uint8 page payloads + dtype/shape metadata (the
  :meth:`~paddle_tpu.serving.PagedKVCache.export_request` byte
  convention, so bf16 and every int8-KV tier round-trip exactly),
  LRU-bounded by a page capacity, with an optional STANDING on-disk
  layer (one ``.npz`` per prefix chain) that survives process restarts.

- :class:`TieredKVCache` — a :class:`~paddle_tpu.serving.PagedKVCache`
  whose evictions move bytes instead of dropping them:

  * **swap-out / swap-in** — a preemption victim's live pages gather to
    host (:func:`_pool_gather`, one jitted read) and its device pages
    free; resume allocates fresh pages and scatters the bytes back
    through the SHARED donated
    :func:`~paddle_tpu.serving.paged_cache._pool_scatter` program —
    the PR 9 handoff scatter, so swap-in is bit-identical to having
    never been evicted by the same argument the prefill→decode handoff
    gate already proves (raw bytes in, raw bytes out; page ids differ
    but the block table makes content position-addressed). Resume cost
    drops from ``O(resident tokens)`` of replay-prefill FLOPs to one
    host→device page copy.
  * **demote / promote** — a prefix-trie chain evicted under
    ``PoolExhausted`` demotes its full-page KV bytes to the host store
    (keyed by the chain's token prefix — the same context hash the trie
    uses) instead of dying; the next admission that walks past the
    device trie's span promotes matching host pages back into the pool
    and re-registers them, so the prompt prefix-HITs instead of
    re-prefilling.
  * **standing prefix store** — registered prompt chains write through
    to the store (RAM, plus disk when ``prefix_store_dir`` is set), so
    a RESTARTED engine — or a PR 9 cluster's replacement replica —
    serves a persisted system prompt as a prefix HIT without any drain
    checkpoint having been taken: the PR 8 drain/restore trie
    persistence generalized into an always-warm tier.

Fault sites (ISSUE 8 discipline): ``swap_out`` fires BEFORE any gather
(a fault commits nothing — the victim still evicts through the plain
path or the supervisor recovers it), ``swap_in`` BEFORE any allocation
(the payload survives for the retry). Both are chaos-soaked with zero
lost/duplicated requests (tools/chaos_soak.py).

Telemetry: the ``serving_swap_*`` family (out/in counters + bytes,
transfer-latency histograms), the ``serving_host_pool_*`` occupancy
gauges and the demote/promote counters.
"""
from __future__ import annotations

import hashlib
import json
import os
import time
from collections import OrderedDict
from typing import Dict, List, Optional

import numpy as np

from ..observability import hooks as _obs
from .paged_cache import PagedKVCache, PoolExhausted
from .resilience import (CorruptionDetected, _np_dtype, fault_point,
                         payload_checksums, tamper_point,
                         verify_checksums)


def _pool_gather(pool: Dict, src):
    """The swap-out gather program: read the pages at ids ``src`` out
    of every pool array — shape ``(L, k, page, ...)`` per array — as
    ONE jitted program (the read half of the
    :func:`~paddle_tpu.serving.paged_cache._pool_scatter` pair).
    Mosaic-lowered by ``tools/aot_validate.py --config serving-host``
    at fp, int8-KV and tp-sharded pool layouts."""
    return {name: arr[:, src] for name, arr in pool.items()}


def _key_name(key: bytes) -> str:
    """Stable on-disk name for a prefix-chain key (the chain's token
    bytes) — content-addressed, so two engines sharing one store
    directory converge on the same files."""
    return hashlib.sha1(key).hexdigest() + ".npz"


def _tampered_entry(entry: Dict) -> Dict:
    """A copy of ``entry`` with one payload byte flipped — the
    injector's payload-corruption mode (ISSUE 13:
    ``FaultInjector.arm_tamper``): the CHECKSUM verifier, not the
    injector, must detect the damage, so the whole
    detect→quarantine→replay path runs on real corrupt bytes."""
    arrays = {n: np.array(a, copy=True)
              for n, a in entry["arrays"].items()}
    name = sorted(arrays)[0]
    flat = arrays[name].reshape(-1).view(np.uint8)
    if flat.size:
        flat[flat.size // 2] ^= 0xFF
    out = dict(entry)
    out["arrays"] = arrays
    return out


class HostPageStore:
    """Host-numpy page pool: the RAM (+ optional disk) tier below HBM.

    Entries are keyed by an arbitrary hashable key — the tiered cache
    uses ``("swap", rid)`` for swapped-out requests, the raw token
    bytes of a chain prefix for demoted/persisted trie pages, and the
    adapter plane (ISSUE 14) ``b"adapter/<id>"`` for LoRA factors
    demoted on slot reclaim (:class:`~paddle_tpu.serving.adapters.
    AdapterPool`) — and hold
    raw-uint8 array payloads with dtype/shape metadata (the
    ``export_request`` byte convention: extension dtypes like bf16
    round-trip exactly). ``capacity_pages`` LRU-bounds RAM residency;
    dropping an entry is always safe (a dropped swap payload falls back
    to the replay-prefill resume, a dropped prefix page to a plain
    prefill miss). ``path`` adds the STANDING tier: entries put with
    ``persist=True`` (prefix chains) also land on disk as one ``.npz``
    each and are readable by any later process — a RAM miss falls
    through to disk before reporting a miss."""

    def __init__(self, page_size: int,
                 capacity_pages: Optional[int] = None,
                 path: Optional[str] = None,
                 max_disk_bytes: Optional[int] = None):
        if capacity_pages is not None and capacity_pages < 1:
            raise ValueError(
                f"HostPageStore: capacity_pages={capacity_pages} "
                f"must be >= 1 (or None for unbounded)")
        if max_disk_bytes is not None and max_disk_bytes < 1:
            raise ValueError(
                f"HostPageStore: max_disk_bytes={max_disk_bytes} "
                f"must be >= 1 (or None for unbounded)")
        self.page_size = page_size
        self.capacity_pages = capacity_pages
        self.path = path
        #: ISSUE 15 satellite: byte bound on the STANDING disk layer —
        #: long-running engines write prefix chains through forever,
        #: so without a cap artifacts/ grows without limit. Oldest-
        #: mtime files prune first (LRU by last write/promotion);
        #: pruning a standing entry is always safe — the next miss is
        #: a plain prefix MISS and the chain re-prefills.
        self.max_disk_bytes = max_disk_bytes
        self._entries: "OrderedDict" = OrderedDict()
        self.pages_resident = 0
        self.bytes_resident = 0
        self.puts_total = 0
        self.hits_total = 0
        self.misses_total = 0
        self.capacity_drops_total = 0
        #: corrupt/torn entries removed so they can never be re-served
        #: (ISSUE 13) — the integrity gate's quarantine counter
        self.quarantined_total = 0
        #: standing-store files (and bytes) removed by the disk bound —
        #: next to the corrupt-unlink counter, so dashboards can tell
        #: capacity pruning from quarantine
        self.disk_pruned_total = 0
        self.disk_pruned_bytes_total = 0
        # cached standing-store residency: adjusted on every write,
        # re-synced from a full directory scan only when the bound
        # trips (the prune needs the listing anyway to pick LRU) — a
        # put() on the serving hot path must not stat the whole
        # directory (engines sharing a dir drift the cache slightly;
        # the overflow re-scan corrects it before anything prunes)
        self._disk_bytes: Optional[int] = None
        if path is not None:
            os.makedirs(path, exist_ok=True)
            if max_disk_bytes is not None:
                self._disk_bytes = sum(
                    os.path.getsize(os.path.join(path, f))
                    for f in os.listdir(path) if f.endswith(".npz"))

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        return self.contains(key)

    def contains(self, key) -> bool:
        """Side-effect-free existence probe: RAM membership plus a
        disk ``stat`` for bytes keys — no payload read, no LRU bump,
        no hit/miss counting, and crucially no disk→RAM promotion (a
        probe must never evict resident swap payloads to answer a
        yes/no question)."""
        if key in self._entries:
            return True
        return (self.path is not None and isinstance(key, bytes)
                and os.path.exists(
                    os.path.join(self.path, _key_name(key))))

    @staticmethod
    def encode(arrays: Dict[str, np.ndarray]) -> Dict:
        """Pack host arrays into the raw-uint8 + meta payload form,
        stamped with per-array CRCs (ISSUE 13) — every consumer
        verifies them before installing the bytes anywhere."""
        enc, meta, pages = {}, {}, 0
        for name, a in arrays.items():
            a = np.ascontiguousarray(a)
            enc[name] = np.frombuffer(a.tobytes(), np.uint8)
            meta[name] = {"shape": list(a.shape), "dtype": str(a.dtype)}
            if a.ndim >= 2:
                pages = max(pages, int(a.shape[1]))
        return {"arrays": enc, "meta": meta, "pages": pages,
                "bytes": sum(int(v.nbytes) for v in enc.values()),
                "checksums": payload_checksums(enc)}

    @staticmethod
    def decode(entry: Dict) -> Dict[str, np.ndarray]:
        """Unpack a payload back into typed host arrays."""
        return {
            name: np.frombuffer(bytes(entry["arrays"][name]),
                                _np_dtype(m["dtype"])).reshape(m["shape"])
            for name, m in entry["meta"].items()}

    def _account(self, entry: Dict, sign: int):
        self.pages_resident += sign * entry["pages"]
        self.bytes_resident += sign * entry["bytes"]

    def _publish(self):
        _obs.serving_host_pool(self.pages_resident, self.bytes_resident,
                               self.capacity_pages)

    def put(self, key, arrays: Dict[str, np.ndarray],
            extra: Optional[Dict] = None, persist: bool = False) -> Dict:
        """Store ``arrays`` (typed host arrays) under ``key``; returns
        the encoded entry. ``persist=True`` (bytes keys only — prefix
        chains) also writes the standing ``.npz`` when the store has a
        disk path. Over-capacity RAM entries drop LRU-first; persisted
        entries stay readable from disk after a RAM drop."""
        if persist and not isinstance(key, bytes):
            # validate BEFORE any mutation: the error path must leave
            # residency accounting and the gauges untouched
            raise ValueError(
                "HostPageStore: only bytes keys (prefix-chain token "
                "bytes) persist to the standing store")
        entry = self.encode(arrays)
        entry["extra"] = dict(extra or {})
        entry["persist"] = bool(persist)
        old = self._entries.pop(key, None)
        if old is not None:
            self._account(old, -1)
        self._entries[key] = entry
        self._account(entry, +1)
        self.puts_total += 1
        self._enforce_capacity()
        if persist and self.path is not None:
            self._write_disk(key, entry)
        self._publish()
        return entry

    def _enforce_capacity(self):
        """Drop LRU entries until RAM residency fits ``capacity_pages``
        — shared by :meth:`put` and :meth:`get`'s disk→RAM promotion,
        so read-driven residency obeys the same bound write-driven
        residency does (persisted entries stay readable from disk)."""
        if self.capacity_pages is None:
            return
        while (self.pages_resident > self.capacity_pages
               and len(self._entries) > 1):
            _, dropped = self._entries.popitem(last=False)
            self._account(dropped, -1)
            self.capacity_drops_total += 1

    def _write_disk(self, key: bytes, entry: Dict):
        meta = {"meta": entry["meta"], "pages": entry["pages"],
                "extra": entry["extra"],
                "checksums": entry.get("checksums")}
        fn = os.path.join(self.path, _key_name(key))
        tmp = fn + ".tmp"
        old_size = 0
        if self._disk_bytes is not None:
            try:
                old_size = os.path.getsize(fn)
            except OSError:
                pass
        with open(tmp, "wb") as f:
            np.savez(f, key=np.frombuffer(key, np.uint8),
                     meta=np.frombuffer(json.dumps(meta).encode(),
                                        np.uint8),
                     **{f"a_{n}": a for n, a in entry["arrays"].items()})
        os.replace(tmp, fn)     # atomic: a reader never sees half a file
        if self._disk_bytes is not None:
            try:
                self._disk_bytes += os.path.getsize(fn) - old_size
            except OSError:
                pass
            if self._disk_bytes > self.max_disk_bytes:
                self._enforce_disk_bound(keep=fn)

    def _enforce_disk_bound(self, keep: Optional[str] = None) -> int:
        """Prune oldest-mtime standing-store files until total disk
        residency fits ``max_disk_bytes`` (ISSUE 15 satellite). Runs
        only when the cached byte total trips the bound; the full
        directory scan here re-syncs that cache (the listing is needed
        anyway to pick the LRU victims). The just-written file
        (``keep``) never prunes — the bound must not eat the entry
        whose write triggered it. Best-effort: a file raced away by
        another engine sharing the directory just skips."""
        if self.max_disk_bytes is None or self.path is None:
            return 0
        try:
            files = []
            total = 0
            for fn in os.listdir(self.path):
                if not fn.endswith(".npz"):
                    continue
                full = os.path.join(self.path, fn)
                try:
                    st = os.stat(full)
                except OSError:
                    continue
                files.append((st.st_mtime, st.st_size, full))
                total += st.st_size
            pruned = 0
            for _mtime, size, full in sorted(files):
                if total <= self.max_disk_bytes:
                    break
                if full == keep:
                    continue
                try:
                    os.unlink(full)
                except OSError:
                    continue
                total -= size
                pruned += 1
                self.disk_pruned_total += 1
                self.disk_pruned_bytes_total += size
            self._disk_bytes = total
            if pruned:
                _obs.serving_host_disk_pruned(
                    pruned, self.disk_pruned_bytes_total)
            return pruned
        except OSError:
            return 0

    def _quarantine_disk(self, fn: str):
        """Remove a corrupt/torn standing-store file so it is NEVER
        re-read (counted; removal failure still counts — the in-RAM
        miss already protects this process, the unlink protects the
        next one)."""
        self.quarantined_total += 1
        _obs.serving_integrity("disk_store", "quarantined")
        self._unlink_tracked(fn)

    def _unlink_tracked(self, fn: str) -> None:
        """Unlink a standing-store file, keeping the cached disk-byte
        total honest (best-effort on both syscalls)."""
        size = 0
        if self._disk_bytes is not None:
            try:
                size = os.path.getsize(fn)
            except OSError:
                pass
        try:
            os.unlink(fn)
        except OSError:
            return
        if self._disk_bytes is not None:
            self._disk_bytes = max(0, self._disk_bytes - size)

    def _read_disk(self, key: bytes) -> Optional[Dict]:
        fn = os.path.join(self.path, _key_name(key))
        if not os.path.exists(fn):
            return None
        try:
            with np.load(fn) as data:
                meta = json.loads(bytes(data["meta"]).decode())
                entry = {"arrays": {n[2:]: np.asarray(data[n])
                                    for n in data.files
                                    if n.startswith("a_")},
                         "meta": meta["meta"], "pages": meta["pages"],
                         "extra": meta["extra"], "persist": True,
                         "checksums": meta.get("checksums")}
        except Exception:
            # torn/truncated/foreign file: a detected corruption — the
            # file quarantines (never re-read) and the caller serves a
            # plain miss (prefix MISS -> replay), not a crash
            _obs.serving_integrity("disk_store", "detected")
            self._quarantine_disk(fn)
            return None
        try:
            # bit-flips np.load cannot see: verify the stamped CRCs
            # BEFORE the entry enters RAM or any scatter (ISSUE 13)
            verify_checksums(entry["arrays"], entry.get("checksums"),
                             "disk_store")
        except CorruptionDetected:
            _obs.serving_integrity("disk_store", "detected")
            self._quarantine_disk(fn)
            return None
        entry["bytes"] = sum(int(v.nbytes)
                             for v in entry["arrays"].values())
        try:
            # bump mtime on promotion so the disk bound's oldest-mtime
            # pruning is genuinely LRU (last write OR promotion), not
            # FIFO by original write time — without this the hottest
            # standing entries would prune first
            os.utime(fn, None)
        except OSError:
            pass
        return entry

    def get(self, key, touch: bool = True) -> Optional[Dict]:
        """RAM lookup, falling through to the standing disk tier for
        bytes keys; a disk hit re-enters RAM (promote within the host
        hierarchy). ``touch`` bumps LRU recency."""
        entry = self._entries.get(key)
        if entry is None and self.path is not None \
                and isinstance(key, bytes):
            entry = self._read_disk(key)
            if entry is not None:
                self._entries[key] = entry
                self._account(entry, +1)
                self._enforce_capacity()
                self._publish()
        if entry is None:
            if touch:
                self.misses_total += 1
            return None
        if touch:
            self.hits_total += 1
            self._entries.move_to_end(key)
        return entry

    def pop(self, key) -> Optional[Dict]:
        entry = self._entries.pop(key, None)
        if entry is not None:
            self._account(entry, -1)
            self._publish()
        return entry

    def quarantine(self, key, site: str) -> None:
        """Remove a corrupt entry EVERYWHERE it could be re-served
        (RAM and, for persisted bytes keys, the standing disk file) and
        count it (ISSUE 13). A quarantined entry is gone for good: the
        next lookup is an honest miss, and its request recovers through
        the gated replay path."""
        self.pop(key)
        self.quarantined_total += 1
        _obs.serving_integrity(site, "quarantined")
        if self.path is not None and isinstance(key, bytes):
            self._unlink_tracked(
                os.path.join(self.path, _key_name(key)))

    def stats(self) -> Dict:
        return {"entries": len(self._entries),
                "pages_resident": self.pages_resident,
                "bytes_resident": self.bytes_resident,
                "capacity_pages": self.capacity_pages,
                "puts_total": self.puts_total,
                "hits_total": self.hits_total,
                "misses_total": self.misses_total,
                "capacity_drops_total": self.capacity_drops_total,
                "quarantined_total": self.quarantined_total,
                "disk_pruned_total": self.disk_pruned_total,
                "disk_pruned_bytes_total": self.disk_pruned_bytes_total}


class TieredKVCache(PagedKVCache):
    """A :class:`~paddle_tpu.serving.PagedKVCache` with the host tier
    under its allocator (ISSUE 10): preemption victims SWAP OUT to a
    :class:`HostPageStore` and resume by swap-in scatter instead of
    replay-prefill; prefix-trie chains evicted under pool pressure
    DEMOTE to host and PROMOTE back on the next matching admission; and
    registered prompt chains write through to a standing store
    (``prefix_store_dir``) that survives engine restarts.

    Every host entry travels as raw bytes + dtype/shape meta (the PR 9
    handoff convention) and re-enters the pool through the SHARED
    donated ``_pool_scatter`` program — so swap-in and promotion are
    bit-identical to never having left HBM, at fp and int8-KV and on
    tp-sharded pools (gated in tests/test_host_tier.py).

    ``store`` shares one :class:`HostPageStore` across caches (the
    PR 9 cluster attaches one store to every replica, so rehomed
    sessions swap in on their NEW replica and a replacement replica
    warms from the standing prefix tier). All host bookkeeping here is
    host-side numpy; the only device programs are the one gather and
    the shared scatter."""

    def __init__(self, cfg, max_batch: int, max_len: int, *,
                 host_capacity_pages: Optional[int] = None,
                 prefix_store_dir: Optional[str] = None,
                 persist_prefix: bool = True,
                 store: Optional[HostPageStore] = None,
                 swap_in_retries: int = 2,
                 retry_sleep=time.sleep, **kw):
        super().__init__(cfg, max_batch, max_len, **kw)
        self.host = store if store is not None else HostPageStore(
            self.page_size, capacity_pages=host_capacity_pages,
            path=prefix_store_dir)
        self.persist_prefix = persist_prefix
        self._gather_fn = None
        # bounded idempotent retry of the swap-in scatter (ISSUE 13):
        # a transient fault retries in place with exponential backoff
        # instead of costing a full engine recovery — every failed
        # attempt frees what it allocated first, and the fault site
        # fires before any commit, so retries never double-install
        self.swap_in_retries = int(swap_in_retries)
        self._retry_sleep = retry_sleep
        self.swap_outs_total = 0
        self.swap_ins_total = 0
        self.swap_out_bytes_total = 0
        self.swap_in_bytes_total = 0
        self.swap_in_pages_total = 0
        self.swap_replay_fallbacks = 0
        self.swap_in_retries_total = 0
        #: why the LAST swap_in fell back to replay-prefill
        #: ("dropped" | "stale" | "corrupt"; None after a success) —
        #: the predictor's trace mark reads this for the request trace
        self.last_swap_fallback: Optional[str] = None
        self.corruptions_detected_total = 0
        self.demotions_total = 0
        self.promote_hits_total = 0
        self._swap_charge = 0   # pending planner debit, tokens
        # async swap-outs issued but not yet fenced into the host
        # store (ISSUE 12): key -> {arrays (device), length, pages,
        # t0}. The gather program was enqueued and its device→host
        # copies started non-blocking; fence_swaps() materializes the
        # entries. Everything that READS the store (has_swapped /
        # swap_in / drop_swapped) fences first, so a pending payload
        # is never invisible.
        self._pending_swaps: "OrderedDict" = OrderedDict()
        #: last swap-in wall latencies (ms), host-side — the bench
        #: rider's swap_in_ms_p50 source (bounded; metrics registry
        #: keeps the full histogram)
        self.swap_in_ms: List[float] = []

    # ---- shared device programs ----
    def _gather_device(self, ids) -> Dict:
        """Launch the jitted gather (:func:`_pool_gather`) for the
        pages at ``ids`` and return the DEVICE arrays without fetching
        — the async swap-out path starts their device→host copies
        non-blocking and fences later. PJRT usage holds keep the read
        ordered before any later donation of the same pool buffers, so
        freeing the pages (host bookkeeping) immediately after is
        safe."""
        import jax
        import jax.numpy as jnp
        if self._gather_fn is None:
            self._gather_fn = jax.jit(_pool_gather)
        return self._gather_fn(self.pool,
                               jnp.asarray(np.asarray(ids, np.int32)))

    def _gather_pages(self, ids) -> Dict[str, np.ndarray]:
        """Fetch the pages at ``ids`` from every pool array to host as
        typed numpy — one jitted gather (:func:`_pool_gather`) + one
        device→host transfer, shared across all swap/demote paths and
        carried across supervisor rebuilds like the scatter/CoW
        programs."""
        return {n: np.asarray(a)
                for n, a in self._gather_device(ids).items()}

    def _decode_validated(self, entry: Dict, k: Optional[int] = None,
                          site: str = "host_payload") -> Dict:
        """Decode a host payload and validate it against THIS pool's
        geometry (array set, dtypes, layer/page shape) — a stale
        standing store from a different config must read as a loud
        error on the swap path and a silent miss on the prefix path,
        never a corrupt scatter. The payload's stamped CRCs verify
        FIRST (ISSUE 13): corrupt bytes raise
        :class:`~paddle_tpu.serving.CorruptionDetected` before any
        decode — the callers quarantine and fall back to replay."""
        verify_checksums(entry["arrays"], entry.get("checksums"), site)
        if set(entry["meta"]) != set(self.pool):
            raise ValueError(
                f"host payload arrays {sorted(entry['meta'])} != pool "
                f"arrays {sorted(self.pool)} — kv-dtype tier mismatch")
        arrays = self.decode_entry(entry)
        for name, a in arrays.items():
            want = self.pool[name]
            if str(a.dtype) != str(want.dtype):
                raise ValueError(
                    f"host payload {name} dtype {a.dtype} != pool "
                    f"dtype {want.dtype}")
            got = tuple(a.shape)
            kk = got[1] if k is None else k
            if (got[0] != want.shape[0] or got[1] != kk
                    or got[2:] != tuple(want.shape[2:])):
                raise ValueError(
                    f"host payload {name} shape {got} does not match "
                    f"pool page shape "
                    f"{(want.shape[0], kk) + tuple(want.shape[2:])}")
        return arrays

    @staticmethod
    def decode_entry(entry: Dict) -> Dict[str, np.ndarray]:
        return HostPageStore.decode(entry)

    # ---- swap-out / swap-in (preemption tier) ----
    @staticmethod
    def _swap_key(rid: int):
        return ("swap", int(rid))

    def swap_out(self, slot: int, rid: int,
                 nonblocking: bool = False) -> int:
        """Preemption SWAP-OUT: gather ``slot``'s live pages (the ones
        covering ``lengths[slot]`` committed tokens — the tail
        reservation holds no KV) to the host store keyed by ``rid``,
        then release the device pages exactly as
        :meth:`~paddle_tpu.serving.PagedKVCache.evict_for_preempt`
        would. Returns pages actually freed. The fault site fires
        BEFORE the gather, so an injected fault commits nothing and
        the supervisor's recovery sees an ordinary running slot.

        ``nonblocking=True`` (the overlapped runtime, ISSUE 12): the
        gather is enqueued and its device→host copies START here, but
        the host-store entry materializes at the next
        :meth:`fence_swaps` — issued under the in-flight decode step,
        fenced at commit, so the DMA never sits on the critical path.
        Every store read (has_swapped / swap_in) fences first, so the
        payload is observable the moment anyone asks."""
        if not self.active[slot]:
            raise ValueError(f"swap_out of inactive slot {slot}")
        length = int(self.lengths[slot])
        if length <= 0:
            raise ValueError(
                f"swap_out of slot {slot} with no committed tokens — "
                f"mid-prefill victims evict and replay instead")
        fault_point("swap_out")
        t0 = time.perf_counter_ns()
        k = self.pages_for(length)
        ids = self._slot_pages[slot][:k]
        if nonblocking:
            out = self._gather_device(ids)
            for a in out.values():
                start = getattr(a, "copy_to_host_async", None)
                if start is not None:
                    start()             # non-blocking device→host DMA
            self._pending_swaps[self._swap_key(rid)] = {
                "arrays": out, "length": length, "pages": k, "t0": t0}
            return self.evict_for_preempt(slot)
        arrays = self._gather_pages(ids)
        entry = self.host.put(self._swap_key(rid), arrays,
                              extra={"length": length})
        freed = self.evict_for_preempt(slot)
        self.swap_outs_total += 1
        self.swap_out_bytes_total += entry["bytes"]
        _obs.serving_swap_out(t0, entry["bytes"], k)
        return freed

    def fence_swaps(self) -> int:
        """Materialize every pending async swap-out into the host
        store (the commit-time fence of the overlapped runtime).
        Returns the number fenced; 0 when nothing was pending. The
        ``serving_swap_out`` latency histogram spans issue→fence —
        the honest wall cost of the overlapped DMA."""
        if not self._pending_swaps:
            return 0
        n = 0
        pend, self._pending_swaps = self._pending_swaps, OrderedDict()
        for key, ent in pend.items():
            arrays = {nm: np.asarray(a)
                      for nm, a in ent["arrays"].items()}
            entry = self.host.put(key, arrays,
                                  extra={"length": ent["length"]})
            self.swap_outs_total += 1
            self.swap_out_bytes_total += entry["bytes"]
            _obs.serving_swap_out(ent["t0"], entry["bytes"],
                                  ent["pages"])
            n += 1
        return n

    def has_swapped(self, rid: int) -> bool:
        key = self._swap_key(rid)
        return key in self._pending_swaps or self.host.contains(key)

    def drop_swapped(self, rid: int) -> None:
        """Retire a swapped payload (its request finished or was
        cancelled while evicted) — always safe, never required: a
        missing payload just means the resume replays."""
        self._pending_swaps.pop(self._swap_key(rid), None)
        self.host.pop(self._swap_key(rid))

    def _quarantine_swap_in(self, rid: int) -> None:
        """Corrupt swap payload: quarantine (counted, never re-served)
        and count the fall-back to the gated replay resume — the
        journal holds everything needed to recompute the KV bit-exactly.
        ``fence_swaps`` already drained any pending async copy of this
        payload into the store, so quarantining the store entry is the
        whole cleanup."""
        self.corruptions_detected_total += 1
        _obs.serving_integrity("swap_in", "detected")
        self.host.quarantine(self._swap_key(rid), "swap_in")
        self.swap_replay_fallbacks += 1
        _obs.serving_swap_fallback()
        _obs.serving_integrity("swap_in", "replayed")

    def swap_in(self, slot: int, rid: int, total_tokens: int,
                expect_tokens: int) -> Optional[int]:
        """Preemption SWAP-IN: re-admit ``rid`` on ``slot`` by
        allocating its full ``total_tokens`` page budget and scattering
        the swapped payload's bytes into the leading pages (the shared
        donated ``_pool_scatter``). Returns the restored committed
        length, or None when no valid payload exists (LRU-dropped, or
        ``expect_tokens`` — the journal-authoritative resume length —
        no longer matches) and the caller must fall back to the
        replay-prefill resume. Raises
        :class:`~paddle_tpu.serving.PoolExhausted` with NOTHING
        committed (the payload survives for the retry)."""
        self.fence_swaps()      # a pending async payload must be visible
        entry = self.host.get(self._swap_key(rid))
        if entry is None:
            self.last_swap_fallback = "dropped"
            self.swap_replay_fallbacks += 1
            _obs.serving_swap_fallback()
            return None
        length = int(entry["extra"]["length"])
        if length != int(expect_tokens):
            # the journal rolled the request past/behind this payload
            # (shouldn't happen — tokens only append — but the journal
            # is authoritative): drop and replay rather than trust it
            self.last_swap_fallback = "stale"
            self.drop_swapped(rid)
            self.swap_replay_fallbacks += 1
            _obs.serving_swap_fallback()
            return None
        if tamper_point("swap_in"):
            # injected payload corruption: real bytes flip, the CRC
            # verifier below must catch them (never the injector)
            entry = _tampered_entry(entry)
        t0 = time.perf_counter_ns()
        n = self._check_admit(slot, total_tokens)
        k = self.pages_for(length)
        try:
            arrays = self._decode_validated(entry, k=k, site="swap_in")
        except CorruptionDetected:
            self.last_swap_fallback = "corrupt"
            self._quarantine_swap_in(rid)
            return None
        # bounded idempotent retry (ISSUE 13): a transient fault at the
        # site — or inside the alloc/scatter — retries in place with
        # exponential backoff instead of poisoning the whole engine.
        # Each failed attempt frees everything it allocated before
        # re-raising (and the fault site fires before any allocation),
        # so a retried swap-in can never double-install pages.
        # PoolExhausted stays back-pressure (the caller's contract);
        # an injected corrupt-mode fault is a detection (quarantine +
        # replay, same as real corrupt bytes above).
        attempt = 0
        while True:
            try:
                fault_point("swap_in")
                pages = self._alloc_with_evict(n)
                try:
                    self._scatter_pages(arrays, pages[:k])
                except Exception:
                    self.allocator.free(pages)
                    raise
                break
            except PoolExhausted:
                raise
            except CorruptionDetected:
                self.last_swap_fallback = "corrupt"
                self._quarantine_swap_in(rid)
                return None
            except Exception:
                attempt += 1
                if attempt > self.swap_in_retries:
                    raise
                self.swap_in_retries_total += 1
                _obs.serving_integrity_retry("swap_in")
                self._retry_sleep(min(0.2, 0.005 * 2 ** (attempt - 1)))
        self._install(slot, pages)
        self.lengths[slot] = length
        self.last_swap_fallback = None
        self.host.pop(self._swap_key(rid))
        self.swap_ins_total += 1
        self.swap_in_pages_total += k
        self.swap_in_bytes_total += entry["bytes"]
        self._swap_charge += k * self.page_size
        self.swap_in_ms.append((time.perf_counter_ns() - t0) / 1e6)
        del self.swap_in_ms[:-1024]
        _obs.serving_swap_in(t0, entry["bytes"], k)
        return length

    def consume_swap_charge(self) -> int:
        """Token-equivalent debit of the swap-ins since the last call —
        ``page_size`` tokens per swapped-in page, the same rate a
        prefill chunk is charged (a swap-in writes the same KV bytes a
        chunk would, minus the FLOPs). The scheduler reserves this out
        of the step's token budget so the budget stays a hard bound on
        per-step HBM writes even when admissions swap in."""
        c = self._swap_charge
        self._swap_charge = 0
        return c

    # ---- prefix demote / promote / standing store ----
    def _chain_key(self, prompt: np.ndarray, n_pages: int) -> bytes:
        return np.ascontiguousarray(
            prompt[:n_pages * self.page_size]).tobytes()

    def register_prefix(self, slot: int, prompt):
        """Publish the prompt's pages to the trie (parent behavior)
        AND write each full page through to the standing host store —
        chains survive trie eviction (demote becomes a no-op re-keying)
        and engine restarts (the persistence half of ROADMAP item 4)."""
        super().register_prefix(slot, prompt)
        if self.prefix is None or not self.persist_prefix:
            return
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0 or not self.active[slot]:
            return
        pg = self.page_size
        nfull = prompt.size // pg
        missing = [j for j in range(nfull)
                   if not self.host.contains(
                       self._chain_key(prompt, j + 1))]
        if not missing:
            return
        pages = self._slot_pages[slot]
        gathered = self._gather_pages([pages[j] for j in missing])
        for i, j in enumerate(missing):
            self.host.put(
                self._chain_key(prompt, j + 1),
                {n: a[:, i:i + 1] for n, a in gathered.items()},
                extra={"tokens":
                       prompt[:(j + 1) * pg].tolist()},
                persist=True)

    def _evict_prefix(self, need: int) -> int:
        """Trie eviction under pool pressure, with DEMOTION: each full
        page dropped from the trie lands in the host store first (keyed
        by its chain prefix) unless already written through — so
        ``PoolExhausted`` moves cold prefix KV down the hierarchy
        instead of destroying it. Partial-page tails do not demote
        (their rows are donor state for copy-on-write, recomputed
        cheaply on the next miss)."""
        pend: List = []

        def demote(chain_tokens: np.ndarray, page: int):
            key = chain_tokens.tobytes()
            if not self.host.contains(key):
                pend.append((key, chain_tokens, page))
            self.demotions_total += 1
            _obs.serving_prefix_demoted(1)
        freed = self.prefix.evict(self.allocator, need, on_evict=demote)
        if pend:
            # ONE batched gather for the whole eviction batch (not one
            # dispatch per page on the PoolExhausted admission path).
            # Deferring past the free is safe: freeing is host
            # bookkeeping — the caller's re-allocation writes nothing
            # into these pages until after this returns.
            gathered = self._gather_pages([p for _, _, p in pend])
            for i, (key, toks, _page) in enumerate(pend):
                self.host.put(key,
                              {n: a[:, i:i + 1]
                               for n, a in gathered.items()},
                              extra={"tokens": toks.tolist()},
                              persist=self.persist_prefix)
        return freed

    def admit_prompt(self, slot: int, prompt, total_tokens: int):
        """Parent admission, preceded by PROMOTION: host-store chains
        extending past the device trie's matched span scatter back into
        freshly allocated pages and re-register, so the parent's trie
        match then covers them — a demoted (or persisted-from-a-past-
        process) system prompt is a prefix HIT, not a re-prefill."""
        if self.prefix is not None:
            self._promote_prefix(
                np.asarray(prompt, np.int32).reshape(-1))
        return super().admit_prompt(slot, prompt, total_tokens)

    def _promote_prefix(self, prompt: np.ndarray) -> int:
        pg = self.page_size
        max_full = max(0, (prompt.size - 1) // pg)
        if max_full == 0:
            return 0
        matched, _ = self.prefix.match(prompt)
        entries = []
        j = len(matched)
        while j < max_full:
            entry = self.host.get(self._chain_key(prompt, j + 1))
            if entry is None:
                break
            entries.append(entry)
            j += 1
        if not entries:
            return 0
        t0 = time.perf_counter_ns()
        try:
            arrays = [self._decode_validated(e, k=1,
                                             site="prefix_promote")
                      for e in entries]
        except CorruptionDetected:
            # corrupt demoted/persisted chain (bit-flip, torn write):
            # quarantine every entry of the chain (counted, never
            # re-served — RAM and disk) and serve the admission as a
            # plain prefix MISS; the replay prefill recomputes the KV
            self.corruptions_detected_total += 1
            _obs.serving_integrity("prefix_promote", "detected")
            for jj in range(len(matched), len(matched) + len(entries)):
                self.host.quarantine(self._chain_key(prompt, jj + 1),
                                     "prefix_promote")
            _obs.serving_integrity("prefix_promote", "replayed")
            return 0
        except ValueError:
            # stale store (different geometry/kv tier): drop the bad
            # chain and serve the admission as a plain miss
            for jj in range(len(matched), len(matched) + len(entries)):
                self.host.pop(self._chain_key(prompt, jj + 1))
            return 0
        # pin the matched span FIRST (the same guard admit_prompt
        # carries): the eviction our own allocation may trigger must
        # not recycle a matched page mid-promotion — re-registering
        # the extended chain onto a recycled id would alias two chain
        # nodes onto one physical page (silent prefix corruption)
        matched = list(matched)
        self.allocator.share(matched)
        try:
            fresh = self._alloc_with_evict(len(entries))
        except PoolExhausted:
            self.allocator.free(matched)
            return 0            # no room to promote: plain miss, no harm
        try:
            merged = {n: np.concatenate([a[n] for a in arrays], axis=1)
                      for n in arrays[0]}
            self._scatter_pages(merged, fresh)
            span = len(matched) + len(entries)
            self.prefix.register(prompt[:span * pg], matched + fresh,
                                 self.allocator)
        except Exception:
            self.allocator.free(matched + fresh)
            raise
        # the trie owns the pages now; drop the pins + bootstrap refs
        self.allocator.free(matched + fresh)
        self.promote_hits_total += len(entries)
        _obs.serving_prefix_promoted(t0, len(entries))
        return len(entries)

    # ---- supervisor / cluster integration ----
    def adopt_host_tier(self, old: "TieredKVCache") -> None:
        """Carry the host tier across an engine rebuild
        (:meth:`~paddle_tpu.serving.EngineSupervisor._build`): the
        store is HOST state committed only after successful gathers —
        it survives a poisoned device pool, which is exactly what lets
        recovery swap sessions in instead of replaying them. Lifetime
        counters and the compiled gather carry too (monotonic stats,
        pure function). Pending ASYNC swap-outs (ISSUE 12) fence into
        the store first — their gathers committed on device before the
        fault — and a fence that itself fails just drops the payloads:
        those resumes fall back to the gated replay path."""
        try:
            old.fence_swaps()
        except Exception:
            old._pending_swaps.clear()
        self.host = old.host
        self._gather_fn = old._gather_fn
        self.persist_prefix = old.persist_prefix
        for name in ("swap_outs_total", "swap_ins_total",
                     "swap_out_bytes_total", "swap_in_bytes_total",
                     "swap_in_pages_total", "swap_replay_fallbacks",
                     "swap_in_retries_total",
                     "corruptions_detected_total",
                     "demotions_total", "promote_hits_total"):
            setattr(self, name, getattr(old, name))
        self.swap_in_ms = old.swap_in_ms
        self.swap_in_retries = old.swap_in_retries
        self._retry_sleep = old._retry_sleep

    def tier_stats(self) -> Dict:
        s = {"swap_outs_total": self.swap_outs_total,
             "swap_outs_pending": len(self._pending_swaps),
             "swap_ins_total": self.swap_ins_total,
             "swap_out_bytes_total": self.swap_out_bytes_total,
             "swap_in_bytes_total": self.swap_in_bytes_total,
             "swap_replay_fallbacks": self.swap_replay_fallbacks,
             "swap_in_retries_total": self.swap_in_retries_total,
             "corruptions_detected_total":
                 self.corruptions_detected_total,
             "prefix_demotions_total": self.demotions_total,
             "prefix_promote_hits_total": self.promote_hits_total}
        s.update({f"host_{k}": v for k, v in self.host.stats().items()})
        return s
