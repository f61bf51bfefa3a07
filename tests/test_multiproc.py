"""Multi-process serving cluster gates (ISSUE 19).

The acceptance gates:

- **Token identity across the process boundary** — a routed
  2-worker-process cluster (1 prefill + 1 decode behind socket RPC)
  produces output TOKEN-IDENTICAL to the in-process
  :class:`~paddle_tpu.serving.ServingCluster` on the same seeded trace,
  INCLUDING a mid-trace ``kill -9`` of the decode worker (fp fast;
  int8-KV slow-marked). The replacement process recovers the dead
  worker's sessions from its WAL directory — zero lost, zero
  duplicated.
- **Fabric warm start** — a fresh replica process serves a system
  prompt another cluster's replica demoted to the shared KV fabric as
  a prefix PROMOTE HIT (tier + client + server counters all asserted),
  token-identically to the cold path.
- **Cross-process trace stitch** — with the PR 16 tracer on, a
  handed-off request's ONE trace carries spans from both worker
  processes (``trace.replicas`` spans the prefill and decode ids).
- **RPC robustness** (unit, no subprocesses): torn frame / bit-flip /
  bad magic / half-closed socket are detected and typed; a request
  timeout surfaces a structured :class:`ReplicaUnreachable` after the
  bounded retry budget — never a hang; a dropped reply retries into
  the server's dedupe cache (the handler executes ONCE); remote typed
  exceptions cross the wire as the real classes without burning
  retries.
- **Fabric integrity** (unit, in-thread server): a CRC-corrupt promote
  quarantines on both sides and reads as an honest miss, so the
  engine falls back to the gated replay path token-identically.

Subprocess hygiene: every spawned tree is closed in ``finally`` —
an orphaned worker holds the test runner's stdout pipe open and
wedges piped CI invocations. The multiproc soak smoke keeps the spawn
count at one tree (3 processes + 1 failover respawn); everything
heavier is slow-marked, and conftest orders this file dead last so a
truncated slow-box run loses the newest gates first.
"""
import os
import socket
import tempfile
import threading
import time

import numpy as np
import pytest

from paddle_tpu.serving import FaultInjector
from paddle_tpu.serving.resilience import CorruptionDetected
from paddle_tpu.serving import rpc as rpc_mod
from paddle_tpu.serving.rpc import (
    MAGIC, ReplicaUnreachable, RpcClient, RpcClosed, RpcCorruptFrame,
    RpcServer, RpcTornFrame, SocketTransport, decode_message,
    encode_message,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# RPC framing: torn / corrupt / half-closed detection


def _pipe_transports():
    a, b = socket.socketpair()
    return SocketTransport(a), SocketTransport(b)


class TestRpcFraming:
    def test_codec_roundtrip_with_blobs(self):
        header = {"id": 7, "kind": "call", "method": "x",
                  "data": {"a": 1, "f": 2.5, "s": "txt",
                           "n": np.int64(9)}}
        blobs = {"k": np.arange(24, dtype=np.uint8).reshape(2, 12),
                 "v": np.linspace(0, 1, 6, dtype=np.float32)}
        frame = encode_message(header, blobs)
        assert frame[:4] == MAGIC
        hdr, out = decode_message(frame[12:])
        assert hdr["id"] == 7 and hdr["data"]["n"] == 9
        assert np.array_equal(out["k"], blobs["k"])
        assert np.array_equal(out["v"], blobs["v"])
        out["k"][0, 0] = 255        # decoded blobs are owned copies

    def test_torn_frame_detected(self):
        tx, rx = _pipe_transports()
        frame = encode_message({"id": 1, "kind": "call"})
        tx.sock.sendall(frame[:len(frame) - 3])     # die mid-write
        tx.close()
        with pytest.raises(RpcTornFrame):
            rx.recv_frame()
        rx.close()

    def test_bitflip_detected_before_decode(self):
        tx, rx = _pipe_transports()
        frame = bytearray(encode_message({"id": 1, "kind": "call",
                                          "data": {"x": 1}}))
        frame[-1] ^= 0x40                           # flip a payload bit
        tx.sock.sendall(bytes(frame))
        with pytest.raises(RpcCorruptFrame):
            rx.recv_frame()
        tx.close()
        rx.close()

    def test_bad_magic_rejected(self):
        tx, rx = _pipe_transports()
        frame = bytearray(encode_message({"id": 1, "kind": "call"}))
        frame[:4] = b"PTWL"         # a WAL segment fed to the socket
        tx.sock.sendall(bytes(frame))
        with pytest.raises(RpcCorruptFrame):
            rx.recv_frame()
        tx.close()
        rx.close()

    def test_half_closed_socket_is_clean_close(self):
        tx, rx = _pipe_transports()
        tx.close()                  # peer gone between frames
        with pytest.raises(RpcClosed):
            rx.recv_frame()
        rx.close()

    def test_oversize_length_rejected(self):
        import struct
        tx, rx = _pipe_transports()
        hdr = struct.pack("<4sII", MAGIC, (1 << 30) + 1, 0)
        tx.sock.sendall(hdr)
        with pytest.raises(RpcCorruptFrame):
            rx.recv_frame()
        tx.close()
        rx.close()


# ---------------------------------------------------------------------------
# RPC client/server: retry, dedupe, typed remote errors, timeouts


class _EchoHandler:
    def __init__(self):
        self.calls = 0

    def rpc_echo(self, data, blobs):
        self.calls += 1
        return dict(data), dict(blobs)

    def rpc_corrupt(self, data, blobs):
        raise CorruptionDetected("wire")


class TestRpcClientServer:
    def _serve(self):
        handler = _EchoHandler()
        server = RpcServer(handler).start()
        client = RpcClient.dial(server.host, server.port,
                                retries=2, backoff_s=0.0,
                                sleep=lambda s: None)
        return handler, server, client

    def test_call_roundtrip_blobs(self):
        handler, server, client = self._serve()
        try:
            blobs = {"pages": np.arange(16, dtype=np.uint8)}
            data, out = client.call("echo", {"x": 3}, blobs)
            assert data == {"x": 3}
            assert np.array_equal(out["pages"], blobs["pages"])
            assert handler.calls == 1
            assert client.retries_total == 0
        finally:
            client.close()
            server.shutdown()

    def test_dropped_reply_retries_into_dedupe_cache(self):
        """An injected post-recv fault drops a DELIVERED reply: the
        retry must replay the server's cached frame, not execute the
        handler twice — the exactly-once contract submit/adopt rides
        on."""
        handler, server, client = self._serve()
        try:
            with FaultInjector(seed=0) as inj:
                inj.arm("rpc_recv", "raise", nth=1)
                data, _ = client.call("echo", {"x": 9})
            assert data == {"x": 9}
            assert handler.calls == 1
            assert client.retries_total == 1
            assert server.deduped_replies == 1
        finally:
            client.close()
            server.shutdown()

    def test_dropped_send_retries_fresh_execution(self):
        handler, server, client = self._serve()
        try:
            with FaultInjector(seed=0) as inj:
                inj.arm("rpc_send", "raise", nth=1)
                data, _ = client.call("echo", {"x": 4})
            assert data == {"x": 4}
            # frame never reached the server: no dedupe, one execution
            assert handler.calls == 1
            assert server.deduped_replies == 0
            assert client.retries_total == 1
        finally:
            client.close()
            server.shutdown()

    def test_remote_typed_error_no_retry(self):
        """Application exceptions are NOT transport failures: the
        envelope re-raises the real class (site preserved) without
        burning a single retry."""
        handler, server, client = self._serve()
        try:
            with pytest.raises(CorruptionDetected) as ei:
                client.call("corrupt")
            assert ei.value.site == "wire"
            assert client.retries_total == 0
        finally:
            client.close()
            server.shutdown()

    def test_unknown_method_is_value_error(self):
        handler, server, client = self._serve()
        try:
            with pytest.raises(ValueError):
                client.call("no_such_method")
        finally:
            client.close()
            server.shutdown()

    def test_timeout_bounded_retry_structured_error(self):
        """A server that accepts but never replies must cost exactly
        (retries + 1) timed-out attempts and surface a structured
        ReplicaUnreachable carrying the replica label — never a
        hang."""
        listener = socket.socket()
        listener.bind(("127.0.0.1", 0))
        listener.listen(4)
        conns = []

        def _blackhole():
            while True:
                try:
                    c, _ = listener.accept()
                except OSError:
                    return
                conns.append(c)     # read nothing, reply nothing

        t = threading.Thread(target=_blackhole, daemon=True)
        t.start()
        host, port = listener.getsockname()[:2]
        client = RpcClient.dial(host, port, label="replica9",
                                retries=2, timeout_s=0.05,
                                backoff_s=0.0, sleep=lambda s: None)
        try:
            t0 = time.monotonic()
            with pytest.raises(ReplicaUnreachable) as ei:
                client.call("step")
            assert ei.value.label == "replica9"
            assert client.timeouts_total == 3    # retries + 1 attempts
            assert time.monotonic() - t0 < 5.0
        finally:
            client.close()
            listener.close()
            for c in conns:
                c.close()


# ---------------------------------------------------------------------------
# fabric integrity: corrupt promote -> quarantine -> honest miss


class TestFabricIntegrity:
    def _fabric(self):
        from paddle_tpu.serving.fabric import FabricClient, FabricServer
        server = FabricServer(page_size=8).start()
        client = FabricClient.dial("127.0.0.1", server.port,
                                   page_size=8, retries=1,
                                   backoff_s=0.0, sleep=lambda s: None)
        return server, client

    def test_put_get_roundtrip(self):
        server, client = self._fabric()
        try:
            arrays = {"k": np.arange(64, dtype=np.uint8).reshape(2, 32)}
            client.put(b"chain/1", arrays, extra={"span": 1},
                       persist=True)
            entry = client.get(b"chain/1")
            assert entry is not None
            # the store's raw-uint8 view convention flattens; the
            # bytes round-trip exactly
            assert entry["arrays"]["k"].tobytes() \
                == arrays["k"].tobytes()
            assert entry["extra"] == {"span": 1}
            assert client.hits_total == 1
            assert server.store.stats()["puts_total"] == 1
        finally:
            client.close()
            server.shutdown()

    def test_corrupt_promote_quarantines_and_misses(self):
        """A tampered promote payload must fail the client-side CRC
        gate BEFORE any install path sees it: quarantined on both
        sides, never re-served, surfaced as an honest miss (the gated
        replay fallback's trigger)."""
        server, client = self._fabric()
        try:
            arrays = {"k": np.arange(32, dtype=np.uint8)}
            client.put(b"chain/x", arrays)
            with FaultInjector(seed=0) as inj:
                inj.arm_tamper("fabric_get", nth=1)
                assert client.get(b"chain/x") is None
            assert client.quarantined_total == 1
            assert client.misses_total == 1
            # quarantined server-side too: the clean copy is gone, a
            # re-fetch is a miss, not a resurrect of suspect bytes
            assert client.get(b"chain/x") is None
            assert server.store.stats()["quarantined_total"] >= 1
        finally:
            client.close()
            server.shutdown()

    def test_corrupt_inbound_put_refused(self):
        """The server's CRC gate on demotes: a payload corrupted
        between client encode and server install raises the typed
        CorruptionDetected back through the envelope and installs
        nothing."""
        from paddle_tpu.serving.fabric import (entry_to_wire,
                                               key_to_wire)
        from paddle_tpu.serving.host_tier import (HostPageStore,
                                                  _tampered_entry)
        server, client = self._fabric()
        try:
            entry = HostPageStore.encode(
                {"k": np.arange(16, dtype=np.uint8)})
            entry["extra"] = {}
            entry["persist"] = False
            data, blobs = entry_to_wire(_tampered_entry(entry))
            data["key"] = key_to_wire(b"chain/bad")
            with pytest.raises(CorruptionDetected):
                client._rpc.call("put", data, blobs)
            assert server.quarantined_inbound == 1
            assert not client.contains(b"chain/bad")
        finally:
            client.close()
            server.shutdown()

    def test_corrupt_promote_falls_back_to_replay_token_identical(self):
        """The end-to-end gate: an engine warming its prefix tier from
        the fabric hits a corrupt chain, quarantines it, and the
        admission falls back to gated replay — producing EXACTLY the
        tokens the clean warm path (and the cold path) produce."""
        from paddle_tpu.serving.fabric import FabricClient
        from paddle_tpu.serving.node import tiny_llama_engine

        rs = np.random.RandomState(11)
        prompt = rs.randint(3, 256, (24,)).astype(np.int32)
        server, seeder = self._fabric()
        try:
            cold = tiny_llama_engine()()
            ref = np.asarray(cold.generate([prompt],
                                           max_new_tokens=6)[0])
            # seed the fabric: this engine demotes the prompt's prefix
            # chains through its write-through host tier
            eng1 = tiny_llama_engine(store=seeder)()
            out1 = np.asarray(eng1.generate([prompt],
                                            max_new_tokens=6)[0])
            assert np.array_equal(out1, ref)
            assert seeder.puts_total > 0

            # a fresh replica promotes the seeded chains: warm HIT
            warm = FabricClient.dial("127.0.0.1", server.port,
                                     page_size=8)
            eng2 = tiny_llama_engine(store=warm)()
            out2 = np.asarray(eng2.generate([prompt],
                                            max_new_tokens=6)[0])
            assert np.array_equal(out2, ref)
            assert warm.hits_total > 0

            # re-seed (the warm engine's promote popped nothing, but a
            # quarantine below will), then corrupt the promote: the
            # CRC gate quarantines and the engine replays instead
            eng1b = tiny_llama_engine(store=seeder)()
            np.asarray(eng1b.generate([prompt], max_new_tokens=6)[0])
            hurt = FabricClient.dial("127.0.0.1", server.port,
                                     page_size=8)
            eng3 = tiny_llama_engine(store=hurt)()
            with FaultInjector(seed=0) as inj:
                inj.arm_tamper("fabric_get", nth=1)
                out3 = np.asarray(eng3.generate([prompt],
                                                max_new_tokens=6)[0])
            assert np.array_equal(out3, ref)    # replay == warm == cold
            assert hurt.quarantined_total >= 1
            warm.close()
            hurt.close()
        finally:
            seeder.close()
            server.shutdown()


# ---------------------------------------------------------------------------
# the process-tree gates


def _seeded_jobs(seed=3, lens=(6, 12, 9, 5, 14, 7), max_new=8):
    rs = np.random.RandomState(seed)
    prompts = [rs.randint(3, 256, (n,)).astype(np.int32) for n in lens]
    return prompts, max_new


def _inprocess_reference(prompts, max_new, **factory_kw):
    from paddle_tpu.serving.cluster import ServingCluster
    from paddle_tpu.serving.node import tiny_llama_engine
    ref = ServingCluster(tiny_llama_engine(**factory_kw), replicas=2,
                         prefill_replicas=1,
                         supervisor_kw=dict(sleep=lambda s: None,
                                            backoff_s=0.0))
    handles = [ref.submit(p, max_new_tokens=max_new) for p in prompts]
    while ref.step():
        pass
    assert all(h.done for h in handles)
    return {h.rid: list(h.tokens) for h in handles}


def _run_identity_with_kill(tmp, prompts, max_new, ref_tokens,
                            **factory_kw):
    """Drive the multi-process cluster over the same trace, SIGKILL
    the decode worker once it owns decoded tokens, and assert the
    failover recovers every stream token-identically."""
    import signal

    from paddle_tpu.observability import tracing
    from paddle_tpu.serving.multiproc import MultiProcessCluster

    tracing.enable()
    mc = None
    try:
        mc = MultiProcessCluster(replicas=2, prefill_replicas=1,
                                 workdir=tmp, trace=True,
                                 factory_kw=factory_kw or None)
        handles = [mc.submit(p, max_new_tokens=max_new)
                   for p in prompts]
        killed = False
        steps = 0
        while mc.step():
            steps += 1
            if not killed and any(
                    len(h.tokens) >= 2 and mc._owner.get(h.rid) == 1
                    for h in handles if not h.done):
                os.kill(mc.nodes[1].proc.pid, signal.SIGKILL)
                killed = True
            assert steps < 400, "multi-process cluster did not drain"

        # zero lost, zero duplicated, token-identical to in-process
        assert killed, "decode worker never owned tokens — kill " \
                       "gate not exercised"
        assert mc.failovers_total >= 1
        assert mc.handoffs_total >= 1
        for h in handles:
            assert h.done and h.finish_reason in ("eos", "max_len")
            assert list(h.tokens) == ref_tokens[h.rid], \
                f"rid {h.rid}: multi-process != in-process"

        # cross-process trace stitch (PR 16): a handed-off request's
        # ONE trace carries spans minted in BOTH worker processes
        stitched = [h for h in handles
                    if h.trace is not None
                    and {0, 1} <= set(h.trace.replicas)]
        assert stitched, "no trace spans both worker processes"
        names = {s.name for s in stitched[0].trace.spans}
        assert "handoff_export" in names
        assert "handoff_import" in names
        return mc
    finally:
        if mc is not None:
            mc.close()
        tracing.disable()


class TestMultiProcessCluster:
    def test_kill9_token_identity_and_trace_stitch(self, tmp_path):
        """HEADLINE: 1 prefill + 1 decode worker process, decode
        SIGKILLed mid-trace; output token-identical to the in-process
        ServingCluster on the same seeded trace, spans stitched across
        the process boundary."""
        prompts, max_new = _seeded_jobs()
        ref = _inprocess_reference(prompts, max_new)
        _run_identity_with_kill(str(tmp_path), prompts, max_new, ref)

    @pytest.mark.slow
    def test_kill9_token_identity_int8_kv(self, tmp_path):
        """The identity gate at int8 KV: quantized cache state crosses
        the wire (export → adopt) and the WAL recovery replays it —
        still bit-identical to the in-process int8 cluster."""
        prompts, max_new = _seeded_jobs(seed=5, lens=(6, 11, 8, 13))
        ref = _inprocess_reference(prompts, max_new,
                                   kv_cache_dtype="int8")
        _run_identity_with_kill(str(tmp_path), prompts, max_new, ref,
                                kv_cache_dtype="int8")

    def test_fabric_warm_start_prefix_hit(self, tmp_path):
        """A fresh replica PROCESS serves another cluster's demoted
        system prompt as a fabric prefix HIT: tier promote counters,
        client hit counters and server hit counters all advance, and
        the warm tokens equal the cold ones."""
        from paddle_tpu.serving.multiproc import (FabricProcess,
                                                  MultiProcessCluster)
        rs = np.random.RandomState(7)
        sysprompt = rs.randint(3, 256, (24,)).astype(np.int32)
        fp = None
        mc1 = mc2 = None
        try:
            fp = FabricProcess(str(tmp_path), page_size=8)
            mc1 = MultiProcessCluster(
                replicas=1, workdir=str(tmp_path / "c1"),
                fabric=fp.endpoint)
            h1 = mc1.submit(sysprompt, max_new_tokens=6)
            mc1.run(max_steps=200)
            ts1 = mc1.tier_stats(0)
            assert ts1["tier"]["prefix_demotions_total"] > 0 or \
                ts1["fabric_client"]["puts_total"] > 0
            mc1.close()
            mc1 = None

            mc2 = MultiProcessCluster(
                replicas=1, workdir=str(tmp_path / "c2"),
                fabric=fp.endpoint)
            h2 = mc2.submit(sysprompt, max_new_tokens=6)
            mc2.run(max_steps=200)
            ts2 = mc2.tier_stats(0)
            # the promote-counter gate: the fresh process HIT the
            # other replica's demoted chains at every level
            assert ts2["tier"]["prefix_promote_hits_total"] > 0
            assert ts2["fabric_client"]["hits_total"] > 0
            assert list(h1.tokens) == list(h2.tokens)
            assert h2.done and h2.finish_reason in ("eos", "max_len")
            mc2.close()
            mc2 = None

            fc = fp.client()
            stats, _ = fc.call("stats")
            fc.close()
            assert stats["puts_total"] > 0
            assert stats["hits_total"] > 0
        finally:
            for c in (mc1, mc2):
                if c is not None:
                    c.close()
            if fp is not None:
                fp.close()

    def test_multiproc_chaos_soak_smoke(self, tmp_path):
        """Tier-1 variant of ``tools/chaos_soak.py --multiproc``: a
        real 2-replica + fabric process tree, decode worker SIGKILLed
        mid-soak, a tampered wire handoff and dropped RPC frames —
        run_multiproc_soak raises SoakError on any lost/duplicated
        request, undetected corruption or unbalanced allocator."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chaos_soak", os.path.join(REPO, "tools", "chaos_soak.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        report = mod.run_multiproc_soak(seed=0, requests=6,
                                        workdir=str(tmp_path))
        assert report["failovers"] >= 1
        assert report["handoff_corruptions"] >= 1
        assert report["fabric"]["puts_total"] >= 1
        assert report["faults_by_site"].get("rpc_send", 0) >= 1
