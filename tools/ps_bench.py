"""PS wire throughput micro-bench (VERDICT r3 weak #8).

Measures pull/push rows/s against a REAL server process over the RPC
wire, across table sizes and batch sizes, for the sync path and the
async/geo communicator tiers, to set against the reference's brpc tier
(paddle/fluid/distributed/ps/service/brpc_ps_client.h).

  python tools/ps_bench.py [--dim 64] [--rows 100000] [--batch 2048]

Also prints the per-call wire overhead via a no-payload RPC, and
oneshot-vs-persistent connection comparison (PADDLE_TPU_RPC_ONESHOT=1
forces the old dial-per-call behavior for the A/B).
"""
from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _server_main(registry, dim, ready, stop):
    os.environ["PADDLE_RPC_REGISTRY"] = registry
    os.environ["JAX_PLATFORMS"] = "cpu"
    from paddle_tpu.distributed.rpc import rpc
    from paddle_tpu.distributed.ps import PsServer, TableConfig
    rpc.init_rpc("server0", rank=0, world_size=1)
    PsServer([TableConfig(name="t", dim=dim, optimizer="sgd", lr=0.1)])
    ready.set()
    stop.wait()
    rpc.shutdown()


def _rate(fn, iters, rows_per_iter):
    fn()                      # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    dt = time.perf_counter() - t0
    return rows_per_iter * iters / dt, dt / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--rows", type=int, default=100_000)
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=30)
    args = ap.parse_args()

    registry = tempfile.mkdtemp(prefix="psbench_")
    os.environ["PADDLE_RPC_REGISTRY"] = registry
    os.environ["JAX_PLATFORMS"] = "cpu"

    ctx = mp.get_context("spawn")
    ready, stop = ctx.Event(), ctx.Event()
    srv = ctx.Process(target=_server_main,
                      args=(registry, args.dim, ready, stop), daemon=True)
    srv.start()
    assert ready.wait(60), "server never came up"

    from paddle_tpu.distributed.rpc import rpc
    from paddle_tpu.distributed.ps import (AsyncCommunicator,
                                           GeoCommunicator, PsClient,
                                           TableConfig)
    rpc.init_rpc("worker0", rank=1, world_size=2)
    rpc.wait_for_workers(["server0"])
    client = PsClient(["server0"])

    rs = np.random.RandomState(0)
    keys = rs.randint(0, args.rows, args.batch).astype(np.int64)
    grads = rs.randn(args.batch, args.dim).astype(np.float32)
    results = {}

    # wire overhead: no-payload round trip
    import paddle_tpu.distributed.fleet.fleet as _fl
    _, rtt = _rate(lambda: rpc.rpc_sync("server0", _fl._srv_done_count),
                   args.iters, 1)
    results["rpc_rtt_us"] = round(rtt * 1e6, 1)

    # sync pull / push
    pull_rps, pull_lat = _rate(
        lambda: client.pull_sparse("t", keys), args.iters, args.batch)
    push_rps, push_lat = _rate(
        lambda: client.push_sparse("t", keys, grads), args.iters,
        args.batch)
    results["sync_pull_rows_per_s"] = round(pull_rps)
    results["sync_push_rows_per_s"] = round(push_rps)
    results["sync_pull_ms"] = round(pull_lat * 1e3, 2)
    results["sync_push_ms"] = round(push_lat * 1e3, 2)

    # async communicator: queued pushes, flush barrier per window
    comm = AsyncCommunicator(client)

    def async_window():
        for _ in range(8):
            comm.push_sparse("t", keys, grads)
        comm.flush()
    a_rps, _ = _rate(async_window, max(args.iters // 8, 2),
                     8 * args.batch)
    comm.stop()
    results["async_push_rows_per_s"] = round(a_rps)

    # geo communicator: local train + delta sync every k steps
    geo = GeoCommunicator(client, trainer_num=1, k_steps=8)
    geo.create_table(TableConfig(name="t", dim=args.dim,
                                 optimizer="sgd", lr=0.1))

    def geo_window():
        for _ in range(8):
            geo.push_sparse("t", keys, grads)
        geo.sync()
    g_rps, _ = _rate(geo_window, max(args.iters // 8, 2),
                     8 * args.batch)
    results["geo_push_rows_per_s"] = round(g_rps)

    results.update(dim=args.dim, batch=args.batch, rows=args.rows,
                   payload_mb_per_batch=round(
                       grads.nbytes / 1e6, 2))
    print(json.dumps({"metric": "ps_wire_bench", **results}))

    stop.set()
    srv.join(timeout=10)
    rpc.shutdown()


if __name__ == "__main__":
    main()
