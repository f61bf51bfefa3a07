"""Scratch (PR 26): one run of a cell as ``chipbench.run`` makes it, with two
looks added that change nothing inside the window.

1. ``ServingScheduler.stats()`` is called by the driver at the window's start
   and after it; both snapshots are kept, and the program's span totals
   between them are printed as milliseconds a scheduler step (traced or not).
2. With ``--trace 1`` the profiler's ``.xplane.pb`` is opened with
   ``jax.profiler.ProfileData`` before the harness reduces and deletes it:
   the programs on the ``XLA Modules`` line, the tail of each Pallas event's
   name (``kernel_metadata``), the ``paddle_tpu.`` spans on the host's lines
   beside the harness's ``chipbench.`` spans, the device's idle gaps charged
   to the program's spans, and how many events the Python tracer wrote.

    python3 chipbench/tools/calls/pr26_look.py --workload <cell> --seed <n> \
        --seconds 45 --trace <0|1>

With ``LOOK_STEPS=1`` in the environment every scheduler step is timed from
outside as well (two clock reads a step), and the longest are printed: this
also runs on the parent commit (from its directory, by this file's path), for
which the spans' part prints nothing.

Everything goes to standard error as lines that start with ``look:``; the
result line stays the last line of standard output.
"""
import os
import re
import statistics
import sys
import time

sys.path.insert(0, os.getcwd())

from chipbench import run, trace_reduce                    # noqa: E402
try:
    from chipbench.readers import program                  # noqa: E402
except ImportError:         # the parent commit's chipbench has no such reader
    program = None

PHASES = ("sched.admit", "sched.plan", "engine.dispatch", "engine.wait",
          "engine.commit")


def say(*a):
    print("look:", *a, file=sys.stderr, flush=True)


def phases_a_step(opened, closed):
    if program is None:
        return
    record = {"stats_open": opened, "stats_close": closed}

    def grown(name, field):
        return program._span_growth(record, name, field)
    steps = grown("sched.step", "count")
    if steps is None:
        say("the program has no span totals")
        return
    say(f"scheduler steps between the snapshots: {steps}")
    if not steps:
        return
    step_ms = grown("sched.step", "ns") / 1e6 / steps
    parts = {n: grown(n, "ns") / 1e6 / steps for n in PHASES
             if grown(n, "ns") is not None}
    for n, ms in parts.items():
        say(f"  {n}: {ms:.4f} ms a step, {grown(n, 'count') / steps:.3f} a step")
    say(f"  sched.step: {step_ms:.4f} ms a step; self "
        f"{step_ms - sum(parts.values()):.4f} ms; host phases (all but "
        f"engine.wait) {step_ms - parts.get('engine.wait', 0.0):.4f} ms")
    say(f"  engine.build_program between the snapshots: "
        f"{grown('engine.build_program', 'count')}")
    for k in ("queue_wait_ns_total", "admissions_total", "prompt_tokens_total",
              "prefix_hit_tokens_total", "shares_total", "allocs_total"):
        say(f"  {k}: {program._counter_growth(record, k)}")


def look_at_trace(pd, chips):
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                by = {}
                for e in line.events:
                    name = re.sub(r"\(\d+\)$", "", e.name)
                    c = by.setdefault(name, [0, 0.0])
                    c[0] += 1
                    c[1] += e.duration_ns / 1e9
                for name, (n, s) in sorted(by.items(), key=lambda kv: -kv[1][1]):
                    say(f"XLA Modules: {name}: {n} events, {s:.4f} s")
            if line.name == trace_reduce.OPS_LINE:
                tails = {}
                for e in line.events:
                    if "tpu_custom_call" in e.name:
                        head = e.name.split(" = ")[0]
                        i = e.name.find("frontend_attributes")
                        tails.setdefault(
                            (head, " ".join(e.name[i:].split()) if i >= 0
                             else "no frontend_attributes"), [0])[0] += 1
                for (head, tail), (n,) in sorted(tails.items()):
                    say(f"Pallas event {head} x{n}: ...{tail[:160]}")
    host = [p for p in pd.planes if p.name == "/host:CPU"]
    spans, others = [], 0
    for p in host:
        for line in p.lines:
            events = [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                       e.name) for e in line.events]
            mine = [x for x in events
                    if x[2].startswith(("paddle_tpu.", "chipbench."))]
            if mine:
                say(f"host line {line.name!r}: {len(mine)} paddle_tpu./chipbench. "
                    f"events of {len(events)} on the line")
                others += len(events) - len(mine)
                spans += mine
    say(f"other events on those lines (the Python tracer's): {others}")
    by = {}
    for s, e, n in spans:
        by.setdefault(n, []).append((e - s) / 1e6)
    for n, ms in sorted(by.items()):
        say(f"span {n}: {len(ms)} events, mean {statistics.fmean(ms):.4f} ms, "
            f"median {statistics.median(ms):.4f} ms")
    outer = sorted(x for x in spans if x[2] == "chipbench.sched_step")
    inner = sorted(x for x in spans if x[2] == "paddle_tpu.sched.step")
    nested = sum(1 for (s, e, _), (s2, e2, _) in zip(outer, inner)
                 if s <= s2 and e2 <= e)
    say(f"paddle_tpu.sched.step inside chipbench.sched_step: {nested} of "
        f"{len(inner)} (harness spans: {len(outer)})")
    # the device's idle gaps by the program's innermost span: the five
    # phases do not overlap one another; what they leave of a step is the
    # step's own
    for ops in trace_reduce.device_ops(pd, chips):
        _, merged = trace_reduce.union_seconds((s, e) for s, e, _ in ops)
        leaves = sorted(x for x in spans
                        if x[2].split(".", 1)[1] in PHASES)
        charged = trace_reduce.charge_gaps(merged, leaves)
        step = trace_reduce.charge_gaps(merged, inner).get(
            "paddle_tpu.sched.step", 0.0)
        total = sum(s1 - e0 for (_, e0), (s1, _) in zip(merged, merged[1:])) / 1e9
        say(f"idle between device operations: {total:.4f} s; inside "
            f"paddle_tpu.sched.step {step:.4f} s")
        for n, sec in sorted(charged.items(), key=lambda kv: -kv[1]):
            say(f"  idle under {n}: {sec:.4f} s")
        say(f"  idle under sched.step's own time: "
            f"{step - sum(v for k, v in charged.items() if k != '_none_'):.4f} s")
        # where in a wait or a dispatch the device stands idle: before its
        # first operation there (the launch has not reached it), or after
        # its last (the result is on its way to the host)
        gaps = [(e0, s1) for (_, e0), (s1, _) in zip(merged, merged[1:])
                if s1 > e0]
        for name in ("paddle_tpu.engine.wait", "paddle_tpu.engine.dispatch"):
            head = tail = 0.0
            for s, e, n in leaves:
                if n != name:
                    continue
                for g0, g1 in gaps:
                    if g0 <= s < g1:
                        head += min(g1, e) - s
                    elif g0 < e <= g1:
                        tail += e - max(g0, s)
            say(f"  of the idle under {name}: {head / 1e9:.4f} s from its "
                f"start to the device's next operation, {tail / 1e9:.4f} s "
                f"from the device's last operation to its end")


def main():
    snapshots, steps = [], []
    from paddle_tpu.serving import ServingScheduler
    stats, step = ServingScheduler.stats, ServingScheduler.step

    def kept(self):
        s = stats(self)
        snapshots.append((time.perf_counter(), s))
        return s

    def timed(self):
        t0 = time.perf_counter()
        more = step(self)
        steps.append((t0, time.perf_counter() - t0))
        return more
    ServingScheduler.stats = kept
    if os.environ.get("LOOK_STEPS"):
        # two clock reads a step inside the window: only where asked for
        ServingScheduler.step = timed
    reduce_file = trace_reduce.reduce_file

    def looked(path, chips):
        import jax
        look_at_trace(jax.profiler.ProfileData.from_file(path), chips)
        return reduce_file(path, chips)
    trace_reduce.reduce_file = looked
    rc = run.main(sys.argv[1:])
    if len(snapshots) >= 2:
        (t_open, opened), (t_close, closed) = snapshots[0], snapshots[-1]
        phases_a_step(opened, closed)
        inside = sorted((ms for t, ms in steps if t_open <= t < t_close),
                        reverse=True)
        if inside:
            say(f"steps between the snapshots: {len(inside)}, mean "
                f"{1e3 * statistics.fmean(inside):.3f} ms, median "
                f"{1e3 * statistics.median(inside):.3f} ms, the longest "
                f"{[round(1e3 * x, 1) for x in inside[:8]]} ms")
    return rc


if __name__ == "__main__":
    sys.exit(main())
