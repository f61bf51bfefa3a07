"""PR 36: one run of a serving cell as ``chipbench.run`` makes it, with the
harness's own list of steps and the scheduler's two ``stats()`` snapshots
kept (nothing is added inside a step: ``Loop.step`` hands over a reference
to its list once), and after the run, on standard error as ``hunt:`` lines:

- the window cut into slices of 45 s (``--seconds 225`` is five windows
  behind one set-up): each slice's tokens a second, its steps by what they
  LAUNCHED (the harness's ``steps by program`` split, for every cell), the
  longest step, the step after it (a step that follows a pause of the host
  alone finds its program finished and is short; one that follows a late
  device is not), and every stall record that lies in the slice, with the
  step of ``Loop.steps`` it lies in;
- between the snapshots, the steps by what they COMMITTED, from the
  program's own counters: the readings of ``chipbench/readers/steps.py``;
- the kernel's own counters for the stepping thread and its control group
  at the two snapshots (``host_counters``), where the machine has them;
- with ``--trace 1``, before the harness reduces and deletes the trace:
  every ``paddle_tpu.`` span of 100 ms or more that the profiler caught,
  with what each line of every plane (the host's threads, the device's
  operations) held under it, so that a stall inside the traced seconds
  shows whether the device worked, and what the runtime's threads did.

It also runs from the parent's directory (by this file's path), which has
no stall records and no chunk-step counters: those lines say so.

    python3 tools/chip_calls/pr36_hunt.py --workload <cell> --seed <n> \
        --seconds <45 x k> --trace <0|1>
"""
import json
import os
import sys

sys.path.insert(0, os.getcwd())

from chipbench import run                                   # noqa: E402
from chipbench.drivers import serve_open_loop               # noqa: E402

SLICE_S = 45.0
LONG_NS = 100e6      # a span in the trace worth a look: the stall threshold


def say(*a):
    print("hunt:", *a, file=sys.stderr, flush=True)


def by_launch(steps):
    """``serve_arch._split_by_program`` without the expert counters."""
    out = {}
    for name, want in (("decode_only", False), ("with_chunk", True)):
        ms = [1e3 * (s["t1"] - s["t0"]) for s in steps
              if bool(s["prefill_width"]) == want and s["rows"]]
        if ms:
            out[name] = {"steps": len(ms), "ms": sum(ms) / len(ms),
                         "ms_longest": max(ms)}
    return out


def by_commit(opened, closed):
    """The steps by what they committed, from the program's own totals,
    and the new readers' values on the same two snapshots."""
    if "steps_committing_chunk_total" not in closed:
        return None
    from chipbench.readers import steps as readers
    record = {"stats_open": opened, "stats_close": closed}
    chunk, chunk_ns, n, ns = readers._chunk_steps(record)
    out = {"steps": n, "committing_chunk": chunk}
    if chunk and n - chunk:
        out.update(ms_with_chunk=chunk_ns / chunk / 1e6,
                   ms_others=(ns - chunk_ns) / (n - chunk) / 1e6)
    for name in ("chunk_step_share", "chunk_step_extra_ms", "stall_ms",
                 "stall_off_cpu_share"):
        out[name] = getattr(readers, name)(record, {})
    out["stalls_total"] = closed["stalls_total"] - opened["stalls_total"]
    out["max_ms_since_start"] = {
        k: round(v["max_ns"] / 1e6, 2) for k, v in closed["spans"].items()}
    return out


def where(steps, rec):
    """The entry of ``Loop.steps`` a stall record lies in, by the clock
    both share (``time.perf_counter``)."""
    t = rec["start_ns"] / 1e9
    for i, s in enumerate(steps):
        if s["t0"] <= t <= s["t1"]:
            return f"inside harness step {i} ({1e3 * (s['t1'] - s['t0']):.1f} ms)"
        if t < s["t0"]:
            return f"between harness steps {i - 1} and {i}"
    return "after the last harness step"


HOST_FILES = ("/proc/thread-self/schedstat", "/proc/thread-self/status",
              "/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat",
              "/sys/fs/cgroup/cpu.max", "/proc/pressure/cpu")


def host_counters():
    """What the kernel keeps of this thread and its control group, read
    outside the window: the run-queue wait of ``schedstat`` (runnable and
    not running) tells a thread kept off its core from one that slept in
    a call, and ``cpu.stat`` counts the periods the group was throttled."""
    out = {}
    for path in HOST_FILES:
        try:
            with open(path) as f:
                text = f.read()
        except OSError:
            continue
        if path.endswith("status"):
            text = " ".join(ln for ln in text.splitlines()
                            if "ctxt_switches" in ln)
        out[path] = " ".join(text.split())
    return out


def look_at_trace(pd):
    """What lay under each long ``paddle_tpu.`` span of the trace."""
    lines = [(p.name, ln.name,
              [(float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
               for e in ln.events]) for p in pd.planes for ln in p.lines]
    long = sorted((s, e, n) for _, _, evs in lines for s, e, n in evs
                  if n.startswith("paddle_tpu.") and e - s >= LONG_NS)
    say(f"trace: {len(long)} paddle_tpu. spans of {LONG_NS / 1e6:.0f} ms "
        f"or more")
    for s, e, n in long:
        if any(s <= s2 and e2 <= e and (s2, e2, n2) != (s, e, n)
               for s2, e2, n2 in long):
            continue                            # the innermost ones only
        say(f"trace: {n} {(e - s) / 1e6:.1f} ms; under it, by line:")
        for plane, line, evs in lines:
            by = {}
            for s2, e2, n2 in evs:
                over = min(e, e2) - max(s, s2)
                if over > 0 and n2 != n:
                    c = by.setdefault(n2.split(" = ")[0][:70], [0, 0.0])
                    c[0] += 1
                    c[1] += over / 1e6
            top = sorted(by.items(), key=lambda kv: -kv[1][1])[:6]
            if top and top[0][1][1] >= 1.0:
                say(f"trace:   {plane} / {line}: " + "; ".join(
                    f"{k} x{c} {ms:.1f} ms" for k, (c, ms) in top))


def main():
    kept, snapshots = [], []
    from paddle_tpu.serving import ServingScheduler
    stats, step = ServingScheduler.stats, serve_open_loop.Loop.step

    def kept_stats(self):
        s = stats(self)
        snapshots.append((serve_open_loop.time.perf_counter(), s,
                          host_counters()))
        return s

    def handing_over(self):
        if not kept:
            kept.append(self.steps)
        return step(self)
    ServingScheduler.stats = kept_stats
    serve_open_loop.Loop.step = handing_over
    from chipbench import trace_reduce
    reduce_file = trace_reduce.reduce_file

    def looked(path, chips):
        import jax
        look_at_trace(jax.profiler.ProfileData.from_file(path))
        return reduce_file(path, chips)
    trace_reduce.reduce_file = looked
    rc = run.main(sys.argv[1:])
    if len(snapshots) < 2 or not kept:
        say("nothing kept")
        return rc
    (t_open, opened, host0), (_, closed, host1) = snapshots[0], snapshots[-1]
    for path in HOST_FILES:
        say(f"host: {path}: " + (f"{host0[path]} -> {host1[path]}"
                                 if path in host0 else "absent"))
    seconds = float(sys.argv[sys.argv.index("--seconds") + 1])
    steps = kept[0]
    stalls = closed.get("stalls")
    say("stall records in the closing snapshot: " +
        ("the program keeps none" if stalls is None else str(len(stalls))))
    for k in range(max(1, round(seconds / SLICE_S))):
        a, b = t_open + k * SLICE_S, t_open + min((k + 1) * SLICE_S, seconds)
        idx = [i for i, s in enumerate(steps) if a <= s["t0"] < b]
        if not idx:
            continue
        mine = [steps[i] for i in idx]
        end = max(mine[-1]["t1"], b) if k == round(seconds / SLICE_S) - 1 else b
        longest = max(idx, key=lambda i: steps[i]["t1"] - steps[i]["t0"])
        after = steps[longest + 1] if longest + 1 < len(steps) else None
        say(f"slice {k} [{a - t_open:.0f}, {b - t_open:.0f}) s: "
            f"{len(mine)} steps, "
            f"{sum(s['tokens'] for s in mine) / (end - a):.1f} tokens/s, "
            f"by launch {json.dumps(by_launch(mine))}; longest step "
            f"{1e3 * (steps[longest]['t1'] - steps[longest]['t0']):.1f} ms "
            f"(harness step {longest}, prefill_width "
            f"{steps[longest]['prefill_width']}), the step after it "
            + (f"{1e3 * (after['t1'] - after['t0']):.1f} ms" if after
               else "none"))
        for rec in stalls or []:
            if a <= rec["start_ns"] / 1e9 < b:
                say(f"  stall in slice {k}: {json.dumps(rec)} "
                    f"{where(steps, rec)}")
    before = [r for r in stalls or [] if r["start_ns"] / 1e9 < t_open]
    say(f"stall records from before the window (set-up): {len(before)}")
    for rec in before:
        say(f"  set-up stall: {json.dumps(rec)}")
    commit = by_commit(opened, closed)
    say("between the snapshots, by what a step committed: " +
        ("the program has no such counters" if commit is None
         else json.dumps(commit)))
    return rc


if __name__ == "__main__":
    sys.exit(main())
