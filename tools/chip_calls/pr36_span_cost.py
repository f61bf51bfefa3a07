"""PR 36: what a span costs the host, outside and inside a profiler
session, for each ``spans.py`` given (the parent's and the change's), and
what the scheduler's step adds around its spans (the thread's sample at the
step's entry, the two chunk-step counters). Best of five loops; the host is
the machine's, the device plays no part (run with ``JAX_PLATFORMS=cpu``).

    python3 tools/chip_calls/pr36_span_cost.py <spans.py> [<spans.py> ...]
"""
import importlib.util
import shutil
import sys
import tempfile
import time


def load(path):
    spec = importlib.util.spec_from_file_location("spans_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def best_ns(body, n, loops=5):
    best = float("inf")
    for _ in range(loops):
        t0 = time.perf_counter_ns()
        body(n)
        best = min(best, (time.perf_counter_ns() - t0) / n)
    return best


def spans_cost(mod, n):
    t = mod.SpanTotals()

    def with_kind(n):
        for _ in range(n):
            with t.span("engine.dispatch", kind="decode"):
                pass

    def without(n):
        for _ in range(n):
            with t.span("engine.commit", rows=3):
                pass

    def a_step(n):
        # the spans of a pipelined decode step, in their order
        for i in range(n):
            with t.span("sched.step", step=i):
                with t.span("engine.wait", kind="decode"):
                    pass
                with t.span("engine.commit", rows=32):
                    pass
                with t.span("sched.admit", queued=0):
                    pass
                with t.span("sched.plan"):
                    pass
                with t.span("engine.dispatch", kind="decode"):
                    pass
    return (best_ns(with_kind, n), best_ns(without, n),
            best_ns(a_step, n // 6))


def step_extras(mod, n):
    t = mod.SpanTotals()
    if not hasattr(t, "step_begins"):
        return None

    def body(n):
        for i in range(n):
            c0 = t.calls("engine.wait/chunk")
            t.step_begins(i)
            if t.calls("engine.wait/chunk") >= c0:
                t.count("steps_committing_chunk_total", 1)
                t.count("steps_committing_chunk_ns_total", 12345)
            t.step_ends(True)
    return best_ns(body, n)


def main():
    import jax
    for path in sys.argv[1:]:
        mod = load(path)
        out = spans_cost(mod, 100_000)
        d = tempfile.mkdtemp(prefix="pr36_cost_")
        jax.profiler.start_trace(d)
        try:
            inside = spans_cost(mod, 12_000)
        finally:
            jax.profiler.stop_trace()
            shutil.rmtree(d, ignore_errors=True)
        extra = step_extras(mod, 100_000)
        print(f"pr36_span_cost: {path}: ns a span outside a session: with "
              f"kind {out[0]:.0f}, without {out[1]:.0f}, the six spans of a "
              f"decode step {out[2]:.0f} ({out[2] / 6:.0f} a span); inside a "
              f"session: {inside[0]:.0f}, {inside[1]:.0f}, {inside[2]:.0f} "
              f"({inside[2] / 6:.0f} a span); the step's sample and "
              f"counters: " + ("none" if extra is None
                               else f"{extra:.0f} ns a step"), flush=True)


if __name__ == "__main__":
    main()
