"""Scratch: the result lines of a file of runs as one table, with the spread
(interquartile over median, statistics.quantiles) of each metric."""
import json
import statistics
import sys


def lines(path):
    for raw in open(path):
        raw = raw.strip()
        if raw.startswith('{"correct"'):
            yield json.loads(raw)
        elif raw.startswith('{"set"'):
            yield json.loads(raw)["line"]


def main():
    for path in sys.argv[1:]:
        rows = list(lines(path))
        print(f"-- {path}: {len(rows)} runs, correct "
              f"{sum(1 for r in rows if r['correct'])}")
        cols = {}
        for r in rows:
            for k, v in r["metrics"].items():
                cols.setdefault(k, []).append(v["value"])
            for k in ("ttft_p90_ms", "backlog_end", "tokens_per_s",
                      "step_ms_p50", "steps_over_1_1x", "checked_tokens",
                      "checked_prefilled_in_window"):
                if k in r:
                    cols.setdefault(k, []).append(r[k])
            for c in r["compared"]:
                cols.setdefault("cmp." + c["name"], []).append(c["value"])
            if "slowest_steps_ms" in r:
                print("   slowest steps", [[j, round(ms, 1)] for j, ms in
                                           r["slowest_steps_ms"]],
                      "steps", r.get("steps"))
        for k, v in cols.items():
            spread = ""
            if len(v) >= 2 and statistics.median(v):
                q = statistics.quantiles(v, n=4)
                spread = f"  spread {100 * (q[2] - q[0]) / statistics.median(v):.2f}%"
            print(f"   {k}: median {statistics.median(v):.6g} "
                  f"[{min(v):.6g} .. {max(v):.6g}]{spread}")
            print("      ", [round(x, 5) for x in v])


if __name__ == "__main__":
    main()
