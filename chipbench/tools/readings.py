"""Scratch: several seeds of one cell in one process, to read the numbers
that ``correct`` compares (the program's, the control's, a planted fault's)
or to try another value of a mix's parameter (the rate sweep that finds the
knee), without paying the interpreter's and the chip's start-up for each
seed. Not the measured command: a line made here says what was set.

    python3 -m chipbench.tools.readings --workload W --seeds 1,2,3 \
        --seconds 10 [--plant control] [--trace 1] \
        [--set arrivals.rate_per_s=2.8] [--set group=8]
"""
import argparse
import gc
import importlib
import json
import sys

from chipbench import harness


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--plant", default=None)
    ap.add_argument("--set", action="append", default=[],
                    help="dotted.key=json: another value in the mix or job")
    a = ap.parse_args()
    for seed in a.seeds.split(","):
        cell = harness.Cell(a.workload)
        for item in a.set:
            path, value = item.split("=", 1)
            at = cell.mix
            *parents, leaf = path.split(".")
            for k in parents:
                at = at[k]
            at[leaf] = json.loads(value)
        print(f"== seed {seed} plant {a.plant} set {a.set}", flush=True)
        driver = importlib.import_module(
            f"chipbench.drivers.{cell.mix['kind']}")
        try:
            driver.run(cell, int(seed), a.seconds, bool(a.trace),
                       plant=a.plant)
        except Exception as e:  # a control that crashes has failed; go on
            print(f"== seed {seed} raised {type(e).__name__}: {e}", flush=True)
        gc.collect()


if __name__ == "__main__":
    sys.exit(main())
