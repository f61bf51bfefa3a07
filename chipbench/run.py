"""``python3 -m chipbench.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell in a new process. It loads, warms the
cell's own shapes, measures, compares with the plain reference and prints
the result as the last line of standard output.

``--rehearsal 1`` is the sandbox's switch: tiny widths from
``chipbench/rehearsal/``, the CPU, kernels interpreted. Its line names the
CPU as its device and is never ``correct``. ``--plant`` breaks the timed
path on purpose (the control and the faults of the comparison's tests).
"""
from __future__ import annotations

import argparse
import importlib
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    ap.add_argument("--plant", default=None,
                    choices=("control", "half_batch", "state_unchanged",
                             "token_altered"))
    a = ap.parse_args(argv)
    from . import harness
    cell = harness.Cell(a.workload, rehearsal=bool(a.rehearsal))
    driver = importlib.import_module(f"chipbench.drivers.{cell.mix['kind']}")
    return driver.run(cell, a.seed, a.seconds, bool(a.trace), plant=a.plant)


if __name__ == "__main__":
    sys.exit(main())
