"""PR 33: one traced run of a cell as ``chipbench.run`` makes it, with the
whole table of device operations (seconds over the traced window, events)
written to ``$PR33_OPS`` before the harness prints its ten largest.

    PR33_OPS=<file.json> python3 <this file> --workload <cell> --seed <n> \
        --seconds 45 --trace 1

Run from the root of the tree to measure; changes nothing inside the
window (the table is made from the reduction the harness makes anyway)."""
import json
import os
import sys

sys.path.insert(0, os.getcwd())

from chipbench import run, trace_reduce  # noqa: E402

_reduce_file = trace_reduce.reduce_file


def reduce_file(path, chips):
    red = _reduce_file(path, chips)
    table = sorted(((trace_reduce.short_name(n), sec, red["counts"][n])
                    for n, sec in red["ops"].items()), key=lambda r: -r[1])
    with open(os.environ["PR33_OPS"], "w") as f:
        json.dump({"busy_s": red["busy_s"], "window_s": red["window_s"],
                   "ops": table[:120]}, f)
    return red


trace_reduce.reduce_file = reduce_file
if __name__ == "__main__":
    sys.exit(run.main(sys.argv[1:]))
