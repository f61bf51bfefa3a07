"""``pr37_chunk_split.py`` for the Mamba-1 convolution step: one traced run
of a cell as ``chipbench.run`` makes it, the traced window's device time
split by program (decode and chunk), each program's fifteen longest
operations, and the seconds of the ``mamba1_conv_step`` kernel's custom
calls apart (printed as ``under mamba1_conv_step``). On a tree without the
kernel that reading is 0, and the list shows where the tails' pool is
copied and written instead.

    python3 tools/chip_calls/pr42_trace_split.py --workload <cell> \
        --seed <n> --seconds 45 --trace 1
"""
import os
import sys

sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import pr37_chunk_split as base  # noqa: E402

base.SCOPE = "mamba1_conv_step"
if __name__ == "__main__":
    sys.exit(base.run.main(sys.argv[1:]))
