"""``pr37_chunk_split.py`` for the K/V chunk: one traced run of a cell as
``chipbench.run`` makes it, the traced window's device time split by
program, the chunk programs' attention apart. The attention is what runs
under the ``kv_chunk_attention`` scope or kernel, and where ``$PR39_LINES``
names source lines (``<file suffix>:<first>-<last>,...``) every instruction
that the optimised HLO (dumped to ``$PR37_DUMP``) traces to one of them: the
gathering path's attention has no scope of its own, so the parent's is
found by the lines of ``_attn_with_cache`` and of the temp cache's gather.

    PR37_SPLIT=<file.json> PR37_DUMP=<dir> PR39_LINES=models/generate.py:1687-1787 \\
        XLA_FLAGS="--xla_dump_to=<dir> --xla_dump_hlo_as_text --xla_dump_hlo_module_re=prefill_chunk" \\
        python3 <this file> --workload <cell> --seed <n> --seconds 45 --trace 1
"""
import glob
import os
import re
import sys

sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import pr37_chunk_split as base  # noqa: E402

base.SCOPE = "kv_chunk_attention"
RANGES = [(spec.rsplit(":", 1)[0], *map(int, spec.rsplit(":", 1)[1].split("-")))
          for spec in os.environ.get("PR39_LINES", "").split(",") if spec]


def scoped(dump):
    """module name -> the instructions its optimised HLO puts under the
    scope or at one of the named source lines."""
    out = {}
    for f in glob.glob(os.path.join(dump, "**", "*after_optimizations.txt"),
                       recursive=True):
        names, module = set(), None
        for line in open(f):
            if module is None and line.startswith("HloModule "):
                module = line.split()[1].rstrip(",")
            m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = ", line)
            if not m:
                continue
            op = re.search(r'op_name="([^"]*)"', line)
            src = re.search(r'source_file="([^"]*)" source_line=(\d+)', line)
            if (op and base.SCOPE in op.group(1).split("/")) or (src and any(
                    src.group(1).endswith(sfx) and lo <= int(src.group(2)) <= hi
                    for sfx, lo, hi in RANGES)):
                names.add(m.group(1))
        if module:
            out.setdefault(module, set()).update(names)
    return out


base.scoped = scoped
if __name__ == "__main__":
    sys.exit(base.run.main(sys.argv[1:]))
