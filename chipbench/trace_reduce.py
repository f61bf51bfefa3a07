"""From the profiler's ``.xplane.pb`` to numbers: seconds in which an
operation ran on the device (the union of the operations' intervals,
averaged over the chips used), time per named operation, and the idle gaps
between operations charged to the harness's own annotation that covers them.

Read with ``jax.profiler.ProfileData`` alone. Device planes are
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per operation.
Host threads are lines of ``/host:CPU``; the harness's spans are the events
there whose names start with ``chipbench.``. On the CPU (the rehearsal and
the unit test) there is no device plane, and the PjRt CPU client's lines
stand in for it: events that carry an ``hlo_op`` stat.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Sequence, Tuple

SPAN_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


def union_seconds(intervals: Iterable[Interval]) -> Tuple[float, List[Interval]]:
    """Length of the union of [start, end) intervals in ns, as seconds, and
    the merged intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged) / 1e9, [(s, e) for s, e in merged]


def short_name(name: str) -> str:
    """A device operation's event name is its whole HLO text. For the
    breakdown keep the instruction, its kind and its result's shape:
    ``%copy.66 copy bf16[24,801,64,8,128]``."""
    m = re.match(r"(%[\w.\-]+) = (\(?[a-z0-9]+\[[\d,]*\])[^ ]* ?.*?([a-z][\w\-]*)\(",
                 name)
    if not m:
        return name[:80]
    kind = m.group(3)
    if 'custom_call_target="tpu_custom_call"' in name:
        kind = "pallas"
    return f"{m.group(1)} {kind} {m.group(2).lstrip('(')}"


def self_times(ops: Sequence[Tuple[float, float, str]]
               ) -> List[Tuple[float, float, str, float]]:
    """(start, end, name, self ns) of every operation: a ``while`` or a
    ``conditional`` is an event that holds its body's events, and its own
    time is what they leave."""
    out, stack = [], []
    for s, e, n in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][1] <= s:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= min(e, stack[-1][1]) - s
        stack.append([s, e, n, e - s])
    out += [tuple(x) for x in stack]
    return out


def _device_planes(pd, chips: int):
    planes = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    planes.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    return planes[:chips]


def _events(line):
    return [(float(e.start_ns), float(e.start_ns + e.duration_ns), e.name, e)
            for e in line.events]


def device_ops(pd, chips: int) -> List[List[Tuple[float, float, str]]]:
    """Per chip, the (start, end, name) of every operation that ran on it."""
    planes = _device_planes(pd, chips)
    out = []
    if planes:
        for p in planes:
            ops = []
            for line in p.lines:
                if line.name == OPS_LINE:
                    ops += [(s, e, n) for s, e, n, _ in _events(line)]
            out.append(ops)
        return out
    ops = []
    for p in pd.planes:
        if p.name != "/host:CPU":
            continue
        for line in p.lines:
            for s, e, n, ev in _events(line):
                if any(k == "hlo_op" for k, _ in ev.stats):
                    ops.append((s, e, n))
    return [ops]


def host_spans(pd) -> List[Tuple[float, float, str]]:
    spans = []
    for p in pd.planes:
        if p.name != "/host:CPU":
            continue
        for line in p.lines:
            spans += [(s, e, n) for s, e, n, _ in _events(line)
                      if n.startswith(SPAN_PREFIX)]
    return sorted(spans)


def charge_gaps(busy: Sequence[Interval], spans: Sequence[Tuple[float, float, str]]
                ) -> Dict[str, float]:
    """Seconds of idle time between the first and the last operation, by the
    harness span that covers them; what no span covers goes to ``_none_``."""
    out: Dict[str, float] = {}
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        if s1 <= e0:
            continue
        left = s1 - e0
        for s, e, n in spans:
            if e <= e0:
                continue
            if s >= s1:
                break
            c = min(e, s1) - max(s, e0)
            out[n] = out.get(n, 0.0) + c / 1e9
            left -= c
        if left > 0:
            out["_none_"] = out.get("_none_", 0.0) + left / 1e9
    return out


def reduce(pd, chips: int) -> Dict:
    per_chip = device_ops(pd, chips)
    if not any(per_chip):
        raise ValueError("chipbench: the trace holds no device operation")
    busy_s, by_name, counts, gaps = [], {}, {}, {}
    spans = host_spans(pd)
    # the traced window: from the first span or operation to the last
    lo = min([s for ops in per_chip for s, _, _ in ops] + [s for s, _, _ in spans])
    hi = max([e for ops in per_chip for _, e, _ in ops] + [e for _, e, _ in spans])
    for ops in per_chip:
        b, merged = union_seconds((s, e) for s, e, _ in ops)
        busy_s.append(b)
        for _, _, n, own in self_times(ops):
            by_name[n] = by_name.get(n, 0.0) + own / 1e9 / len(per_chip)
            counts[n] = counts.get(n, 0.0) + 1.0 / len(per_chip)
        for n, sec in charge_gaps(merged, spans).items():
            gaps[n] = gaps.get(n, 0.0) + sec / len(per_chip)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])
    return {"busy_s": sum(busy_s) / len(busy_s),
            "window_s": (hi - lo) / 1e9,
            "ops": by_name,
            "counts": counts,
            "per_chip": per_chip,
            "device_ops": [[short_name(n), s] for n, s in top],
            "idle_gaps": [[n, s] for n, s in
                          sorted(gaps.items(), key=lambda kv: -kv[1])],
            "spans": [(n, (e - s) / 1e9) for s, e, n in spans]}


def reduce_file(path: str, chips: int) -> Dict:
    import jax
    return reduce(jax.profiler.ProfileData.from_file(path), chips)


def op_seconds(reduced: Dict, pattern: str) -> Tuple[float, float]:
    """Seconds and number of events, a chip, of the operations whose name
    matches the regular expression."""
    hits = [n for n in reduced["ops"] if re.search(pattern, n)]
    return (sum(reduced["ops"][n] for n in hits),
            sum(reduced["counts"][n] for n in hits))


def exposed_seconds(reduced: Dict, pattern: str) -> float:
    """Seconds, a chip, in which an operation matching the pattern runs and
    no other operation does."""
    total = 0.0
    for ops in reduced["per_chip"]:
        mine = [(s, e) for s, e, n in ops if re.search(pattern, n)]
        rest = [(s, e) for s, e, n in ops if not re.search(pattern, n)]
        both, _ = union_seconds(mine + rest)
        alone, _ = union_seconds(rest)
        total += both - alone
    return total / len(reduced["per_chip"])
