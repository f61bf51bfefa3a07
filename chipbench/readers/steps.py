"""Readers over what the program keeps of its own steps since PR 36
(``paddle_tpu/observability/spans.py``, ``ServingScheduler.step``): the
steps that committed a prefill chunk's program, with their wall time, and
the stall records. Like ``program.py`` each reads the growth between
``stats_open`` and ``stats_close``; it gives 0 where nothing happened and
None only where the closing snapshot lacks the key, as a program from
before these totals does."""
from .program import _counter_growth, _span_growth


def _chunk_steps(record):
    """(steps that committed a chunk, their ns, all steps, their ns)
    between the snapshots, or None where a total is missing."""
    grown = (_counter_growth(record, "steps_committing_chunk_total"),
             _counter_growth(record, "steps_committing_chunk_ns_total"),
             _span_growth(record, "sched.step", "count"),
             _span_growth(record, "sched.step", "ns"))
    return None if None in grown else grown


def chunk_step_share(record, spec):
    """Percent of the scheduler's steps that committed a prefill chunk's
    program."""
    grown = _chunk_steps(record)
    if grown is None:
        return None
    chunk, _, steps, _ = grown
    return 100.0 * chunk / steps if steps else 0.0


def chunk_step_extra_ms(record, spec):
    """Mean wall time of the steps that committed a chunk less the mean of
    the others: what a chunk adds to a step. A pipelined step waits for
    what it commits, so this is device time."""
    grown = _chunk_steps(record)
    if grown is None:
        return None
    chunk, chunk_ns, steps, step_ns = grown
    rest = steps - chunk
    if not chunk or not rest:
        return 0.0
    return (chunk_ns / chunk - (step_ns - chunk_ns) / rest) / 1e6


def _stalls(record):
    """The closing snapshot's stall records (the program keeps the newest
    32) as (those of the window, those the harness's own work between
    set-up's last step and the window's first one left behind: recorded
    after the opening snapshot under a step before it)."""
    stalls = record["stats_close"].get("stalls")
    if stalls is None:
        return None
    first = record["stats_open"].get("sched_steps", 0)
    old = {r["start_ns"] for r in record["stats_open"].get("stalls", [])}
    return ([r for r in stalls if r["step"] >= first],
            [r for r in stalls if r["step"] < first
             and r["start_ns"] not in old])


def stall_ms(record, spec):
    """Milliseconds inside stalls (a span's own time over 100 ms, or as
    long between two steps) from the window's first step on: the growth
    of the program's total, which forgets nothing, less what lay before
    that step."""
    ns, stalls = _counter_growth(record, "stall_ns_total"), _stalls(record)
    if ns is None or stalls is None:
        return None
    _, before = stalls
    return (ns - sum(r["wall_ns"] for r in before)) / 1e6


def stall_off_cpu_share(record, spec):
    """Of the wall time of the window's stall records, the percent the
    thread spent off its core."""
    stalls = _stalls(record)
    if stalls is None:
        return None
    mine, _ = stalls
    wall = sum(r["wall_ns"] for r in mine)
    if not wall:
        return 0.0
    on_cpu = sum(min(r["cpu_ns"], r["wall_ns"]) for r in mine)
    return 100.0 * (wall - on_cpu) / wall
