"""Readers over the program's own span totals and counters
(``paddle_tpu/observability/spans.py``), as ``ServingScheduler.stats()``
gives them at the window's start (``stats_open``) and after it
(``stats_close``). Every reading is the difference of the totals between
the two, over the scheduler steps between them: the window and, where the
mix drains, the drain after it. A program that has no such total (the one
before the spans were added) gives None, and the metric is left out of
the line."""


def _span_growth(record, name, field):
    """How much a span's monotonic total (``count`` or ``ns``) grew between
    the two snapshots; None where the closing snapshot does not have it."""
    close = (record["stats_close"].get("spans") or {}).get(name)
    if close is None:
        return None
    opened = (record["stats_open"].get("spans") or {}).get(name)
    return close[field] - (opened[field] if opened else 0)


def _counter_growth(record, key):
    close = record["stats_close"].get(key)
    if close is None:
        return None
    return close - record["stats_open"].get(key, 0)


def span_ms_per_step(record, spec):
    """Milliseconds a scheduler step inside the spans ``spec['spans']``,
    less those inside ``spec['less']`` (a parent's self time is its total
    less its children's)."""
    steps = _span_growth(record, "sched.step", "count")
    if not steps:
        return None
    total = 0
    for sign, names in ((1, spec["spans"]), (-1, spec.get("less", []))):
        for name in names:
            ns = _span_growth(record, name, "ns")
            if ns is None:
                return None
            total += sign * ns
    return total / 1e6 / steps


def counter_ratio(record, spec):
    """``spec['scale']`` times the growth of the counter ``spec['num']``
    over the growth of ``spec['den']``."""
    num = _counter_growth(record, spec["num"])
    den = _counter_growth(record, spec["den"])
    if num is None or not den:
        return None
    return spec["scale"] * num / den
