"""Readers over counters: the program's own (``ServingScheduler.stats()``,
``engine.stats()``) and JAX's compile events."""


def _window(record):
    i0, i1 = record["window_steps"]
    return record["steps"][i0:i1]


def programs_first_met_in_window(record, spec):
    """Compilations and persistent-cache loads between the window's start
    and its end."""
    return float(record["programs_in_window"])


def prefix_hit_share(record, spec):
    """Prompt tokens served from cached pages over prompt tokens, for the
    requests admitted after the window opened. The allocator counts pages
    granted (``allocs_total``) and references taken on live pages
    (``shares_total``); the difference is fresh pages. What the admissions
    reserved (the harness counts it: pages for prompt and answer) less the
    fresh pages is what they were given from the cache."""
    e = record["mix"]["engine"]
    page, t0 = e["page_size"], record["t_open"]
    mine = [lv for lv in record["lives"]
            if lv["admitted"] is not None and lv["admitted"] >= t0]
    if not mine:
        return None
    a, b = record["stats_open"], record["stats_close"]
    fresh = (b["allocs_total"] - a["allocs_total"]) \
        - (b["shares_total"] - a["shares_total"])
    reserved = sum(-(-(lv["prompt_tokens"] + lv["max_new"]) // page) for lv in mine)
    hit = (reserved - fresh) * page
    prompts = sum(lv["prompt_tokens"] for lv in mine)
    return 100.0 * hit / prompts if hit > 0 else None


def pool_used_peak_share(record, spec):
    """Peak pages in use over the pool's usable pages, in the window."""
    steps = _window(record)
    if not steps:
        return None
    usable = record["stats_close"]["num_usable"]
    return 100.0 * max(s["pages_used"] for s in steps) / usable


def batch_occupancy(record, spec):
    """Rows that decoded over ``max_batch``, mean over the window's steps."""
    steps = _window(record)
    if not steps:
        return None
    rows = sum(s["rows"] for s in steps) / len(steps)
    return 100.0 * rows / record["mix"]["engine"]["max_batch"]
