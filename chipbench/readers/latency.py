"""Readers over the harness's own stamps of each request."""
import numpy as np


def _counted(record):
    t0, t1 = record["t_open"], record["t_close"]
    return [lv for lv in record["lives"] if lv["counted"] and t0 <= lv["due"] < t1]


def generator_late_p99(record, spec):
    """How late the generator handed requests over: sent minus due."""
    late = [1e3 * (lv["sent"] - lv["due"]) for lv in _counted(record)
            if lv["sent"] is not None]
    return float(np.percentile(late, 99)) if late else None


def queue_wait_p50(record, spec):
    """Due to first admission into a slot."""
    wait = [1e3 * (lv["admitted"] - lv["due"]) for lv in _counted(record)
            if lv["admitted"] is not None]
    return float(np.median(wait)) if wait else None


def ttft_percentile(record, spec):
    """A percentile (``spec['q']``) of due time to first token over the
    requests due inside the window. With some hundred requests to a window
    it follows the order of the arrivals too closely to be held to a bound
    (PERF.md)."""
    if not record.get("ttft_s"):
        return None
    return 1e3 * float(np.percentile(record["ttft_s"], spec["q"]))


def ttft_mean(record, spec):
    """Mean of due time to first token over the requests due inside the
    window."""
    if not record.get("ttft_s"):
        return None
    return 1e3 * float(np.mean(record["ttft_s"]))
