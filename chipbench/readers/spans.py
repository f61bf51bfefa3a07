"""Readers over the harness's annotations in the profiler's trace."""
import statistics


def span_ms_p50(record, spec):
    """Median length of the spans named ``spec['span']`` in the traced
    window."""
    if not record.get("trace"):
        return None
    ms = [1e3 * s for n, s in record["trace"]["spans"] if n == spec["span"]]
    return statistics.median(ms) if ms else None
