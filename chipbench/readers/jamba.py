"""Readers for a Jamba model (``arith_jamba.py``): Mamba-1 layers over a
recurrent-state pool, attention over the paged KV pool, a dense MLP a layer.
A step's ``contexts`` are the tokens each decoding row had cached; its
``prefill_width`` the tokens its chunk program took, ``prefill_ctx`` the
context that chunk stood on. A program without Mamba-1 layers (the one
before they were added) cannot run the configuration at all; every reader
still returns None where it finds nothing to read."""
from chipbench import arith, arith_jamba, trace_reduce


def _traced(record):
    """The steps of the traced window, or None."""
    if not record.get("trace") or not record.get("trace_steps") \
            or record["peaks"] is None:
        return None
    i0, i1 = record["trace_steps"]
    return record["steps"][i0:i1] or None


def decode_step_mfu(record, spec):
    """Least time the chip needs for the traced steps over the traced
    window's length: a step reads the weights once a program, every
    decoding row's recurrent state (read and written) and the live keys and
    values, and its chunk's row's state and context; the larger of bytes
    over the peak bandwidth and operations over the peak rate, step by
    step."""
    steps = _traced(record)
    if steps is None:
        return None
    c, peaks = record["config"], record["peaks"]
    least = 0.0
    for s in steps:
        programs = int(bool(s["contexts"]))
        flops = arith_jamba.decode_flops(c, s["contexts"])
        chunk = int(bool(s["prefill_width"]))
        ctx = s.get("prefill_ctx", 0)
        if chunk:
            flops += arith_jamba.prefill_flops(c, s["prefill_width"], ctx)
        least += arith.least_seconds(
            flops, arith_jamba.step_bytes(c, s["contexts"], programs + chunk,
                                          chunk, ctx + s["prefill_width"]),
            peaks)
    if least <= 0:
        return None
    return arith.share(least, record["trace"]["window_s"], spec["name"])


def mamba1_state_update_roofline(record, spec):
    """Every decoding row's state read and written once in every Mamba-1
    layer, or the update's operations, whichever floor is higher, over the
    device time of the operations ``op_pattern`` names, in the traced
    steps."""
    steps = _traced(record)
    if steps is None:
        return None
    sec, _ = trace_reduce.op_seconds(record["trace"], spec["op_pattern"])
    c = record["config"]
    row_layers = arith_jamba.kind_layers(c)[0] * sum(
        len(s["contexts"]) for s in steps)
    if sec <= 0 or row_layers <= 0:
        return None
    least = arith.least_seconds(
        arith_jamba.state_update_flops(c, row_layers),
        arith_jamba.state_update_bytes(c, row_layers), record["peaks"])
    return arith.share(least, sec, spec["name"])


def mamba1_chunk_scan_roofline(record, spec):
    """The chunk scan: each chunk token's inputs and output and each call's
    state and decays once, or the recurrence's operations, whichever floor
    is higher, over the device time of the operations ``op_pattern``
    names, in the traced steps."""
    steps = _traced(record)
    if steps is None:
        return None
    sec, _ = trace_reduce.op_seconds(record["trace"], spec["op_pattern"])
    c = record["config"]
    layers = arith_jamba.kind_layers(c)[0]
    tokens = layers * sum(s["prefill_width"] for s in steps)
    calls = layers * sum(1 for s in steps if s["prefill_width"])
    if sec <= 0 or tokens <= 0:
        return None
    least = arith.least_seconds(arith_jamba.scan_flops(c, tokens),
                                arith_jamba.scan_bytes(c, tokens, calls),
                                record["peaks"])
    return arith.share(least, sec, spec["name"])
