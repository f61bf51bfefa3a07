"""The whole step's share of the chip's peak, from the configuration and the
work the harness counted, whatever implements the step."""
from chipbench import arith


def _traced_steps(record):
    if not record.get("trace") or not record.get("trace_steps"):
        return []
    i0, i1 = record["trace_steps"]
    return record["steps"][i0:i1]


def decode_step_mfu(record, spec):
    """Least time the chip needs for the traced steps over the traced
    window's length. A step reads every weight once and the live keys and
    values, and a prefill chunk in the step reads the weights once more; the
    least time is the larger of bytes over the peak bandwidth and operations
    over the peak rate."""
    steps = _traced_steps(record)
    if not steps or record["peaks"] is None:   # no peaks: a CPU rehearsal
        return None
    c, peaks = record["config"], record["peaks"]
    least = 0.0
    for s in steps:
        if s["contexts"]:
            least += arith.least_seconds(
                arith.decode_step_flops(c, s["contexts"]),
                arith.decode_step_bytes(c, s["contexts"]), peaks)
        if s["prefill_width"]:
            least += arith.least_seconds(
                arith.prefill_flops(c, s["prefill_width"], 0),
                arith.decode_step_bytes(c, []), peaks)
    if least <= 0:
        return None
    return arith.share(least, record["trace"]["window_s"], spec["name"])


def train_mfu(record, spec):
    """FLOPs a token (recompute not credited) times tokens a second a chip
    over the chip's peak."""
    if record["peaks"] is None:
        return None
    c, job = record["config"], record["mix"]
    flops = arith.train_flops_per_token(c, job["seq_len"])
    return arith.share(flops * record["tokens_per_s_per_chip"],
                       record["peaks"]["bf16_flops_per_s"], spec["name"])
