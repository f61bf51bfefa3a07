"""Readers over the device trace: a kernel's time against the least time
the chip needs for the work the harness counted or the shapes give."""
from chipbench import arith, trace_reduce


def _ready(record):
    return bool(record.get("trace")) and record["peaks"] is not None


def paged_attention_roofline(record, spec):
    """Live keys and values read over the peak bandwidth, over the kernel's
    device time, in the traced steps."""
    if not _ready(record) or not record.get("trace_steps"):
        return None
    sec, _ = trace_reduce.op_seconds(record["trace"], spec["op_pattern"])
    i0, i1 = record["trace_steps"]
    ctx = sum(sum(s["contexts"]) for s in record["steps"][i0:i1])
    if sec <= 0 or ctx <= 0:
        return None
    least = ctx * arith.kv_bytes_per_token(record["config"]) \
        / record["peaks"]["hbm_bytes_per_s"]
    return arith.share(least, sec, spec["name"])


def flash_roofline(record, spec):
    """Causal FLOPs from the shapes, a chip, over the peak rate, over the
    kernel's device time. ``events_per_pass`` kernels make one pass."""
    if not _ready(record):
        return None
    sec, events = trace_reduce.op_seconds(record["trace"], spec["op_pattern"])
    if sec <= 0:
        return None
    c, job, chips = record["config"], record["mix"], record["cell"].chips
    fn = arith.flash_fwd_flops if spec["pass"] == "fwd" else arith.flash_bwd_flops
    flops = fn(c, job["batch"], job["seq_len"]) / chips \
        * events / spec["events_per_pass"]
    return arith.share(flops / record["peaks"]["bf16_flops_per_s"], sec,
                       spec["name"])


def collective_exposed_share(record, spec):
    """Time a collective runs and no other operation does, over the traced
    window."""
    if not _ready(record):
        return None
    sec = trace_reduce.exposed_seconds(record["trace"], spec["op_pattern"])
    return 100.0 * sec / record["trace"]["window_s"] if sec > 0 else None
