"""Readers for a hybrid model with a recurrent-state pool and a share of
the routed experts (``arith_hybrid.py``). The driver stamps each step with
the engine's expert counters as ``readers/moe.py`` says; here
``moe_routed_items_total`` counts the items computed on this chip and
``moe_experts_hit_total`` the held experts they reached. A program without
the counters or the state pool (the one before they were added) cannot run
the configuration at all; every reader still returns None where it finds
nothing to read."""
from chipbench import arith, arith_hybrid, trace_reduce
from chipbench.readers import moe as _moe
from chipbench.readers import program


def decode_step_mfu(record, spec):
    """Least time the chip needs for the traced steps over the traced
    window's length: a step reads the shared weights once a program, the
    held experts its programs hit, every decoding row's recurrent state
    (read and written) and the live keys and values; the larger of bytes
    over the peak bandwidth and operations over the peak rate, step by
    step."""
    t = _moe._traced(record)
    if t is None or record["peaks"] is None:
        return None
    c, peaks = record["config"], record["peaks"]
    least, prev = 0.0, record["steps"][record["trace_steps"][0] - 1]["moe"]
    for s in t[0]:
        items, hit = s["moe"][0] - prev[0], s["moe"][1] - prev[1]
        prev = s["moe"]
        programs = int(bool(s["contexts"]))
        chunk = int(bool(s["prefill_width"]))
        # the step's items belong to its two programs together; their
        # operations are the same wherever they are booked
        flops = arith_hybrid.decode_flops(c, s["contexts"], items)
        if chunk:
            flops += arith_hybrid.prefill_flops(
                c, s["prefill_width"], s.get("prefill_ctx", 0), 0)
        least += arith.least_seconds(
            flops, arith_hybrid.step_bytes(c, s["contexts"], programs + chunk,
                                           hit, chunk), peaks)
    if least <= 0:
        return None
    return arith.share(least, record["trace"]["window_s"], spec["name"])


def ssm_state_update_roofline(record, spec):
    """Every decoding row's state read and written once in every Mamba-2
    layer, or the update's operations, whichever floor is higher, over the
    device time of the operations ``op_pattern`` names, in the traced
    steps."""
    if not record.get("trace") or not record.get("trace_steps") \
            or record["peaks"] is None:
        return None
    sec, _ = trace_reduce.op_seconds(record["trace"], spec["op_pattern"])
    i0, i1 = record["trace_steps"]
    c = record["config"]
    row_layers = arith_hybrid.kind_layers(c)[0] * sum(
        len(s["contexts"]) for s in record["steps"][i0:i1])
    if sec <= 0 or row_layers <= 0:
        return None
    least = arith.least_seconds(arith_hybrid.ssm_update_flops(c, row_layers),
                                arith_hybrid.ssm_update_bytes(c, row_layers),
                                record["peaks"])
    return arith.share(least, sec, spec["name"])


def expert_matmul_roofline(record, spec):
    """The grouped expert matmuls: the held experts hit read once and the
    items' activations, or their operations, whichever floor is higher,
    over the device time of the operations ``op_pattern`` names."""
    t = _moe._traced(record)
    if t is None or record["peaks"] is None:
        return None
    sec, _ = trace_reduce.op_seconds(record["trace"], spec["op_pattern"])
    items, hit = t[1][0], t[1][1]
    if sec <= 0 or hit <= 0:
        return None
    c = record["config"]
    least = arith.least_seconds(arith_hybrid.expert_matmul_flops(c, items),
                                arith_hybrid.expert_matmul_bytes(c, hit, items),
                                record["peaks"])
    return arith.share(least, sec, spec["name"])


def state_slots_used_peak_share(record, spec):
    """Most slots of the recurrent-state pool in use at once over the
    slots there are."""
    close = record["stats_close"]
    if not close.get("state_slots"):
        return None
    return 100.0 * close["state_slots_used_peak"] / close["state_slots"]


def experts_hit_share(record, spec):
    """Held experts a program's rows reached over the experts held, mean
    over expert layers run between the two snapshots."""
    hit, runs = (program._counter_growth(record, "moe_experts_hit_total"),
                 program._counter_growth(record, "moe_layer_steps_total"))
    if hit is None or not runs:
        return None
    return 100.0 * hit / (runs * record["config"]["n_routed_experts"])


def items_held_share(record, spec):
    """Items computed here over all routed items: the held share of the
    experts where the router is uniform."""
    here, away = (program._counter_growth(record, "moe_routed_items_total"),
                  program._counter_growth(record, "moe_items_elsewhere_total"))
    if here is None or away is None or not here + away:
        return None
    return 100.0 * here / (here + away)
