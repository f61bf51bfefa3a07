"""Readers for a model with routed experts and sliding-window layers
(``arith_moe.py``). The driver ``drivers/serve_arch.py`` stamps each step
with the engine's expert counters as they stood after it (``moe``: routed
items, experts hit, largest expert loads and expert layers run, all summed
over layers and programs), so a reading over the traced steps is a
difference of two stamps. A program without those counters (the one before
the expert layer was grouped) stamps nothing, and every reader here then
returns None."""
from chipbench import arith, arith_moe, trace_reduce
from chipbench.readers import program


def _traced(record):
    """(steps of the traced window, growth of the expert counters there)."""
    if not record.get("trace") or not record.get("trace_steps"):
        return None
    i0, i1 = record["trace_steps"]
    steps = record["steps"][i0:i1]
    if not steps or i0 == 0 or "moe" not in steps[-1]:
        return None
    before = record["steps"][i0 - 1]["moe"]
    return steps, [b - a for a, b in zip(before, steps[-1]["moe"])]


def decode_step_mfu(record, spec):
    """Least time the chip needs for the traced steps over the traced
    window's length: a step reads the shared weights once a program, the
    experts its programs hit, and the live keys and values (a window at most
    in the sliding layers); the larger of bytes over the peak bandwidth and
    operations over the peak rate, step by step."""
    t = _traced(record)
    if t is None or record["peaks"] is None:
        return None
    c, peaks = record["config"], record["peaks"]
    least, prev = 0.0, record["steps"][record["trace_steps"][0] - 1]["moe"]
    for s in t[0]:
        hit = s["moe"][1] - prev[1]
        prev = s["moe"]
        flops = arith_moe.decode_flops(c, s["contexts"])
        programs = int(bool(s["contexts"]))
        if s["prefill_width"]:
            programs += 1
            flops += arith_moe.prefill_flops(c, s["prefill_width"],
                                             s.get("prefill_ctx", 0))
        least += arith.least_seconds(
            flops, arith_moe.step_bytes(c, s["contexts"], programs, hit), peaks)
    if least <= 0:
        return None
    return arith.share(least, record["trace"]["window_s"], spec["name"])


def expert_matmul_roofline(record, spec):
    """The grouped expert matmuls: the experts hit read once and the routed
    items' activations, or their operations, whichever floor is higher, over
    the device time of the operations ``op_pattern`` names."""
    t = _traced(record)
    if t is None or record["peaks"] is None:
        return None
    sec, _ = trace_reduce.op_seconds(record["trace"], spec["op_pattern"])
    items, hit = t[1][0], t[1][1]
    if sec <= 0 or hit <= 0:
        return None
    c = record["config"]
    least = arith.least_seconds(arith_moe.expert_matmul_flops(c, items),
                                arith_moe.expert_matmul_bytes(c, hit, items),
                                record["peaks"])
    return arith.share(least, sec, spec["name"])


def paged_attention_roofline(record, spec):
    """Live keys and values over the peak bandwidth, a window at most in a
    sliding layer, over the paged kernel's device time in the traced steps."""
    if not record.get("trace") or not record.get("trace_steps") \
            or record["peaks"] is None:
        return None
    sec, _ = trace_reduce.op_seconds(record["trace"], spec["op_pattern"])
    i0, i1 = record["trace_steps"]
    live = sum(arith_moe.kv_live_bytes(record["config"], s["contexts"])
               for s in record["steps"][i0:i1])
    if sec <= 0 or live <= 0:
        return None
    return arith.share(live / record["peaks"]["hbm_bytes_per_s"], sec,
                       spec["name"])


def _growth(record, key):
    return program._counter_growth(record, key)


def experts_hit_share(record, spec):
    """Experts a program's rows reached over the experts there are, mean
    over expert layers run between the two snapshots."""
    hit, runs = (_growth(record, "moe_experts_hit_total"),
                 _growth(record, "moe_layer_steps_total"))
    if hit is None or not runs:
        return None
    return 100.0 * hit / (runs * record["config"]["num_experts"])


def max_expert_load_ratio(record, spec):
    """The fullest expert's items over the mean expert's, mean over expert
    layers run: 1 is a uniform router."""
    top, items = (_growth(record, "moe_max_expert_load_total"),
                  _growth(record, "moe_routed_items_total"))
    if top is None or not items:
        return None
    return top * record["config"]["num_experts"] / items


def window_pages_released_per_step(record, spec):
    """Pages of the sliding layers' pool that went back to its free list,
    a scheduler step."""
    freed = _growth(record, "window_pages_released_total")
    steps = program._span_growth(record, "sched.step", "count")
    if freed is None or not steps:
        return None
    return freed / steps


def full_pool_used_peak_share(record, spec):
    """Peak pages in use of the full layers' pool over its usable pages."""
    close = record["stats_close"]
    if "full_pool_used_peak" not in close:
        return None
    return 100.0 * close["full_pool_used_peak"] / close["num_usable"]
