"""Readers for a latent-attention model with a share of the routed experts
(``arith_mla.py``). The driver stamps each step with the engine's expert
counters as ``readers/moe.py`` says; here ``moe_routed_items_total`` counts
the items computed on this chip and ``moe_experts_hit_total`` the held
experts they reached. A step's ``contexts`` are the tokens each decoding
row had cached: what the program's ``latent_tokens_attended_total`` sums a
latent layer. A program without latent attention (the one before it was
added) cannot run the configuration at all; every reader still returns None
where it finds nothing to read. The shares of the held experts hit and of
the items held are ``readers/hybrid.py``'s."""
from chipbench import arith, arith_mla, trace_reduce
from chipbench.readers import moe as _moe


def decode_step_mfu(record, spec):
    """Least time the chip needs for the traced steps over the traced
    window's length: a step reads the shared weights once a program, the
    held experts its programs hit, the live latents of every decoding row
    and the context of its chunk's row; the larger of bytes over the peak
    bandwidth and operations over the peak rate, step by step."""
    t = _moe._traced(record)
    if t is None or record["peaks"] is None:
        return None
    c, peaks = record["config"], record["peaks"]
    least, prev = 0.0, record["steps"][record["trace_steps"][0] - 1]["moe"]
    for s in t[0]:
        items, hit = s["moe"][0] - prev[0], s["moe"][1] - prev[1]
        prev = s["moe"]
        programs = int(bool(s["contexts"]))
        flops = arith_mla.decode_flops(c, s["contexts"], items)
        chunk_ctx = 0
        if s["prefill_width"]:
            programs += 1
            chunk_ctx = s.get("prefill_ctx", 0) + s["prefill_width"]
            # the step's items are booked with the decode rows above
            flops += arith_mla.prefill_flops(
                c, s["prefill_width"], s.get("prefill_ctx", 0), 0)
        least += arith.least_seconds(
            flops, arith_mla.step_bytes(c, s["contexts"], programs, hit,
                                        chunk_ctx), peaks)
    if least <= 0:
        return None
    return arith.share(least, record["trace"]["window_s"], spec["name"])


def paged_latent_attention_roofline(record, spec):
    """The decode kernel: every live latent row read once with the rows'
    queries and results, or the absorbed form's operations, whichever floor
    is higher, over the device time of the operations ``op_pattern`` names,
    in the traced steps."""
    if not record.get("trace") or not record.get("trace_steps") \
            or record["peaks"] is None:
        return None
    sec, _ = trace_reduce.op_seconds(record["trace"], spec["op_pattern"])
    i0, i1 = record["trace_steps"]
    c = record["config"]
    least = sum(arith.least_seconds(
        arith_mla.latent_attention_flops(c, s["contexts"]),
        arith_mla.latent_attention_bytes(c, s["contexts"]), record["peaks"])
        for s in record["steps"][i0:i1] if s["contexts"])
    if sec <= 0 or least <= 0:
        return None
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
    least = arith.least_seconds(arith_mla.expert_matmul_flops(c, items),
                                arith_mla.expert_matmul_bytes(c, hit, items),
                                record["peaks"])
    return arith.share(least, sec, spec["name"])


def latent_pool_used_peak_share(record, spec):
    """Most pages of the latent pool in use at once over its usable pages."""
    close = record["stats_close"]
    if not close.get("latent_pool_bytes"):
        return None
    return 100.0 * close["latent_pool_used_peak"] / close["num_usable"]
