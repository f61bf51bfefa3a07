#!/bin/sh
# PR 36, call 1: a span's cost on the chip's host (parent's spans.py and the
# change's, outside and inside a profiler session; the device plays no part),
# then one traced run of each dense cell from the working tree.
JAX_PLATFORMS=cpu python3 tools/chip_calls/pr36_span_cost.py \
    artifacts/checkout/parent/paddle_tpu/observability/spans.py \
    paddle_tpu/observability/spans.py \
    artifacts/checkout/parent/paddle_tpu/observability/spans.py \
    paddle_tpu/observability/spans.py 2>&1 | cut -c1-700
sh tools/chip_calls/pr36_run.sh 01 \
    C:internlm2-1.8b.longgen-overload:3600000101:45:1 \
    C:internlm2-1.8b.chat-shared:3600000102:45:1
