#!/bin/sh
# PR 36, call 2: the span cost again (the step's sample is amortised now),
# then one traced run of each expert cell from the working tree: the new
# readings beside the harness's `steps by program` line of the same run.
JAX_PLATFORMS=cpu python3 tools/chip_calls/pr36_span_cost.py \
    artifacts/checkout/parent/paddle_tpu/observability/spans.py \
    paddle_tpu/observability/spans.py 2>&1 | grep pr36_span_cost | cut -c1-700
sh tools/chip_calls/pr36_run.sh 02 \
    C:mellum2-12b-a2.5b.repo-context-overload:3600000201:45:1 \
    C:kimi-k2-instruct.longdoc-overload:3600000202:45:1 \
    C:nemotron3-super-120b-a12b.reasoning-overload:3600000203:45:1
