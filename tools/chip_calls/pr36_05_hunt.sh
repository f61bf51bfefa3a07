#!/bin/sh
# PR 36, call 5, from the committed files (artifacts/checkout/final = git
# archive $(git write-tree)): a span's cost on the chip's host for the final
# spans.py, one traced run of longgen-overload, then the hunt for the long
# pauses: untraced windows of 225 s (five 45 s windows behind one set-up, cut
# into slices by pr36_hunt.py), two of Nemotron's cell (set-up alone is
# 100-260 s a process there) and one of longgen-overload.
JAX_PLATFORMS=cpu python3 tools/chip_calls/pr36_span_cost.py \
    artifacts/checkout/parent/paddle_tpu/observability/spans.py \
    artifacts/checkout/final/paddle_tpu/observability/spans.py \
    artifacts/checkout/parent/paddle_tpu/observability/spans.py \
    artifacts/checkout/final/paddle_tpu/observability/spans.py 2>&1 | grep pr36_span_cost | cut -c1-700
sh tools/chip_calls/pr36_run.sh 05 \
    C:internlm2-1.8b.longgen-overload:3600000500:45:1 \
    C:nemotron3-super-120b-a12b.reasoning-overload:3600000501:225:0 \
    C:internlm2-1.8b.longgen-overload:3600000502:225:0 \
    C:nemotron3-super-120b-a12b.reasoning-overload:3600000503:225:0
