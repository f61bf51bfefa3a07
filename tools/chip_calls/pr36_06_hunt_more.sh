#!/bin/sh
# PR 36, call 6, from the committed files: a span's cost on the chip's host
# for the final spans.py (kept in chiprun_out/pr36/span_cost_final.txt: the
# tail of a call's output does not reach back to its first lines), then the
# hunt again: call 5 met 25 stalls of 108-148 ms and none of 0.5 s. Three
# windows of 225 s of longgen-overload and one of Mellum's cell.
mkdir -p chiprun_out/pr36
JAX_PLATFORMS=cpu python3 tools/chip_calls/pr36_span_cost.py \
    artifacts/checkout/parent/paddle_tpu/observability/spans.py \
    artifacts/checkout/final/paddle_tpu/observability/spans.py \
    artifacts/checkout/parent/paddle_tpu/observability/spans.py \
    artifacts/checkout/final/paddle_tpu/observability/spans.py 2>&1 \
    | grep pr36_span_cost > chiprun_out/pr36/span_cost_final.txt
sh tools/chip_calls/pr36_run.sh 06 \
    C:internlm2-1.8b.longgen-overload:3600000601:225:0 \
    C:mellum2-12b-a2.5b.repo-context-overload:3600000602:225:0 \
    C:internlm2-1.8b.longgen-overload:3600000603:225:0 \
    C:internlm2-1.8b.longgen-overload:3600000604:225:0 > chiprun_out/pr36/call06.txt 2>&1
tail -c 3000 chiprun_out/pr36/call06.txt
