#!/usr/bin/env bash
# Tiered test runner — the analog of the reference's L0/L1 scripts
# (/root/reference/tests/L0/run_test.py:1-29, tests/L1/common/run_test.sh)
# and the .jenkins CI harness:
#
#   tests/run_tests.sh l0       fast gate: every subsystem smoke-covered,
#                               ~7 min on a 1-core host (283 tests, r5)
#   tests/run_tests.sh full     the whole suite, chunked so no single
#                               pytest invocation exceeds a CI timeout
#
# Exit code is nonzero on any failure, so this is CI-ready as-is.
set -euo pipefail
cd "$(dirname "$0")/.."

tier="${1:-l0}"

case "$tier" in
  l0)
    exec python -m pytest tests/ -m l0 -q --durations=10
    ;;
  full)
    # chunked: the full suite needs ~20 min serial on a 1-core host, so
    # no single invocation may own the whole wall-clock budget
    python -m pytest tests/test_cross_product.py -q
    python -m pytest tests/test_bert.py tests/test_t5.py -q
    python -m pytest tests/test_gpt.py tests/test_pipeline.py \
        tests/test_combined_axes.py -q
    python -m pytest tests/test_resnet_examples.py \
        tests/test_softmax_attention.py tests/test_moe.py \
        tests/test_ring_attention.py -q
    exec python -m pytest tests/ -q \
        --ignore=tests/test_cross_product.py \
        --ignore=tests/test_bert.py --ignore=tests/test_t5.py \
        --ignore=tests/test_gpt.py --ignore=tests/test_pipeline.py \
        --ignore=tests/test_combined_axes.py \
        --ignore=tests/test_resnet_examples.py \
        --ignore=tests/test_softmax_attention.py \
        --ignore=tests/test_moe.py --ignore=tests/test_ring_attention.py
    ;;
  *)
    echo "usage: tests/run_tests.sh [l0|full]" >&2
    exit 2
    ;;
esac
