#!/bin/bash
# graftlint one-shot entry point: lint the package against the checked-in
# baseline (deeplearning4j_tpu/analysis/baseline.json). Extra args pass
# through, e.g.:
#   tools/lint.sh                         # CI gate: new findings fail
#   tools/lint.sh --fix-baseline          # intentional baseline update
#   tools/lint.sh --no-baseline           # show everything
#   tools/lint.sh --rules host-sync       # one rule class
#   tools/lint.sh --changed               # pre-commit: changed files only
#   tools/lint.sh --sarif out.sarif       # SARIF 2.1.0 log for CI upload
#
# Exit-code contract (documented in docs/LINT.md): 0 clean vs baseline, 1 new findings, 2 usage/parse/git
# error. Wire the pre-commit path with tools/pre-commit.sh.
set -u
cd "$(dirname "$0")/.."
exec env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    python -m deeplearning4j_tpu.analysis.lint deeplearning4j_tpu "$@"
