#!/bin/bash
# graft-lint + graft-race gate — static analysis against the checked-in
# baselines (docs/STATIC_ANALYSIS.md).  Mirrors scripts/t1.sh: run from
# anywhere, exit code is nonzero if EITHER pass finds new findings.
#
# The linter is stdlib-only and never initializes a jax backend; it runs
# with JAX_PLATFORMS=cpu like the rest of verification.
#
# Extra flags pass through to BOTH passes (e.g. --format json); to
# update one baseline, call the module directly with --update-baseline.
cd "$(dirname "$0")/.." || exit 1

JAX_PLATFORMS=cpu python -m lightgbm_tpu lint "$@"
lint_rc=$?

JAX_PLATFORMS=cpu python -m lightgbm_tpu lint --race "$@"
race_rc=$?

[ "$lint_rc" -ne 0 ] && exit "$lint_rc"
exit "$race_rc"
