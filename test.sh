#!/usr/bin/env bash
# Tier-1 test entry point (used by CI and locally).
#
# - JAX_ENABLE_X64: exact-state-reconstruction claims are float64 claims.
# - xla_force_host_platform_device_count=8: exercises the multi-device
#   code paths on CPU hosts.  Tests that must see exactly 1 device
#   (dry-run/elastic-restore) re-exec in subprocesses that override
#   XLA_FLAGS themselves, so the suite is flag-order independent.
set -euo pipefail
cd "$(dirname "$0")"

export JAX_ENABLE_X64=1
# Tests run on the host CPU; the program runs on the chip via chip_smoke.py.
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export XLA_FLAGS="${XLA_FLAGS:---xla_force_host_platform_device_count=8}"
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Static invariants first (stdlib-only, fast): src/ must lint clean —
# any unsuppressed repro-lint finding fails the run before pytest starts.
python -m tools.repro_lint src

exec python -m pytest -x -q "$@"
