#!/usr/bin/env bash
# The fast tier as the driver runs it: CPU, six xdist workers, one test
# file per worker at a time (--dist loadfile keeps a file's module-scoped
# fixtures — and the one process that may describe a TPU topology — in one
# worker).  The workers share the persistent XLA compile cache that
# tests/conftest.py arms (aot.compile_cache_dir).
#
# Usage: ./run_tests.sh [extra pytest args...]   e.g. ./run_tests.sh -m slow
set -u
exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 \
  --dist loadfile -p no:randomly "$@"
