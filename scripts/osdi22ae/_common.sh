#!/usr/bin/env bash
# Shared runner for the artifact-evaluation-style benchmarks: each model is
# run twice — Unity-searched strategy vs --only-data-parallel — and prints
# THROUGHPUT samples/s (protocol of the reference's scripts/osdi22ae/*.sh).
# The two runs are two processes, one after the other: a chip belongs to
# one process at a time.
set -e
REPO="$(cd "$(dirname "${BASH_SOURCE[0]}")/../.." && pwd)"
run_pair() {
  local example="$1"; shift
  echo "Running $example with a parallelization strategy discovered by the search"
  python "$REPO/examples/$example.py" "$@"
  echo "Running $example with data parallelism"
  python "$REPO/examples/$example.py" "$@" --only-data-parallel
}
