#!/usr/bin/env bash
# Tuned launcher (DESIGN.md §Autotuner, launch-environment half).
#
# Shell-native equivalent of `python -m repro.launch.env -- ...` for the
# common case:
#
#   ./run.sh -m repro.launch.train --dataset FB15k --model gqe ...
#
# Everything here is additive: variables you already exported win.
set -euo pipefail
cd "$(dirname "$0")"

# tcmalloc: arena-contention-free allocator for the pipeline's host threads.
# LD_PRELOAD only applies at process start, which is why this is a launcher.
if [ -z "${LD_PRELOAD:-}" ]; then
  for lib in /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
             /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4 \
             /usr/lib/libtcmalloc.so.4 \
             /usr/lib/libtcmalloc_minimal.so.4; do
    if [ -f "$lib" ]; then
      export LD_PRELOAD="$lib"
      break
    fi
  done
fi
export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD="${TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD:-60000000000}"

# Quiet the TF/XLA C++ banner; put step markers at the fused train-step
# boundary (where the profiler + obs span bridge expect them).
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"
if [[ "${XLA_FLAGS:-}" != *"--xla_step_marker_location"* ]]; then
  export XLA_FLAGS="${XLA_FLAGS:-} --xla_step_marker_location=1"
fi

# fp32 bit-identity contracts: never let x64 defaults sneak in.
export JAX_ENABLE_X64="${JAX_ENABLE_X64:-0}"
export JAX_DEFAULT_DTYPE_BITS="${JAX_DEFAULT_DTYPE_BITS:-32}"

# Persisted kernel-tile autotune cache (tuning cost paid once per machine).
export REPRO_AUTOTUNE_CACHE="${REPRO_AUTOTUNE_CACHE:-$PWD/.autotune_cache.json}"

export PYTHONPATH="$PWD/src${PYTHONPATH:+:$PYTHONPATH}"

exec python "$@"
