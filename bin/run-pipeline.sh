#!/usr/bin/env bash
# Pipeline launcher — the reference's bin/run-pipeline.sh (spark-submit
# wrapper with KEYSTONE_MEM) re-imagined for the TPU runtime.
#
#   bin/run-pipeline.sh <PipelineName> [pipeline flags...]
#   bin/run-pipeline.sh --list
#
# Environment knobs (all optional):
#   JAX_PLATFORMS       jax's own platform switch (e.g. "cpu" for the
#                       virtual device path; default: the TPU when one
#                       is attached)
#   KEYSTONE_NUM_CPU_DEVICES
#                       with JAX_PLATFORMS=cpu, number of virtual host
#                       devices to expose (the LocalSparkContext analogue)
#   KEYSTONE_MEM        fraction of HBM jax may preallocate, e.g. "0.8".
#                       NOTE: plays the role of the reference's
#                       executor-memory knob but takes a fraction in
#                       (0,1], NOT a JVM size like "4g"
#   JAX_COMPILATION_CACHE_DIR
#                       persistent XLA compile-cache dir (default:
#                       .jax_cache/ in the checkout) — repeat runs of a
#                       pipeline skip compilation
#   KEYSTONE_COMPILE_CACHE
#                       "off" disables the persistent compile cache
#   KEYSTONE_STATE_DIR  saved-pipeline-state dir: materialized prefixes
#                       persisted by save_pipeline_state are reloaded
#                       instead of recomputed (SavedStateLoadRule)
set -euo pipefail

REPO_ROOT="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
export PYTHONPATH="${REPO_ROOT}${PYTHONPATH:+:${PYTHONPATH}}"

if [[ -n "${KEYSTONE_NUM_CPU_DEVICES:-}" ]]; then
  export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=${KEYSTONE_NUM_CPU_DEVICES}"
fi
if [[ -n "${KEYSTONE_MEM:-}" ]]; then
  # a fraction in (0,1]: either has a nonzero digit after the point, or is 1
  if ! [[ "${KEYSTONE_MEM}" =~ ^0?\.[0-9]*[1-9][0-9]*$|^1(\.0+)?$ ]]; then
    echo "KEYSTONE_MEM must be a fraction in (0,1], e.g. 0.8 (got '${KEYSTONE_MEM}')" >&2
    exit 2
  fi
  export XLA_PYTHON_CLIENT_MEM_FRACTION="${KEYSTONE_MEM}"
fi

exec python -m keystone_tpu.cli "$@"
