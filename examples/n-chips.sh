#!/bin/bash
# Local multi-chip simulation harness — the TPU analogue of the reference's
# examples/n-workers.sh (which spawned W worker processes under `screen` on
# localhost ports): under SPMD there are no worker processes, so an N-chip
# cluster is simulated with N virtual CPU devices in ONE process.
#
# Usage: ./n-chips.sh <n-chips> <model.m> <tokenizer.t> [extra args...]
#
# Extra args win over the defaults (argparse last-wins), so mixed meshes
# run as e.g.:
#   ./n-chips.sh 8 m.m t.t --tp 2 --pp 2 --sp 2        # pp x sp x tp
#   ./n-chips.sh 8 m.m t.t --tp 2 --dp 2 --batch-size 2 # lanes over dp
#   ./n-chips.sh 4 m.m t.t --kv-dtype int8 --weight-format q40

set -e
N=${1:?usage: n-chips.sh <n-chips> <model.m> <tokenizer.t> [args...]}
MODEL=${2:?model path required}
TOK=${3:?tokenizer path required}
shift 3

REPO="$(cd "$(dirname "$0")/.." && pwd)"
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=$N"
export PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}"

exec python -m dllama_tpu inference \
    --model "$MODEL" --tokenizer "$TOK" --tp "$N" \
    --prompt "Hello world" --steps 32 --temperature 0.0 --dtype f32 "$@"
