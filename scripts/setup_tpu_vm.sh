#!/usr/bin/env bash
# TPU VM bring-up: run once on every host of a pod slice.
# Reference analogue: prepareTPUVM.sh (jax[tpu] install + deps).
#
#   gcloud compute tpus tpu-vm ssh $TPU_NAME --zone $ZONE --worker=all \
#     --command="bash -s" < scripts/setup_tpu_vm.sh
set -euo pipefail

python3 -m pip install -U pip
# TPU jax wheel rides libtpu from the special index
python3 -m pip install "jax[tpu]==0.9.0" "jaxlib==0.9.0" "libtpu==0.0.34" \
  -f https://storage.googleapis.com/jax-releases/libtpu_releases.html
# deps inlined (mirrors requirements.txt): under the piped invocation above
# the repo is not on the remote host yet, so no file paths can be read
python3 -m pip install "flax==0.12.3" "optax==0.2.6" "orbax-checkpoint==0.11.32" \
  "chex==0.1.91" "einops==0.8.2" "numpy==2.0.2" "pyyaml==6.0.3" \
  "pytest==8.4.2" "pytest-xdist==3.8.0"
# optional extras used when configured (wandb logging, gs:// data/ckpts,
# HF-streaming source, tokenizer for serve/eval-on-text)
python3 -m pip install wandb gcsfs datasets transformers || true

python3 - <<'PY'
import jax
print(f"devices={jax.device_count()} local={jax.local_device_count()} "
      f"process={jax.process_index()}/{jax.process_count()}")
PY
