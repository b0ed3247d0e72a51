"""SDAR-MoE block-diffusion training benchmark (token sequences, one program per chip)

The fourth token model beside ``benchmark_lfm2_lp.py``: the same flags and
the same ``build_config`` / ``make_trainer`` / ``run_training`` walk. What
differs is the step (``mpi4dl_tpu/models/sdar.py``): every sequence of
``--sequence-length`` tokens enters as a noisy copy beside the clean one
(``data.BlockDiffusionTokens``), attention runs under the block-diffusion
mask, and the trainer takes the model's own weighted loss. A sample is one
sequence, so the rates printed are sequences a second.

    # the tiny cut, on the CPU
    JAX_PLATFORMS=cpu python benchmarks/layer_parallelism/benchmark_sdar_lp.py \
        --model-config benchmarks/layer_parallelism/sdar_tiny.json \
        --sequence-length 64 --batch-size 2 --max-steps 3 -v
    # one chip's share of SDAR-30B-A3B-Chat over eight chips, on the chip
    python benchmarks/layer_parallelism/benchmark_sdar_lp.py \
        --model-config chipbench/configs/sdar_30b_a3b_share8.json \
        --precision bf16 --max-steps 20 -v
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")
)

from common import (
    block_diffusion_dataset,
    build_config,
    build_sdar,
    make_trainer,
    run_training,
    token_model_args,
)


def main():
    from mpi4dl_tpu.models.sdar import block_diffusion_loss

    args = token_model_args(sys.argv[1:])
    cfg = build_config(args, spatial=False)
    cells, plain = build_sdar(args, cfg)
    trainer, _ = make_trainer(args, cfg, cells, plain, loss=block_diffusion_loss)
    run_training(args, trainer, tag="benchmark_sdar_lp", dataset=block_diffusion_dataset)


if __name__ == "__main__":
    main()
