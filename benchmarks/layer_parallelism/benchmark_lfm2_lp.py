"""LFM2-MoE training benchmark (token sequences, one program per chip)

The token-model sibling of ``benchmark_amoebanet_lp.py``: the shared CLI
(``mpi4dl_tpu/parser.py``) plus ``--model-config`` (the model's published
``config.json`` keys, or a chip's share of a deployment of it:
``mpi4dl_tpu/models/lfm2.py``) and ``--sequence-length``; the same
``build_config`` / ``make_trainer`` / ``run_training`` walk. A sample is
one sequence, so the rates printed are sequences a second.

    # the tiny cut, on the CPU
    JAX_PLATFORMS=cpu python benchmarks/layer_parallelism/benchmark_lfm2_lp.py \
        --model-config benchmarks/layer_parallelism/lfm2_tiny.json \
        --sequence-length 64 --batch-size 2 --max-steps 3 -v
    # one chip's share of LFM2-8B-A1B over four chips, on the chip
    python benchmarks/layer_parallelism/benchmark_lfm2_lp.py \
        --model-config chipbench/configs/lfm2_8b_a1b_share4.json --max-steps 20 -v
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")
)

from common import build_config, build_lfm2, make_trainer, run_training, token_model_args


def main():
    args = token_model_args(sys.argv[1:])
    cfg = build_config(args, spatial=False)
    cells, plain = build_lfm2(args, cfg)
    trainer, _ = make_trainer(args, cfg, cells, plain)
    run_training(args, trainer, tag="benchmark_lfm2_lp")


if __name__ == "__main__":
    main()
