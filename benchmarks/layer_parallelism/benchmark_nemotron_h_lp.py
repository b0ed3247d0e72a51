"""Nemotron-H training benchmark (token sequences, one program per chip)

The sibling of ``benchmark_lfm2_lp.py`` and ``benchmark_qwen3_next_lp.py``
for the hybrid whose every layer is one mixer: Mamba-2 state-space layers,
sigmoid-routed squared-ReLU expert layers with a shared expert, and
grouped-query attention without a positional embedding
(``mpi4dl_tpu/models/nemotron_h.py``): the shared CLI plus ``--model-config``
(the model's published ``config.json`` keys, or a chip's share of a
deployment of it) and ``--sequence-length``; the same ``build_config`` /
``make_trainer`` / ``run_training`` walk. A sample is one sequence, so the
rates printed are sequences a second.

    # the tiny cut, on the CPU
    JAX_PLATFORMS=cpu python benchmarks/layer_parallelism/benchmark_nemotron_h_lp.py \
        --model-config benchmarks/layer_parallelism/nemotron_h_tiny.json \
        --sequence-length 80 --batch-size 2 --max-steps 3 -v
    # one chip's share of Nemotron-Labs-TwoTower-30B-A3B's tower over sixteen chips
    python benchmarks/layer_parallelism/benchmark_nemotron_h_lp.py \
        --model-config chipbench/configs/nemotron_twotower_30b_a3b_share16.json \
        --batch-size 2 --precision bf16 --max-steps 20 -v
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")
)

from common import build_config, build_nemotron_h, make_trainer, run_training, token_model_args


def main():
    args = token_model_args(sys.argv[1:])
    cfg = build_config(args, spatial=False)
    cells, plain = build_nemotron_h(args, cfg)
    trainer, _ = make_trainer(args, cfg, cells, plain)
    run_training(args, trainer, tag="benchmark_nemotron_h_lp")


if __name__ == "__main__":
    main()
