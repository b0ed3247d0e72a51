"""ResNet SP(+LP/PP) benchmark

TPU rebuild of reference ``benchmarks/spatial_parallelism/benchmark_resnet_sp.py``: same CLI flags
(``torchgems/parser.py:21-143``), same model and parallelism mode, one SPMD
process over the JAX device mesh instead of ``mpirun_rsh`` ranks.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")
)

from common import (
    build_amoebanet,
    build_config,
    build_resnet,
    make_trainer,
    run_training,
)

from mpi4dl_tpu.parser import get_parser


def main():
    args = get_parser().parse_args()
    cfg = build_config(args, spatial=True)
    n_cells = len(build_resnet(args, cfg)[1])
    from mpi4dl_tpu.parallel.pipeline import PipelineTrainer

    n_spatial = (
        PipelineTrainer.spatial_cell_count(n_cells, cfg) if cfg.spatial_size else 0
    )
    built = build_resnet(args, cfg, spatial_cells=n_spatial)
    n_override = built[2] if len(built) == 3 else None
    cells, plain = built[0], built[1]
    trainer, _ = make_trainer(
        args, cfg, cells, plain, n_spatial=n_override, gems=False
    )
    run_training(args, trainer, tag="benchmark_resnet_sp")


if __name__ == "__main__":
    main()
