"""AmoebaNet-D SP+GEMS(+PP) benchmark

TPU rebuild of reference ``benchmarks/gems_master_with_spatial_parallelism/benchmark_amoebanet_gems_master_with_sp.py``: same CLI flags
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
    n_cells = len(build_amoebanet(args, cfg)[1])
    from mpi4dl_tpu.parallel.pipeline import PipelineTrainer

    n_spatial = (
        PipelineTrainer.spatial_cell_count(n_cells, cfg) if cfg.spatial_size else 0
    )
    built = build_amoebanet(args, cfg, spatial_cells=n_spatial)
    n_override = built[2] if len(built) == 3 else None
    cells, plain = built[0], built[1]
    trainer, _ = make_trainer(
        args, cfg, cells, plain, n_spatial=n_override, gems=True
    )
    run_training(args, trainer, tag="benchmark_amoebanet_gems_master_with_sp")


if __name__ == "__main__":
    main()
