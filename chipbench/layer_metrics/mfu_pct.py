"""Model FLOP/s utilisation: the reference model's conv and matmul FLOPs
(3 x forward per image, recomputation and packing not counted) x this
run's images/s / (the cell's chips x the chip's peak bf16 FLOP/s)."""

from chipbench.harness import counting


def read(context):
    session = context["session"]
    per_image = counting.train_flops_per_image(session.ref_cells, session.x_shape[1:])
    peak = context["peaks"]["bf16_flops_per_s"] * context["cell"].chips
    return 100.0 * per_image * context["window_rate"] / peak
