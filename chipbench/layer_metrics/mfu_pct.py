"""Model FLOP/s utilisation: the model's training FLOPs per sample
(``Session.flops_per_sample``: the configuration's own count where its
reference module gives one, else 3 x the reference's forward conv and
matmul FLOPs; recomputation and packing not counted) x this run's samples/s
/ (the cell's chips x the chip's peak bf16 FLOP/s)."""


def read(context):
    peak = context["peaks"]["bf16_flops_per_s"] * context["cell"].chips
    return 100.0 * context["session"].flops_per_sample * context["window_rate"] / peak
