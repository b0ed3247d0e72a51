"""Share of the matrix units' peak the k x k convolutions reach: the class's
least FLOPs a step (3 x the forward FLOPs of the reference's convolutions
with a window wider than 1x1, counted on the reference's forward jaxpr by
``harness/step_classes.conv_class_flops``; a chip's share under spatial
parallelism; the packed layout's zero taps and recomputation not counted) /
the chip's peak bf16 FLOP/s / ``conv_ms``. Matrix-unit-bound."""

from chipbench.harness import step_classes


def read(context):
    return step_classes.roofline_pct(context, "convkxk")
