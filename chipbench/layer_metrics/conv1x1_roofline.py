"""Share of the matrix units' peak the 1x1 convolutions and dense layers
reach: the class's least FLOPs a step (3 x the forward FLOPs of the
reference's 1x1 convolutions and matrix products, counted on the reference's
forward jaxpr by ``harness/step_classes.conv_class_flops``; a chip's share
under spatial parallelism) / the chip's peak bf16 FLOP/s / ``conv1x1_ms``."""

from chipbench.harness import step_classes


def read(context):
    return step_classes.roofline_pct(context, "conv1x1")
