"""Expert layers of the newest step that computed the prefix of their sorted
token-expert pair rows alone: ``moe_narrow_layers``' reader (the step's own
count, ``trainer.last_metrics``) under the name the SDAR cell reports. A
layer that holds 16 of 128 experts always computes a quarter of the rows
(twice its even share) and the rest only when its own count of held pairs
overflows that; with even routing all eight layers stay on the prefix. None
from a program whose step does not count it."""

from chipbench.harness import spec

read = spec.metric_reader("layer_metrics", "moe_narrow_layers")
