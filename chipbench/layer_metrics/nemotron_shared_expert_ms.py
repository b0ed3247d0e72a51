"""Device milliseconds per step in the shared experts alone (the squared-ReLU
feed-forward of width 3712 every token takes, ungated): the part of
``nemotron_moe_ms`` under ``jax.named_scope("shared_expert")``;
``shared_expert_ms``'s reader under the name the Nemotron-H cell reports.
None from a program whose expert layer has no such scope."""

from chipbench.harness import spec

read = spec.metric_reader("layer_metrics", "shared_expert_ms")
