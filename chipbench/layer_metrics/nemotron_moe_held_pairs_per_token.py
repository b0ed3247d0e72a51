"""Token-expert pairs the held experts computed in the newest step, per
token and per expert layer: ``moe_pairs_per_token``'s reader (the step's own
count ``moe_pairs`` / (tokens in the batch x expert layers, the kinds that
start with ``moe_``)) under the name the Nemotron-H cell reports. Expected:
``num_experts_per_tok x held / published`` (0.375 for 8 of 128 experts and 6
a token); it moves with the router's choices, which the FLOP count behind
``mfu_pct`` does not read. None from a program whose step does not count."""

from chipbench.harness import spec

read = spec.metric_reader("layer_metrics", "moe_pairs_per_token")
