"""Token-expert pairs the held experts computed in the newest step, per row
and per expert layer: ``moe_pairs_per_token``'s reader (the step's own count
``moe_pairs`` / (rows in the batch x expert layers, the kinds that start with
``moe_``)) under the name the SDAR cell reports. A row is a token of either
copy: 16,384 a sequence. Expected: ``num_experts_per_tok x held / published``
(1.0 for 16 of 128 experts and 8 a token); it moves with the router's
choices, and here with the noise level: the masked rows of the noisy copy
enter as one token and route alike. None from a program whose step does not
count."""

from chipbench.harness import spec

read = spec.metric_reader("layer_metrics", "moe_pairs_per_token")
