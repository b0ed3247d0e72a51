"""Share of its roofline the causal depthwise convolution's kernels reach: the
least bytes the operator of one step must move / the chip's peak bytes/s / the
kernels' measured time. Least: five passes over a convolution layer's
``[batch, positions, channels]`` array in the configuration's two-byte
activations, whatever implements it: the forward reads ``x`` and writes ``y``,
the backward reads ``x`` and the output's cotangent and writes the input's
(the taps' and the bias' gradients are a few rows). The channels are the
family's published layout's: a Gated DeltaNet layer's ``2 x keys + values``
(``linear_num_key_heads x linear_key_head_dim`` twice and
``linear_num_value_heads x linear_value_head_dim``; the layers of
``num_hidden_layers`` that are not every ``full_attention_interval``-th), a
Mamba-2 layer's ``mamba_num_heads x mamba_head_dim + 2 x n_groups x
ssm_state_size`` (the ``M``s of ``hybrid_override_pattern``). The rows read
again at a block's edge, the pre-activation built again in the backward and
the partial sums of the taps' gradient are executed and not counted, so the
share cannot pass 100%. The kernels do no matrix work: they are bound by
memory bandwidth (some 20 and 50 vector operations an eight-by-128 register of
float32 against 4 KB and 6 KB moved for it), so the bytes are the roofline."""

from chipbench.harness import xtrace

KERNEL = "mpi4dl_causal_conv"
PASSES = 5          # read x, write y; read x and dy, write dx
BYTES = 2           # the configurations' bfloat16 activations


def conv_layers_and_channels(model: dict) -> tuple:
    """``(convolution layers, channels under each)`` from a model's file."""
    if "hybrid_override_pattern" in model:  # Nemotron-H: Mamba-2
        return (str(model["hybrid_override_pattern"]).count("M"),
                int(model["mamba_num_heads"]) * int(model["mamba_head_dim"])
                + 2 * int(model["n_groups"]) * int(model["ssm_state_size"]))
    layers = int(model["num_hidden_layers"])  # Qwen3-Next: Gated DeltaNet
    return (layers - layers // int(model["full_attention_interval"]),
            2 * int(model["linear_num_key_heads"]) * int(model["linear_key_head_dim"])
            + int(model["linear_num_value_heads"]) * int(model["linear_value_head_dim"]))


def least_bytes_per_step(model: dict, traffic: dict) -> float:
    layers, channels = conv_layers_and_channels(model)
    positions = int(traffic["sequence_length"]) * int(traffic["batch_size"])
    return float(PASSES * BYTES * positions * channels * layers)


def read(context):
    seconds = xtrace.kernel_seconds_per_step(context["reduced"], KERNEL)
    if seconds is None:
        return None
    cell = context["cell"]
    least = least_bytes_per_step(cell.model, cell.traffic)
    return 100.0 * (least / context["peaks"]["hbm_bytes_per_s"]) / seconds
