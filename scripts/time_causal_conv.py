"""Time the causal depthwise convolution with its bias and SiLU alone on the
chip, at the two cells' shapes: the plain path (``ops/sequence.
causal_depthwise_conv1d``, the bias and ``nn.silu`` as ``GatedDeltaNet`` and
``Mamba2`` apply them) and the kernels of ``ops/causal_conv_pallas.py``,
forward / backward (the pull-back of a cotangent alone: the forward call has
no reader there and is not run, as in a step whose "cell" remat kept its
output), with the kernels' distance from the plain path (value and gradients,
relative L2). The table in ``ops/causal_conv_pallas.py``'s docstring is this
script's output (PR 47).

    chiprun --chips 1 -- python scripts/time_causal_conv.py plain kernels

A word ``kernels:<rows>x<lanes>`` times the kernels under another block of a
grid step than ``causal_conv_pallas.plan_for``'s, ``kernels:<rows>x<lanes>:<trip>``
also at another count of positions a loop trip than ``causal_conv_pallas.ROWS``.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import flax.linen as nn
import jax
import jax.numpy as jnp

from mpi4dl_tpu.ops import causal_conv_pallas as ccp
from mpi4dl_tpu.ops import sequence
from time_delta_rule import gap, ms  # the sibling script's clock and relative L2

B, S, TAPS = 2, 8192, 4
# the channels under the convolution and whether it has a bias: Qwen3-Next's
# 2 x 16 x 128 + 32 x 128 (``GatedDeltaNet``), Nemotron-H's 64 x 64 + 2 x 8 x
# 128 (``Mamba2``)
SHAPES = {"qwen3_next": (8192, False), "nemotron_h": (6144, True)}
HBM_BYTES_PER_S = 819e9
TRIP, WIDTH = ccp.ROWS, ccp.WIDTH


def inputs(channels, bias, seed=0):
    """A projection's output (unit normal), a fresh model's taps (LeCun
    normal over the taps) and a bias, and a cotangent for the output."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(keys[0], (B, S, channels)).astype(jnp.bfloat16)
    kernel = (jax.random.normal(keys[1], (TAPS, channels)) * TAPS ** -0.5).astype(jnp.bfloat16)
    ct = jax.random.normal(keys[3], x.shape).astype(jnp.bfloat16)
    args = (x, kernel)
    if bias:
        args += ((0.1 * jax.random.normal(keys[2], (channels,))).astype(jnp.bfloat16),)
    return args, ct


def plain(x, kernel, bias=None):
    y = sequence.causal_depthwise_conv1d(x, kernel)
    return nn.silu(y if bias is None else y + bias)


def passes(conv):
    """``(forward, the cotangents of every input from the output's)``, jitted."""
    return jax.jit(conv), jax.jit(lambda args, ct: jax.vjp(conv, *args)[1](ct))


def main(argv):
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind, "platform": device.platform,
                      "jax": jax.__version__}), flush=True)
    lines = []
    for shape, (channels, bias) in SHAPES.items():
        args, ct = inputs(channels, bias)
        passed = B * S * channels * 2  # one pass over the array, bytes
        want = wanted = None
        for word in argv or ["plain", "kernels"]:
            line = {"shape": shape, "what": word}
            try:
                conv = plain
                if word != "plain":
                    plan = ccp.plan_for(args[0].shape)
                    if ":" in word:
                        plan = ccp.Plan(*map(int, word.split(":")[1].split("x")))
                    fields = word.split(":")
                    ccp.ROWS = int(fields[2]) if len(fields) > 2 else TRIP
                    ccp.WIDTH = int(fields[3]) if len(fields) > 3 else WIDTH
                    jax.clear_caches()  # a trip's rows are no argument of the jitted calls
                    conv = lambda *a, plan=plan: ccp.conv_silu(*a, plan=plan)  # noqa: E731
                    line["plan"] = list(plan)
                fwd, bwd = passes(conv)
                line["fwd_ms"], line["bwd_ms"] = ms(fwd, *args), ms(bwd, args, ct)
                line["layer_ms"] = round(line["fwd_ms"][0] + line["bwd_ms"][0], 3)
                # the least a pass moves: read x, write y; read x and dy, write dx
                line["fwd_hbm_pct"] = round(100 * 2 * passed / HBM_BYTES_PER_S / (1e-3 * line["fwd_ms"][0]), 1)
                line["bwd_hbm_pct"] = round(100 * 3 * passed / HBM_BYTES_PER_S / (1e-3 * line["bwd_ms"][0]), 1)
                out, grads = fwd(*args), bwd(args, ct)
                if word == "plain":
                    want, wanted = out, grads
                elif want is not None:
                    line["out_gap"] = round(gap(out, want), 6)
                    line["grad_gaps"] = [round(gap(a, b), 6) for a, b in zip(grads, wanted)]
            except Exception as error:  # what the compiler refuses is a line of the table too
                line["error"] = f"{type(error).__name__}: {str(error)[:600]}"
            lines.append(line)
            print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/time_causal_conv.jsonl", "a") as out:
        for line in lines:
            out.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main(sys.argv[1:])
