"""Time causal grouped-query attention alone on the chip, at the token cells'
shapes: the plain path (``ops/sequence.blocked_causal_attention``, at the
cell's rows a block) and the kernels of ``ops/attention_pallas.py`` under
the shape's own plan and under any other, forward / backward (each jitted on
the cell's layout, so each figure holds its own layout changes) / a layer's
three passes (two forwards and the backward), with the kernels' distance
from the plain path (output and three cotangents, relative L2). The table in
``ops/attention_pallas.py``'s docstring is this script's output (PR 40).

    chiprun --chips 1 -- python scripts/time_attention.py qwen3_next nemotron_h lfm2

A word is a shape's name, or ``<shape>:fwd:<heads>:<block>`` /
``<shape>:bwd:<heads>:<block>`` for one pass under another plan. (The
table's "buffered once" lines were timed by this script on a copy of the
module whose backward gave its whole-sequence blocks
``pipeline_mode=pl.Buffered(1)``.)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from mpi4dl_tpu.ops import attention_pallas, sequence

# name: (q's shape [B, S, KV, G, D], the plain path's rows a block in the cell)
SHAPES = {
    "lfm2": ((1, 8192, 8, 4, 64), 512),
    "qwen3_next": ((2, 8192, 2, 8, 256), 512),
    "nemotron_h": ((2, 8192, 2, 16, 128), 256),
}


def inputs(shape, seed=0):
    """``q, k, v`` and the output's cotangent, bfloat16 normals."""
    b, s, kv, _, d = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [jax.random.normal(key, dims, jnp.float32).astype(jnp.bfloat16)
            for key, dims in zip(keys, (shape, (b, s, kv, d), (b, s, kv, d), shape))]


def ms(fn, *args, trips=5):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(trips):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - start))
    return round(min(times), 3)


def gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return round(float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)), 6)


def plain_passes(block):
    """``(forward -> (out, lse), backward of the residuals and d_out)``."""
    return (jax.jit(lambda q, k, v: sequence._attention_forward(q, k, v, block)),
            jax.jit(lambda q, k, v, out, lse, d_out: sequence._attention_bwd(
                block, (q, k, v, out, lse), d_out)))


def kernel_passes(plan):
    return (jax.jit(lambda q, k, v: attention_pallas.forward(q, k, v, plan)),
            jax.jit(lambda q, k, v, out, lse, d_out: attention_pallas.backward(
                q, k, v, out, lse, d_out, plan)))


def main(argv):
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind, "platform": device.platform,
                      "jax": jax.__version__}), flush=True)
    wanted = {}
    lines = []
    for word in argv or list(SHAPES):
        name, *other = word.split(":")
        shape, rows = SHAPES[name]
        q, k, v, d_out = inputs(shape)
        if name not in wanted:  # the plain path, once a shape
            fwd, bwd = plain_passes(rows)
            out, lse = fwd(q, k, v)
            wanted[name] = (out, lse) + tuple(bwd(q, k, v, out, lse, d_out))
            line = {"what": f"{name} plain, {rows} rows", "fwd_ms": ms(fwd, q, k, v),
                    "bwd_ms": ms(bwd, q, k, v, out, lse, d_out)}
            line["layer_ms"] = round(2 * line["fwd_ms"] + line["bwd_ms"], 3)
            lines.append(line)
            print(json.dumps(line), flush=True)
        out, lse, *grads = wanted[name]
        plan = attention_pallas.plan_for(shape, k.shape, q.dtype)
        line = {"what": word, "plan": plan}
        try:
            if other:
                which, heads, block = other[0], int(other[1]), int(other[2])
                plan = attention_pallas.Plan(block, heads)
                line["plan"] = plan
                fwd, bwd = kernel_passes(plan)
                if which == "fwd":
                    line["fwd_ms"] = ms(fwd, q, k, v)
                    line["gaps"] = [gap(fwd(q, k, v)[0], out)]
                else:
                    line["bwd_ms"] = ms(bwd, q, k, v, out, lse, d_out)
                    line["gaps"] = [gap(a, b) for a, b in zip(bwd(q, k, v, out, lse, d_out), grads)]
            else:
                fwd, bwd = kernel_passes(plan)
                line["fwd_ms"], line["bwd_ms"] = ms(fwd, q, k, v), ms(bwd, q, k, v, out, lse, d_out)
                line["layer_ms"] = round(2 * line["fwd_ms"] + line["bwd_ms"], 3)
                line["gaps"] = [gap(fwd(q, k, v)[0], out)] + [
                    gap(a, b) for a, b in zip(bwd(q, k, v, out, lse, d_out), grads)]
        except Exception as error:  # what the compiler refuses is a line of the table too
            line["error"] = f"{type(error).__name__}: {str(error)[:400]}"
        lines.append(line)
        print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/time_attention.jsonl", "a") as out_file:
        for line in lines:
            out_file.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main(sys.argv[1:])
