"""Time the gated delta rule alone on the chip, at the Qwen3-Next cell's
shapes: the plain path (``ops/sequence._chunked_rule``) and the kernels of
``ops/delta_rule_pallas.py``, forward / gradient (the forward that keeps the
backward's residuals, then the backward), with the kernels' distance from
the plain path (value and five gradients, relative L2) and of their systems'
inverses from a float64 inverse on the host. The table in
``ops/delta_rule_pallas.py``'s docstring is this script's output (PR 38: the
plans that lost were timed by it on that PR's earlier revisions of the
module, which took a plan as an argument).

    chiprun --chips 1 -- python scripts/time_delta_rule.py plain kernels

Further words are diagnostics with wrong or lower arithmetic: ``no_inverse``
(the kernels with ``T = I - A``: what all but the inverse costs) and
``default_precision`` (the inverse's products at the matrix unit's default
precision for float32).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mpi4dl_tpu.ops import delta_rule_pallas as drp
from mpi4dl_tpu.ops import sequence

B, S, H, R, D, E, CHUNK = 2, 8192, 16, 2, 128, 128, sequence.RULE_CHUNK


def inputs(seed=0, batch=B, length=S):
    """As ``GatedDeltaNet`` makes them on a fresh model: unit ``q, k``,
    ``g = -exp(A_log) softplus(a + 1)`` with ``A_log`` normal of deviation
    2, ``beta`` a sigmoid; and a cotangent for the output."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)

    def unit(t):
        return t * lax.rsqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)

    q = (unit(jax.random.normal(keys[0], (batch, length, H, D))) * D ** -0.5).astype(jnp.bfloat16)
    k = unit(jax.random.normal(keys[1], (batch, length, H, D))).astype(jnp.bfloat16)
    v = jax.random.normal(keys[2], (batch, length, H, R, E)).astype(jnp.bfloat16)
    a_log = 2.0 * jax.random.normal(keys[3], (H, R))
    g = -jnp.exp(a_log) * jax.nn.softplus(jax.random.normal(keys[4], (batch, length, H, R)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (batch, length, H, R)))
    ct = jax.random.normal(keys[6], v.shape).astype(jnp.bfloat16)
    return (q, k, v, g, beta), ct


def ms(fn, *args, trips=5):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(trips):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - start))
    return round(min(times), 3), round(sorted(times)[len(times) // 2], 3)


def gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def passes(rule):
    """``(forward, gradient of sum(out * ct) over the five inputs)``, jitted."""
    return (jax.jit(rule),
            jax.jit(lambda args, ct: jax.grad(
                lambda *a: jnp.sum(rule(*a).astype(jnp.float32) * ct.astype(jnp.float32)),
                argnums=(0, 1, 2, 3, 4))(*args)))


def inverse_error():
    """The kept inverses of 256 chunk-heads against float64 on the host."""
    (q, k, v, g, beta), _ = inputs(seed=1, batch=1, length=512)
    total = jnp.cumsum(g.reshape(1, -1, CHUNK, H, R), axis=2).reshape(g.shape)
    _, _, solves = jax.jit(lambda *a: drp.forward(*a, CHUNK, True))(q, k, v, total, beta)
    solves = np.asarray(solves, np.float64)                       # [1, H, 8, R, C, C]
    k64 = np.asarray(k.astype(jnp.float32), np.float64).reshape(8, CHUNK, H, D)
    total64 = np.asarray(total, np.float64).reshape(8, CHUNK, H, R)
    beta64 = np.asarray(beta, np.float64).reshape(8, CHUNK, H, R)
    worst = 0.0
    for n in range(8):
        for h in range(H):
            kk = k64[n, :, h] @ k64[n, :, h].T
            for r in range(R):
                t = total64[n, :, h, r]
                decay = np.exp(np.minimum(t[:, None] - t[None, :], 0.0))
                system = np.tril(beta64[n, :, h, r][:, None] * kk * decay, -1)
                worst = max(worst, gap(solves[0, h, n, r], np.linalg.inv(np.eye(CHUNK) + system)))
    return worst


def main(argv):
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind, "platform": device.platform,
                      "jax": jax.__version__}), flush=True)
    args, ct = inputs()
    plain_fwd, plain_grad = passes(sequence._chunked_rule)
    want = wanted = None
    lines = []
    for word in argv or ["plain", "kernels"]:
        line = {"what": word}
        exact, inverse = drp._exact, drp.unit_lower_inverse
        try:
            if word == "plain":
                fwd, grad = plain_fwd, plain_grad
            else:
                if word == "no_inverse":
                    drp.unit_lower_inverse = lambda a: jnp.where(
                        drp._iota(CHUNK, 0) == drp._iota(CHUNK, 1), 1.0, 0.0) - a
                elif word == "default_precision":
                    drp._exact = lambda a, b, form="nn": drp.dot(a, b, form)
                elif word != "kernels":
                    raise ValueError(word)
                jax.clear_caches()  # ``forward`` / ``backward`` are jitted: trace them anew
                fwd, grad = passes(lambda *a: drp.rule(*a, CHUNK))
            line["fwd_ms"], line["grad_ms"] = ms(fwd, *args), ms(grad, args, ct)
            line["layer_ms"] = round(line["fwd_ms"][0] + line["grad_ms"][0], 3)
            if word == "plain":
                want, wanted = fwd(*args), grad(args, ct)
            else:
                if want is not None:
                    line["out_gap"] = gap(fwd(*args), want)
                    line["grad_gaps"] = [round(gap(a, b), 6) for a, b in zip(grad(args, ct), wanted)]
                if word != "no_inverse":
                    line["inverse_gap"] = inverse_error()
        except Exception as error:  # what the compiler refuses is a line of the table too
            line["error"] = f"{type(error).__name__}: {str(error)[:600]}"
        drp._exact, drp.unit_lower_inverse = exact, inverse
        lines.append(line)
        print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/time_delta_rule.jsonl", "a") as out:
        for line in lines:
            out.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main(sys.argv[1:])
