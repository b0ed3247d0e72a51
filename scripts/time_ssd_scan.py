"""Time Mamba-2's chunked scan alone on the chip, at the Nemotron-H cell's
shapes: the plain path (``ops/sequence._chunked_scan`` under ``lax.map``)
and the kernels of ``ops/ssd_scan_pallas.py``, forward / gradient (the
forward that keeps the backward's residuals, then the backward), with the
kernels' distance from the plain path (value and four gradients, relative
L2). The table in ``ops/ssd_scan_pallas.py``'s docstring is this script's
output (PR 42).

    chiprun --chips 1 -- python scripts/time_ssd_scan.py plain kernels turned

A standalone call holds what the step does not: ``x``, the cotangent and both
results come and go as ``[B, S, G, R, P]`` arrays in the layout a jit's
arguments have, so the ``kernels`` line carries their turns to the kernels'
positions-minor layout and back. ``turned`` gives and takes them as ``[B, G,
R P, S]``, the layout the step's compiler holds those arrays in already: the
kernels and the running sums alone.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from mpi4dl_tpu.ops import sequence
from mpi4dl_tpu.ops import ssd_scan_pallas as ssp
from time_delta_rule import gap, ms  # the sibling script's clock and relative L2

B, S, G, R, P, N, CHUNK = 2, 8192, 8, 8, 64, 128, 128


def inputs(seed=0, batch=B, length=S):
    """As ``Mamba2`` makes them on the benchmark's fresh model: ``A_log``
    normal of deviation 2, ``dt = softplus(. + dt_bias)`` with both normal,
    ``g = dt A``, ``x = dt silu(.)``, ``B, C = silu(.)``; and a cotangent
    for the output."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    a = -jnp.exp(2.0 * jax.random.normal(keys[0], (G, R)))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, length, G, R))
                         + jax.random.normal(keys[2], (G, R)))
    x = (jax.nn.silu(jax.random.normal(keys[3], (batch, length, G, R, P)))
         * dt[..., None]).astype(jnp.bfloat16)
    b, c = (jax.nn.silu(jax.random.normal(key, (batch, length, G, N))).astype(jnp.bfloat16)
            for key in keys[4:6])
    ct = jax.random.normal(keys[6], x.shape).astype(jnp.bfloat16)
    return (x, dt * a, b, c), ct


def passes(scan):
    """``(forward, gradient of sum(out * ct) over the four inputs)``, jitted."""
    return (jax.jit(scan),
            jax.jit(lambda args, ct: jax.grad(
                lambda *a: jnp.sum(scan(*a).astype(jnp.float32) * ct.astype(jnp.float32)),
                argnums=(0, 1, 2, 3))(*args)))


def main(argv):
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind, "platform": device.platform,
                      "jax": jax.__version__}), flush=True)
    args, ct = inputs()
    plain = lambda x, g, b, c: lax.map(
        lambda row: sequence._chunked_scan(*row, CHUNK), (x, g, b, c))
    want = wanted = None
    lines = []
    scans = {"plain": plain, "kernels": lambda *a: ssp.scan(*a, CHUNK),
             "turned": lambda *a: ssp.scan_turned(*a, R, CHUNK)}
    for word in argv or ["plain", "kernels", "turned"]:
        line = {"what": word}
        try:
            fwd, grad = passes(scans[word])
            given, given_ct, back = args, ct, lambda x: x
            if word == "turned":
                given, given_ct = (ssp._turned(args[0]),) + args[1:], ssp._turned(ct)
                back = lambda xt: ssp._unturned(xt, R)
            line["fwd_ms"], line["grad_ms"] = ms(fwd, *given), ms(grad, given, given_ct)
            line["layer_ms"] = round(line["fwd_ms"][0] + line["grad_ms"][0], 3)
            out, (dx, *rest) = fwd(*given), grad(given, given_ct)
            if word == "plain":
                want, wanted = out, (dx, *rest)
            elif want is not None:
                line["out_gap"] = gap(back(out), want)
                line["grad_gaps"] = [round(gap(a, b), 6) for a, b in zip((back(dx), *rest), wanted)]
        except Exception as error:  # what the compiler refuses is a line of the table too
            line["error"] = f"{type(error).__name__}: {str(error)[:600]}"
        lines.append(line)
        print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/time_ssd_scan.jsonl", "a") as out:
        for line in lines:
            out.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main(sys.argv[1:])
