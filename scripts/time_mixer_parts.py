"""Time one layer of each kind of the four token cells alone on the chip, at
its cell's shapes, and split it by part: the "alone" half of "time the shapes
alone, predict, then pair" for the parts ``chipbench/harness/token_parts.py``
names (projections, convolution, gates and norms, recurrence, q/k
preparation, attention core, router, dispatch, expert products, block).

    chiprun --chips 1 -- python scripts/time_mixer_parts.py [<cell> ...] [--dump <dir>]

For every cell of ``BENCHMARK.json`` named (all four token cells when none
is), the model is built from the cell's configuration as its entry point
builds it (bfloat16 cells, float32 parameters) and one layer cell of each
kind (a Mamba-2 layer, an expert layer, an attention layer, ...; under the
cell's own ``mpi4dl_cell<NN>`` so the reader finds it) runs as two programs of
its own on a random input of the cell's ``[batch, rows, hidden]``: ``fwd`` and
``grad`` (the forward that keeps the backward's residuals, then the backward,
of ``sum(out * ct)`` over parameters and input). Each is timed on the host's
clock (min and median of five) and traced for three runs; the trace is split
by part with the reader the benchmark uses, on the program's own compiled
text. A line a (layer, part): ``fwd_ms``, ``grad_ms`` and their sum
``alone_ms``, which stands against what the part takes of that model cell in
the step (forward, recomputed forward, backward): ``in_step_ms``, read from
the dump ``--dump`` names (``chipbench/tools/token_table.py --dump``'s, by
default ``chiprun_out/parts_<cell>``) where there is one. Alone, a layer's
compiler sees no neighbour: what differs from the step's reading is layout
and fusion across parts, not arithmetic.

Parameters are drawn normal at deviation 0.02, so the router's choices are
near even, as on the benchmark's fresh weights.
"""

import argparse
import collections
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp

from chipbench.harness import spec, token_parts, xtrace
from mpi4dl_tpu.train import cell_scope
from time_delta_rule import ms  # the sibling script's clock

RUNS = 3  # traced runs a program


def model_cells(cell):
    """The program's cell list for a benchmark cell's configuration, and the
    kind of every layer (the reference's, which names them)."""
    kind = cell.model["model_type"]
    name = {"lfm2_moe": "lfm2", "sdar_moe": "sdar"}.get(kind, kind)  # module and builder
    cells = getattr(importlib.import_module("mpi4dl_tpu.models." + name), name)(
        cell.model, jnp.bfloat16)
    kinds = importlib.import_module(cell.config["reference"]["module"]).kinds(cell.model)
    return cells, kinds


def layer_apply(layer, index=None):
    """``apply(params, h)`` of one layer cell (under its own name where
    ``index`` says which), the counts it sows dropped."""
    collection = getattr(layer, "counters", None)
    mutable = [collection] if collection else False

    def apply(params, h):
        if index is None:
            out = layer.apply(params, h, mutable=mutable)
        else:
            with jax.named_scope(cell_scope(index)):
                out = layer.apply(params, h, mutable=mutable)
        return out[0] if mutable else out

    return apply


def drawn_params(layer, index, x):
    """The layer's parameters, normal at deviation 0.02."""
    shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    leaves, tree = jax.tree.flatten({"params": shapes["params"]})
    keys = jax.random.split(jax.random.PRNGKey(index), len(leaves))
    return jax.tree.unflatten(tree, [
        0.02 * jax.random.normal(k, a.shape, a.dtype) for k, a in zip(keys, leaves)])


def programs(layer, index, x):
    """``(parameters, fwd, grad)`` of one layer cell under its own name."""
    apply = layer_apply(layer, index)
    fwd = jax.jit(apply)
    grad = jax.jit(jax.grad(
        lambda p, h, ct: jnp.sum(apply(p, h).astype(jnp.float32) * ct), argnums=(0, 1)))
    return drawn_params(layer, index, x), fwd, grad


def traced_parts(fn, args, name):
    """``{(mixer, part): ms a run}`` of a jitted program from its device
    trace, split under its own compiled text."""
    logdir = os.path.join(ROOT, ".cache", "time_mixer_parts", name)
    jax.block_until_ready(fn(*args))
    with xtrace.capture(logdir):
        for _ in range(RUNS + 1):
            jax.block_until_ready(fn(*args))
    plane = xtrace.device_planes(xtrace.load(logdir))[0]
    runs = sorted(plane.line(xtrace.MODULES_LINE).events, key=lambda ev: ev.start_ns)
    window = (runs[-RUNS - 1].start_ns, runs[-1].start_ns)
    events = [ev for ev in plane.line(xtrace.OPS_LINE).events
              if ev.end_ns > window[0] and ev.start_ns < window[1]]
    text = fn.lower(*args).compile().as_text()
    split = token_parts.split_events(
        token_parts.classify(text, head=""), events, window, RUNS)
    out = collections.defaultdict(float)
    for found, v in split.items():
        out[found.mixer or "-", found.part] += v
    return dict(out)


def in_step(path):
    """``{(cell, mixer, part): ms a step}`` from a dump, {} without one."""
    from chipbench.tools import step_table

    if not os.path.exists(os.path.join(path, "events.json.gz")):
        return {}
    text, events, window, steps = step_table.load(path)
    if not token_parts.has_parts(text):
        return {}
    out = collections.defaultdict(float)
    split = token_parts.split_events(token_parts.classify(text), events, window, steps)
    for found, v in split.items():
        out[found.cell, found.mixer or "-", found.part] += v
    return dict(out)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--dump", default=None,
                    help="a token_table.py --dump directory (one cell named)")
    opts = ap.parse_args(argv)
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind, "platform": device.platform,
                      "jax": jax.__version__}), flush=True)
    names = opts.cells or [
        w["name"] for w in spec.benchmark()["workloads"]
        if "model_type" in spec.Cell(w["name"]).model]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    for name in names:
        cell = spec.Cell(name)
        cells, kinds = model_cells(cell)
        step = in_step(os.path.join(ROOT, opts.dump or f"chiprun_out/parts_{name}"))
        copies = 2 if "block_length" in cell.model else 1
        rows = copies * int(cell.traffic["sequence_length"])
        shape = (int(cell.traffic["batch_size"]), rows, int(cell.model["hidden_size"]))
        seen = set()
        for index, (layer, kind) in enumerate(zip(cells, kinds)):
            if index in (0, len(cells) - 1) or kind in seen:
                continue  # the embedding and the head have no mixer
            seen.add(kind)
            line = {"cell": name, "layer": kind, "index": index}
            try:
                key_x, key_ct = jax.random.split(jax.random.PRNGKey(7 + index))
                x = jax.random.normal(key_x, shape, jnp.bfloat16)
                ct = jax.random.normal(key_ct, shape, jnp.float32)
                params, fwd, grad = programs(layer, index, x)
                line["fwd_ms"], line["grad_ms"] = ms(fwd, params, x), ms(grad, params, x, ct)
                forward = traced_parts(fwd, (params, x), f"{name}.{kind}.fwd")
                backward = traced_parts(grad, (params, x, ct), f"{name}.{kind}.grad")
                line["parts"] = [
                    {"mixer": mixer, "part": part,
                     "fwd_ms": round(forward.get((mixer, part), 0.0), 3),
                     "grad_ms": round(backward.get((mixer, part), 0.0), 3),
                     "alone_ms": round(forward.get((mixer, part), 0.0)
                                       + backward.get((mixer, part), 0.0), 3),
                     "in_step_ms": (round(step[f"{index:02d}", mixer, part], 3)
                                    if (f"{index:02d}", mixer, part) in step else None)}
                    for mixer, part in sorted(set(forward) | set(backward))]
                del params, x, ct
            except Exception as error:  # what the compiler refuses is a line too
                line["error"] = f"{type(error).__name__}: {str(error)[:600]}"
            print(json.dumps(line), flush=True)
            with open(os.path.join(ROOT, "chiprun_out", "time_mixer_parts.jsonl"), "a") as out:
                out.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
