"""Time one expert cell of each token cell alone on the chip under the cell's
checkpoint, by what the checkpoint keeps of the expert layer's forward: what
the replay of ``ops/sequence.ExpertFFN`` costs, and what each set of kept
values buys for its bytes.

    chiprun --chips 1 -- python scripts/time_expert_replay.py [<cell> ...]

For every cell of ``BENCHMARK.json`` named (all four token cells when none
is), the model is built from the cell's configuration as its entry point
builds it (bfloat16 cells, float32 parameters) and its first expert cell (the
layer's whole cell: norm, mixer where the layer has one, experts, residual)
runs as value and gradient of ``sum(out * ct)`` over parameters and input on
a random input of the cell's ``[batch, rows, hidden]``, under three
checkpoints:

    ``bare``    ``jax.checkpoint`` with no policy: the replay runs the whole
                forward, a fused kernel's call too where the cell has one
                (LFM2's and Qwen3-Next's first expert cells: ``bare -
                narrow`` then holds what PR 44 took out as well);
    ``narrow``  every named value kept but those as wide as the pair rows x
                hidden (the gathered rows, the third product's output);
    ``cell``    ``train._cell_ckpt``: every named value kept.

The two in the middle are policies of this script over the one name the
program gives (``config.KERNEL_RESIDUAL``), told apart by a value's shape: the
program has no switch. A line a (cell, checkpoint): ``ms`` (host clock around
the jitted call, min and median of five) and the compiled program's
temporaries, so ``bare - narrow`` and ``narrow - cell`` stand in milliseconds
beside the MiB they hold from forward to backward.

Parameters are drawn normal at deviation 0.02, so the router's choices are
near even, as on the benchmark's fresh weights.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
from jax._src.ad_checkpoint import name_p

from chipbench.harness import spec
from mpi4dl_tpu.config import KERNEL_RESIDUAL
from mpi4dl_tpu.train import _cell_ckpt
from time_delta_rule import ms  # the sibling script's clock
from time_mixer_parts import drawn_params, layer_apply, model_cells


def _narrow(hidden):
    """The policy that keeps a named value unless it is a matrix of rows
    ``hidden`` wide (the two over the pair rows: every other named value is
    as wide as the experts, or of another rank)."""
    def policy(prim, *avals, **params):
        return (prim is name_p and params["name"] == KERNEL_RESIDUAL
                and not (len(avals[0].shape) == 2 and avals[0].shape[1] == hidden))
    return policy


def main(argv=None):
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind, "platform": device.platform,
                      "jax": jax.__version__}), flush=True)
    names = list(argv if argv is not None else sys.argv[1:]) or [
        w["name"] for w in spec.benchmark()["workloads"]
        if "model_type" in spec.Cell(w["name"]).model]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    for name in names:
        cell = spec.Cell(name)
        cells, kinds = model_cells(cell)
        index = next(i for i, kind in enumerate(kinds) if kind.startswith("moe_"))
        layer = cells[index]
        hidden = int(cell.model["hidden_size"])
        copies = 2 if "block_length" in cell.model else 1
        shape = (int(cell.traffic["batch_size"]),
                 copies * int(cell.traffic["sequence_length"]), hidden)
        key_x, key_ct = jax.random.split(jax.random.PRNGKey(7 + index))
        x = jax.random.normal(key_x, shape, jnp.bfloat16)
        ct = jax.random.normal(key_ct, shape, jnp.float32)
        params, apply = drawn_params(layer, index, x), layer_apply(layer)
        for kept, ckpt in (
                ("bare", jax.checkpoint),
                ("narrow", lambda fn: jax.checkpoint(fn, policy=_narrow(hidden))),
                ("cell", _cell_ckpt())):
            step = jax.jit(jax.value_and_grad(
                lambda p, h, ct: jnp.sum(ckpt(apply)(p, h).astype(jnp.float32) * ct),
                argnums=(0, 1)))
            line = {"cell": name, "layer": kinds[index], "index": index, "kept": kept}
            try:
                memory = step.lower(params, x, ct).compile().memory_analysis()
                line["temp_mib"] = round(memory.temp_size_in_bytes / 2**20, 1)
                line["ms_min"], line["ms_median"] = ms(step, params, x, ct)
            except Exception as error:  # what the compiler refuses is a line too
                line["error"] = f"{type(error).__name__}: {str(error)[:600]}"
            print(json.dumps(line), flush=True)
            with open(os.path.join(ROOT, "chiprun_out", "time_expert_replay.jsonl"), "a") as out:
                out.write(json.dumps(line) + "\n")
        del params, x, ct


if __name__ == "__main__":
    main()
