"""Peak trainable resolution per chip — the BASELINE.json capability metric.

Walks image sizes upward (powers of two) for a model family and reports the
largest resolution whose full training step (fwd + bwd + update) compiles
and runs on one chip, with throughput at each size. The reference frames
this as "spatial parallelism trains very-high-res images that DP cannot"
(README.md:6, DP_MP_SP_Vs_Memory.png); on TPU the single-chip ceiling is
set by HBM and the remat policy, and the multi-chip SP path raises it by
tiling H/W over the mesh.

Usage: python scripts/peak_pixels.py [--model resnet|amoebanet] [--batch 1]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def try_size(model: str, size: int, batch: int, remats) -> tuple[float, str] | str:
    import numpy as np

    from mpi4dl_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.config import ParallelConfig
    from mpi4dl_tpu.train import Trainer
    from mpi4dl_tpu.utils import get_depth

    dtype = jnp.bfloat16 if jax.devices()[0].platform == "tpu" else jnp.float32
    if model == "resnet":
        from mpi4dl_tpu.models.resnet import get_resnet_v2

        layout = "packed" if dtype == jnp.bfloat16 else "nhwc"
        cells = get_resnet_v2(
            depth=get_depth(2, 12), num_classes=10, pool_kernel=size // 4,
            layout=layout, dtype=dtype,
        )
    else:
        from mpi4dl_tpu.models.amoebanet import amoebanetd

        cells = amoebanetd(
            num_classes=10, num_layers=18, num_filters=416, dtype=dtype
        )
    cfg = ParallelConfig(
        batch_size=batch, split_size=1, spatial_size=0, image_size=size
    )
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, size, size, 3)), dtype)
    y = jnp.asarray(rng.integers(0, 10, size=(batch,)), jnp.int32)
    last_err = "no remat policy attempted"
    for remat in remats:
        try:
            tr = Trainer(cells, num_spatial_cells=0, config=cfg, remat=remat)
            xs, ys = tr.shard_batch(x, y)
            state = tr.init(jax.random.PRNGKey(0), x.shape, dtype=dtype)
            state, m = tr.train_step(state, xs, ys)
            float(m["loss"])  # force real execution (see bench.py note)
            t0 = time.perf_counter()
            for _ in range(3):
                state, m = tr.train_step(state, xs, ys)
            float(m["loss"])
            return batch * 3 / (time.perf_counter() - t0), remat
        except Exception as e:  # noqa: BLE001 — probe must keep walking
            last_err = f"{remat}: {type(e).__name__}: {str(e)[:160]}"
    return last_err


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet", choices=["resnet", "amoebanet"])
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--start", type=int, default=1024)
    ap.add_argument("--max", type=int, default=16384)
    args = ap.parse_args()
    peak = None
    size = args.start
    while size <= args.max:
        if size >= 4096:
            # Straight to the anchored-quadratic tier: scanlog's live set
            # is a confirmed OOM at 4096 (docs/PERF.md round 5) and its
            # doomed compile costs ~10 uncacheable minutes per size.
            remats = ["scanq"]
        elif size >= 3072:
            # Whole-model logarithmic recursion (fits and is 3.7x faster
            # than scanq at 3072), then the anchored-quadratic tier whose
            # live boundary set is O(1) per run; leaner policies would
            # waste a multi-minute doomed compile per size here.
            remats = ["scanlog", "scanq"]
        elif args.model == "amoebanet":
            remats = ["scan_save", "scan"]
        else:
            remats = ["cell_save", "scan_save", "scan"]
        # One size per SUBPROCESS: a failed size must not kill the whole
        # walk (the parent stays off JAX, so each child gets the chip).
        import subprocess

        code = (
            "import sys; sys.path.insert(0, {root!r});"
            "from scripts.peak_pixels import try_size;"
            "r = try_size({model!r}, {size}, {batch}, {remats!r});"
            "print('RESULT', repr(r))"
        ).format(
            root=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            model=args.model, size=size, batch=args.batch, remats=remats,
        )
        env = dict(os.environ)
        if "scanq" in remats:
            # Measured scanq default: grant the late small-carry runs
            # stored carries (+67% at 4096; 6000 MB OOMs — docs/PERF.md
            # round 5). Explicit env wins.
            env.setdefault("MPI4DL_TPU_SCANQ_STORE_MB", "3000")
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=3600, env=env,
        )
        line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")]
        if not line:
            print(f"{size}px: CRASH ({proc.returncode})", flush=True)
            break
        result = eval(line[-1][len("RESULT "):])  # noqa: S307 — own output
        if isinstance(result, tuple):
            ips, remat = result
            px = size * size
            print(
                f"{size}px: OK {ips:.3f} img/s ({remat}, "
                f"{px / 1e6:.0f} Mpx/image)", flush=True,
            )
            peak = size
            size *= 2
        else:
            print(f"{size}px: FAIL {result}", flush=True)
            break
    print(f"peak trainable: {peak}px at bs={args.batch}" if peak else "none")


if __name__ == "__main__":
    main()
