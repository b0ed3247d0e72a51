"""Subprocess half of the tiled stitch-exactness suite: run on a
SINGLE-device CPU backend — the tiled predictor's actual deployment
topology (one chip serving huge images) — and compare the tile-streaming
forward against the monolithic forward across tile grids and model
families: bit for bit, and by largest absolute difference. Prints one
JSON verdict line.

Every kept output element sees exactly the bytes the monolithic forward
saw, so the two agree bitwise wherever the backend rounds a convolution
independently of how many pixels it covers. The installed XLA:CPU
(jaxlib 0.9.0) does not, for wide convs: a 3x3 conv is an Eigen
contraction over K = 9*C_in whose accumulation is blocked by the
contraction's M x N extent, so the v1 model's last section cell (3x3,
32->64 stride 2 and 64->64, K = 288 / 576) rounds differently on a
10x10 or 10x12 window (plans t16 and t(16, 24): windows 40x40 and
40x48) than on the 14x14 whole-image feature map — ~2e-6 on O(1)
activations, the repo's standard cross-executable f32 boundary. Narrower
convs (C <= 32 at stride 1, every 1x1) are shape-stable there, which is
why t48 (window == image) and the v2 bottleneck model stay bitwise. So
``ok`` holds the plans to that f32 boundary, and the window == image plan
— which pins that the section/head SPLIT itself is bitwise-safe — to
bit-identity.

Why a subprocess: the test harness simulates an 8-device mesh, under
which XLA:CPU also partitions each program's intra-op work per SHAPE.
"""

import json
import sys

import numpy as np

#: The cross-executable f32 boundary (the in-harness test's own atol).
ATOL = 5e-6


def main() -> int:
    import jax
    import jax.numpy as jnp

    from mpi4dl_tpu.evaluate import aot_compile_predict, collect_batch_stats
    from mpi4dl_tpu.models.resnet import get_resnet_v1, get_resnet_v2
    from mpi4dl_tpu.parallel.partition import init_cells
    from mpi4dl_tpu.serve.tiled import TiledPredictor

    assert len(jax.devices()) == 1, "this check needs ONE device"
    results = {}
    max_abs = {}

    def check(tag, cells, size, tile, seed):
        rng = np.random.default_rng(seed)
        params = init_cells(
            cells, jax.random.PRNGKey(seed), jnp.zeros((1, size, size, 3))
        )
        stats = collect_batch_stats(
            cells, params,
            [jnp.asarray(
                rng.standard_normal((2, size, size, 3)), jnp.float32
            )],
        )
        mono = aot_compile_predict(
            cells, params, stats, (size, size, 3), [1]
        )[1]
        for t in tile if isinstance(tile, list) else [tile]:
            pred = TiledPredictor(
                cells, params, stats, (size, size, 3), t
            )
            handle = pred.compile_bucket(1)
            x = rng.standard_normal((1, size, size, 3)).astype(np.float32)
            got = pred.run(handle, x)
            want = np.asarray(mono(params, stats, x))
            results[f"{tag}_t{t}"] = bool(np.array_equal(got, want))
            max_abs[f"{tag}_t{t}"] = float(np.max(np.abs(got - want)))

    # v1 at a ragged size: square/rect cores, ragged last tiles, the
    # single-window degenerate; v2 (pre-activation bottlenecks, 1x1
    # stride-2 shortcuts) at a tiny tile (8x8 grid).
    check(
        "v1_56",
        get_resnet_v1(depth=8, num_classes=10, pool_kernel=14),
        56, [16, (16, 24), 48], seed=0,
    )
    check(
        "v2_32",
        get_resnet_v2(depth=11, num_classes=10, pool_kernel=8),
        32, [4], seed=1,
    )
    ok = results["v1_56_t48"] and all(d <= ATOL for d in max_abs.values())
    print(
        json.dumps({"ok": ok, "bit_identical": results, "max_abs": max_abs}),
        flush=True,
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
