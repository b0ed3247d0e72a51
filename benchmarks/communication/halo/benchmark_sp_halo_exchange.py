"""Raw halo-exchange micro-benchmark + validation.

TPU rebuild of reference
``benchmarks/communication/halo/benchmark_sp_halo_exchange.py`` (timing) and
its ``_val``/``_conv`` validation variants: a deterministic ``arange`` image
is tiled over the mesh, halo-exchanged, and every rank's received halos are
checked against an ``np.pad`` ground truth (ref ``create_input_*``
``:417-566``, ``test_output`` ``:570-584``); then the exchange alone is timed
(ref CUDA-event loop ``:587-620``; host wall-clock + ``block_until_ready``
here).

Flags: --image-size, --num-spatial-parts, --slice-method, --halo-len,
--iterations, --batch-size, --num-filters (channel count).
"""

import argparse
import functools
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..", "..")
)


def get_args():
    p = argparse.ArgumentParser(description="halo exchange benchmark (TPU-native)")
    p.add_argument("--image-size", type=int, default=1024)
    p.add_argument("--batch-size", type=int, default=1)
    p.add_argument("--num-filters", type=int, default=3)
    p.add_argument("--num-spatial-parts", type=int, default=4)
    p.add_argument("--slice-method", type=str, default="square")
    p.add_argument("--halo-len", type=int, default=1)
    p.add_argument("--iterations", type=int, default=100)
    p.add_argument("--warmup", type=int, default=10)
    return p.parse_args()


def main():
    args = get_args()


    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mpi4dl_tpu.config import tile_grid
    from mpi4dl_tpu.parallel.halo import halo_exchange

    th, tw = tile_grid(args.num_spatial_parts, args.slice_method)
    n = th * tw
    if len(jax.devices()) < n:
        sys.exit(
            f"need {n} devices; have {len(jax.devices())}. Set JAX_PLATFORMS=cpu "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} to simulate."
        )
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(th, tw), ("tile_h", "tile_w"))
    spec = P(None, "tile_h", "tile_w", None)
    h = args.halo_len

    b, s, c = args.batch_size, args.image_size, args.num_filters
    x = jnp.arange(b * s * s * c, dtype=jnp.float32).reshape(b, s, s, c)
    xs = jax.device_put(x, NamedSharding(mesh, spec))

    # -- validation vs np.pad ground truth (ref test_output, :570-584) -------
    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False
    )
    def exchange_keep_halo(x):
        # Full padded tile: every tile has the same padded shape, so the
        # shard_map output tiles evenly and the validation below can check
        # the ENTIRE halo ring (all four directions + boundary fill).
        return halo_exchange(x, h, h, "tile_h", "tile_w")

    from halo_common import validate_padded_tiles

    bad = validate_padded_tiles(exchange_keep_halo(xs), x, th, tw, h, h)
    print(f"validation: {'PASSED' if bad == 0 else 'FAILED'}")
    if bad:
        sys.exit(1)

    # -- timing (exchange_keep_halo: output depends on the received halos, so
    # XLA cannot dead-code-eliminate the collectives) -------------------------
    for _ in range(args.warmup):
        out = exchange_keep_halo(xs)
    jax.block_until_ready(out)
    times = []
    for _ in range(args.iterations):
        t0 = time.perf_counter()
        out = exchange_keep_halo(xs)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) * 1e3)
    print(
        f"halo exchange {s}x{s} halo={h} {args.slice_method} x{n}: "
        f"mean {statistics.mean(times):.4f} ms  median {statistics.median(times):.4f} ms"
    )


if __name__ == "__main__":
    main()
