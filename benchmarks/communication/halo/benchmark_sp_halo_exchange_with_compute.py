"""Halo exchange + conv compute micro-benchmark and numerical validation.

TPU rebuild of three reference scripts in one:

- ``benchmark_sp_halo_exchange_with_compute.py`` (exchange then conv on the
  padded tile, timed, ref ``:392-397``);
- ``benchmark_sp_halo_exchange_with_compute_val.py`` (distributed conv with
  weights/bias forced to 1.0 vs sequential full-image conv, ref
  ``:704-780``);
- ``benchmark_sp_halo_exchange_conv.py`` validation modes (full conv
  equality, ref ``:940-1092``).

The reference needed the weights-set-to-1.0 trick to separate exchange bugs
from cuDNN nondeterminism; XLA convs are deterministic, so we validate with
random weights at float tolerance AND with ones at exact equality.

On TPU the "overlap" question the reference's dead code asks
(``spatial.py:415-828``) is answered by the compiler: the exchange and the
conv are one fused XLA program, and XLA's latency-hiding scheduler overlaps
the collective with independent compute. This benchmark reports the fused
cost directly (compare with the exchange-only number from
``benchmark_sp_halo_exchange.py`` to see the overlap).
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
    p = argparse.ArgumentParser(description="halo exchange + conv (TPU-native)")
    p.add_argument("--image-size", type=int, default=1024)
    p.add_argument("--batch-size", type=int, default=1)
    p.add_argument("--num-filters", type=int, default=64)
    p.add_argument("--in-channels", type=int, default=3)
    p.add_argument("--num-spatial-parts", type=int, default=4)
    p.add_argument("--slice-method", type=str, default="square")
    p.add_argument("--halo-len", type=int, default=1, help="(kernel-1)/2")
    p.add_argument("--iterations", type=int, default=50)
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--skip-validation", action="store_true")
    return p.parse_args()


def main():
    args = get_args()

    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
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
    k = 2 * h + 1

    b, s, cin, cout = args.batch_size, args.image_size, args.in_channels, args.num_filters
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((b, s, s, cin)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, k, cin, cout)) * 0.05, jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, spec))

    dn = lax.conv_dimension_numbers(x.shape, w.shape, ("NHWC", "HWIO", "NHWC"))

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh, in_specs=(spec, P()), out_specs=spec, check_vma=False
    )
    def dist_conv(x, w):
        p = halo_exchange(x, h, h, "tile_h", "tile_w")
        return lax.conv_general_dilated(p, w, (1, 1), "VALID", dimension_numbers=dn)

    @jax.jit
    def seq_conv(x, w):
        return lax.conv_general_dilated(
            x, w, (1, 1), ((h, h), (h, h)), dimension_numbers=dn
        )

    if not args.skip_validation:
        got = np.asarray(dist_conv(xs, w))
        want = np.asarray(seq_conv(x, w))
        err = np.max(np.abs(got - want))
        print(f"validation (random weights): max|err| = {err:.3e}")
        ones_w = jnp.ones_like(w)
        got1 = np.asarray(dist_conv(xs, ones_w))
        want1 = np.asarray(seq_conv(x, ones_w))
        exact = np.array_equal(got1, want1)
        print(f"validation (weights=1, ref parity trick): {'EXACT' if exact else 'FAILED'}")
        if err > 1e-4 or not exact:
            sys.exit(1)

    def bench(fn, *a):
        out = None
        for _ in range(args.warmup):
            out = fn(*a)
        if out is not None:
            jax.block_until_ready(out)
        times = []
        for _ in range(args.iterations):
            t0 = time.perf_counter()
            out = fn(*a)
            jax.block_until_ready(out)
            times.append((time.perf_counter() - t0) * 1e3)
        return statistics.mean(times), statistics.median(times)

    m, md = bench(dist_conv, xs, w)
    print(
        f"halo+conv {s}x{s} k={k} {args.slice_method} x{n}: "
        f"mean {m:.4f} ms  median {md:.4f} ms"
    )
    m2, md2 = bench(seq_conv, x, w)
    print(f"sequential full-image conv: mean {m2:.4f} ms  median {md2:.4f} ms")


if __name__ == "__main__":
    main()
