"""Numerical validation of the distributed (halo-exchanged) convolution.

TPU rebuild of reference
``benchmarks/communication/halo/benchmark_sp_halo_exchange_with_compute_val.py``:
weights AND bias forced to 1.0 on both the distributed and the sequential conv
(ref ``:704-706, :752-753`` — the trick that removed cuDNN nondeterminism from
the comparison), then two independent equality checks per tile (ref
``:727-780``):

1. the received halo ring vs an ``np.pad`` ground truth of the global image;
2. the distributed conv output vs the sequential full-image conv.

XLA convs are deterministic, so the 1.0-weights runs are checked with exact
integer-style equality, and an extra random-weights run is checked at float
tolerance (strictly stronger than the reference's validation).
"""

import argparse
import functools
import os
import sys

import numpy as np

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..", "..")
)


def get_args():
    p = argparse.ArgumentParser(
        description="distributed conv validation, weights/bias = 1.0 (TPU-native)"
    )
    p.add_argument("--image-size", type=int, default=256)
    p.add_argument("--batch-size", type=int, default=1)
    p.add_argument("--num-filters", type=int, default=8)
    p.add_argument("--in-channels", type=int, default=3)
    p.add_argument("--num-spatial-parts", type=int, default=4)
    p.add_argument("--slice-method", type=str, default="square")
    p.add_argument("--halo-len", type=int, default=1, help="(kernel-1)/2")
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

    b, s, cin, cout = (
        args.batch_size,
        args.image_size,
        args.in_channels,
        args.num_filters,
    )
    # Deterministic arange image (ref create_input, :417-470) so every check
    # is exact integer equality.
    x = jnp.arange(b * s * s * cin, dtype=jnp.float32).reshape(b, s, s, cin)
    xs = jax.device_put(x, NamedSharding(mesh, spec))
    w_shape = (k, k, cin, cout)
    dn = lax.conv_dimension_numbers(x.shape, w_shape, ("NHWC", "HWIO", "NHWC"))

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, P(), P()),
        out_specs=(spec, spec),
        check_vma=False,
    )
    def dist_conv_and_padded(x, w, bias):
        p = halo_exchange(x, h, h, "tile_h", "tile_w")
        y = (
            lax.conv_general_dilated(p, w, (1, 1), "VALID", dimension_numbers=dn)
            + bias
        )
        # Full padded tile (tiles evenly: every tile has the same padded
        # shape) so check 1 can validate the ENTIRE halo ring — all four
        # exchange directions and all boundary fills.
        return y, p

    @jax.jit
    def seq_conv(x, w, bias):
        return (
            lax.conv_general_dilated(
                x, w, (1, 1), ((h, h), (h, h)), dimension_numbers=dn
            )
            + bias
        )

    failures = 0

    # -- check 1: received halos vs np.pad ground truth (ref :727-748) -------
    ones_w = jnp.ones(w_shape, jnp.float32)
    ones_b = jnp.ones((cout,), jnp.float32)
    from halo_common import validate_padded_tiles

    got_y, got_pad = dist_conv_and_padded(xs, ones_w, ones_b)
    failures += validate_padded_tiles(got_pad, x, th, tw, h, h, label="halo")
    print(f"recv-halo validation: {'PASSED' if failures == 0 else 'FAILED'}")

    # -- check 2: conv output, weights/bias = 1.0, exact (ref :752-780) ------
    want_y = np.asarray(seq_conv(x, ones_w, ones_b))
    got_y = np.asarray(got_y)
    exact = np.array_equal(got_y, want_y)
    print(f"conv validation (weights=bias=1.0): {'EXACT' if exact else 'FAILED'}")
    if not exact:
        failures += 1

    # -- check 3: random weights at float tolerance (beyond the reference) ---
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal(w_shape) * 0.05, jnp.float32)
    bias = jnp.asarray(rng.standard_normal((cout,)), jnp.float32)
    xr = jnp.asarray(rng.standard_normal((b, s, s, cin)), jnp.float32)
    xrs = jax.device_put(xr, NamedSharding(mesh, spec))
    got_r, _ = dist_conv_and_padded(xrs, w, bias)
    err = np.max(np.abs(np.asarray(got_r) - np.asarray(seq_conv(xr, w, bias))))
    print(f"conv validation (random weights): max|err| = {err:.3e}")
    if err > 1e-4:
        failures += 1

    if failures:
        sys.exit(1)
    print("ALL VALIDATIONS PASSED")


if __name__ == "__main__":
    main()
