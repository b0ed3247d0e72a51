"""Full conv+halo validation suite (kernel-shape-aware).

TPU rebuild of reference
``benchmarks/communication/halo/benchmark_sp_halo_exchange_conv.py``: the most
thorough of the reference's halo harnesses, adding

- kernel-size-aware neighbor pruning (ref ``:219-236``): a 1xk kernel needs
  halos only along W, a kx1 kernel only along H — here expressed as per-dim
  halo lengths ``((kh-1)/2, (kw-1)/2)`` passed to the same exchange (the
  "pruning" falls out: a zero halo posts no collective on that axis);
- a CPU/accelerator switch (ref ``ENABLE_GPU``) → ``--platform {auto,cpu}``;
- three validation modes (ref ``:940-1092``), each switchable:
  * ``--val-recv``  — received halo ring vs ``np.pad`` ground truth;
  * ``--val-conv``  — distributed conv output vs sequential full-image conv
    (ref ``ENABLE_VAL_CONV``);
  * ``--val-small-conv`` — run the conv ONLY on each tile's halo-extended
    boundary strips and compare against the same windows of the sequential
    output (ref ``ENABLE_VAL_SMALL_CONV``, the probe that distinguishes
    exchange bugs from conv nondeterminism).
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
    p = argparse.ArgumentParser(description="conv+halo validation suite (TPU-native)")
    p.add_argument("--image-size", type=int, default=256)
    p.add_argument("--batch-size", type=int, default=1)
    p.add_argument("--num-filters", type=int, default=8)
    p.add_argument("--in-channels", type=int, default=3)
    p.add_argument("--num-spatial-parts", type=int, default=4)
    p.add_argument("--slice-method", type=str, default="square")
    p.add_argument(
        "--kernel", type=str, default="3x3",
        help="HxW kernel, odd dims; e.g. 3x3, 1x7, 7x1, 5x5",
    )
    p.add_argument(
        "--platform", type=str, default="auto", choices=["auto", "cpu"],
        help="cpu forces host execution (ref ENABLE_GPU=False)",
    )
    p.add_argument("--val-recv", action="store_true", default=True)
    p.add_argument("--no-val-recv", dest="val_recv", action="store_false")
    p.add_argument("--val-conv", action="store_true", default=True)
    p.add_argument("--no-val-conv", dest="val_conv", action="store_false")
    p.add_argument("--val-small-conv", action="store_true", default=True)
    p.add_argument("--no-val-small-conv", dest="val_small_conv", action="store_false")
    return p.parse_args()


def main():
    args = get_args()

    if args.platform == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")
        jax.config.update(
            "jax_num_cpu_devices", max(args.num_spatial_parts, 1)
        )

    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mpi4dl_tpu.config import tile_grid
    from mpi4dl_tpu.parallel.halo import halo_exchange

    kh, kw = (int(v) for v in args.kernel.split("x"))
    if kh % 2 == 0 or kw % 2 == 0:
        sys.exit("kernel dims must be odd")
    hh, hw = (kh - 1) // 2, (kw - 1) // 2  # per-dim halo = neighbor pruning

    th, tw = tile_grid(args.num_spatial_parts, args.slice_method)
    n = th * tw
    if len(jax.devices()) < n:
        sys.exit(
            f"need {n} devices; have {len(jax.devices())}. Set JAX_PLATFORMS=cpu "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} to simulate."
        )
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(th, tw), ("tile_h", "tile_w"))
    spec = P(None, "tile_h", "tile_w", None)

    b, s, cin, cout = (
        args.batch_size,
        args.image_size,
        args.in_channels,
        args.num_filters,
    )
    x = jnp.arange(b * s * s * cin, dtype=jnp.float32).reshape(b, s, s, cin)
    xs = jax.device_put(x, NamedSharding(mesh, spec))
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((kh, kw, cin, cout)) * 0.05, jnp.float32)
    dn = lax.conv_dimension_numbers(x.shape, w.shape, ("NHWC", "HWIO", "NHWC"))

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, P()),
        out_specs=(spec, spec),
        check_vma=False,
    )
    def dist(x, w):
        p = halo_exchange(x, hh, hw, "tile_h", "tile_w")
        y = lax.conv_general_dilated(p, w, (1, 1), "VALID", dimension_numbers=dn)
        # Full padded tile (tiles evenly) so --val-recv covers the whole
        # halo ring: all exchange directions and all boundary fills.
        return y, p

    @jax.jit
    def seq(x, w):
        return lax.conv_general_dilated(
            x, w, (1, 1), ((hh, hh), (hw, hw)), dimension_numbers=dn
        )

    got_y, got_pad = dist(xs, w)
    got_y, got_pad = np.asarray(got_y), np.asarray(got_pad)
    want_y = np.asarray(seq(x, w))
    t_h, t_w = s // th, s // tw
    failures = 0

    if args.val_recv:
        from halo_common import validate_padded_tiles

        bad = validate_padded_tiles(got_pad, x, th, tw, hh, hw)
        print(f"val-recv (kernel {kh}x{kw}, halo ({hh},{hw})): "
              f"{'PASSED' if bad == 0 else 'FAILED'}")
        failures += bad

    if args.val_conv:
        err = np.max(np.abs(got_y - want_y))
        ok = err <= 1e-4
        print(f"val-conv: max|err| = {err:.3e} {'PASSED' if ok else 'FAILED'}")
        failures += 0 if ok else 1

    if args.val_small_conv:
        # Conv only the boundary strips: for each interior tile edge, take the
        # sequential output rows/cols that straddle it and compare with the
        # distributed output of the tiles on each side. An exchange bug
        # corrupts exactly these windows first (ref :1038-1092).
        bad = 0
        for i in range(1, th):  # horizontal boundaries (need hh > 0)
            if hh == 0:
                break
            r0 = i * t_h - hh
            strip_want = want_y[:, r0 : r0 + 2 * hh, :, :]
            strip_got = got_y[:, r0 : r0 + 2 * hh, :, :]
            if np.max(np.abs(strip_want - strip_got)) > 1e-4:
                bad += 1
                print(f"small-conv H-boundary {i}: MISMATCH", file=sys.stderr)
        for j in range(1, tw):  # vertical boundaries (need hw > 0)
            if hw == 0:
                break
            c0 = j * t_w - hw
            strip_want = want_y[:, :, c0 : c0 + 2 * hw, :]
            strip_got = got_y[:, :, c0 : c0 + 2 * hw, :]
            if np.max(np.abs(strip_want - strip_got)) > 1e-4:
                bad += 1
                print(f"small-conv W-boundary {j}: MISMATCH", file=sys.stderr)
        print(f"val-small-conv: {'PASSED' if bad == 0 else 'FAILED'}")
        failures += bad

    if failures:
        sys.exit(1)
    print("ALL VALIDATIONS PASSED")


if __name__ == "__main__":
    main()
