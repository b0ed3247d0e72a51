"""Model FLOP accounting + MFU.

The north-star target for this framework is stated in MFU (BASELINE.json:
>=45% on the flagship configs), but the reference reports only images/sec —
it has no FLOP counter. Here we count *model* FLOPs analytically from the
jaxpr of the forward pass (convs + matmuls; elementwise/BN ignored, <1%),
so the number is independent of implementation tricks: the MXU-packed conv
(ops/fastconv.py) executes ~1.7x more device FLOPs than the model math
needs, and counting those would flatter MFU. The count is taken with
``MPI4DL_TPU_CONV_IMPL=xla`` for the same reason.

Training FLOPs per example use the standard 3x rule (forward + input-grad +
weight-grad each cost ~one forward; e.g. the PaLM appendix convention):

    train_flops = 3 * forward_flops

MFU = train_flops * images_per_sec / peak_flops(device).
"""

from __future__ import annotations

import functools
import os
from typing import Any, Sequence

import jax
import numpy as np


# Peak dense bf16 FLOP/s per chip (public spec sheets). device_kind strings
# as reported by jax.devices()[0].device_kind. Longest-prefix match so lite
# variants never fall through to their full-size generation.
_PEAK_FLOPS = {
    "TPU v4 lite": 138e12,  # v4i
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,  # Trillium
    "TPU v6e": 918e12,
}


def peak_flops(device=None) -> float | None:
    """Peak bf16 FLOP/s for ``device`` (default: first visible device).
    None on CPU only; a TPU whose ``device_kind`` is not in the table is
    an error, not a default."""
    if device is None:
        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    kind = device.device_kind
    for name in sorted(_PEAK_FLOPS, key=len, reverse=True):
        if kind.startswith(name):
            return _PEAK_FLOPS[name]
    raise ValueError(
        f"no peak FLOP/s on record for device_kind {kind!r} "
        f"(platform {device.platform!r}); add it to flops._PEAK_FLOPS "
        "with its source"
    )


def _eqn_flops(eqn) -> float:
    """FLOPs of one jaxpr equation (matmul-class primitives only)."""
    prim = eqn.primitive.name
    if prim == "conv_general_dilated":
        out = eqn.outvars[0].aval
        rhs = eqn.invars[1].aval
        dnums = eqn.params["dimension_numbers"]
        # rhs spatial extents + input-feature dim from the kernel spec.
        kernel_spatial = [rhs.shape[d] for d in dnums.rhs_spec[2:]]
        cin = rhs.shape[dnums.rhs_spec[1]]
        # The kernel's input-feature dim is ALREADY Cin/feature_group_count
        # in XLA's convention, so grouped/depthwise convs need no extra
        # divisor here.
        return 2.0 * out.size * float(np.prod(kernel_spatial)) * cin
    if prim == "dot_general":
        lhs, rhs = (v.aval for v in eqn.invars[:2])
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        batch = float(np.prod([lhs.shape[d] for d in lb], initial=1.0))
        k = float(np.prod([lhs.shape[d] for d in lc], initial=1.0))
        m = float(
            np.prod(
                [s for d, s in enumerate(lhs.shape) if d not in set(lc) | set(lb)],
                initial=1.0,
            )
        )
        n = float(
            np.prod(
                [s for d, s in enumerate(rhs.shape) if d not in set(rc) | set(rb)],
                initial=1.0,
            )
        )
        return 2.0 * batch * m * n * k
    return 0.0


def _subjaxprs(val):
    """Yield every jaxpr reachable from one eqn param value: a bare jaxpr, a
    ClosedJaxpr, or a tuple/list of either (``cond``'s ``branches``,
    ``custom_*`` residuals). Misses would silently deflate the MFU
    denominator (ADVICE r2), so unknown shapes fall through to zero yields
    only when they genuinely hold no jaxpr."""
    if hasattr(val, "eqns"):
        yield val
    elif hasattr(val, "jaxpr"):
        yield val.jaxpr
    elif isinstance(val, (tuple, list)):
        for item in val:
            yield from _subjaxprs(item)


def _jaxpr_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        total += _eqn_flops(eqn)
        # Recurse into every call-like primitive (pjit, remat, custom_vjp,
        # scan bodies × length, cond/while branches, etc.). ``cond``
        # branches: count the MAX branch — an upper bound that matches the
        # convention of counting what the model would execute; for
        # same-shape branches (the only use in this codebase's models: none
        # today) the branches cost the same anyway.
        for name, val in eqn.params.items():
            subs = list(_subjaxprs(val))
            if not subs:
                continue
            if name == "branches":
                inner = max(_jaxpr_flops(j) for j in subs)
            else:
                inner = sum(_jaxpr_flops(j) for j in subs)
            if eqn.primitive.name == "scan":
                inner *= eqn.params.get("length", 1)
            total += inner
    return total


def forward_flops(cells: Sequence[Any], x_shape, dtype=None) -> float:
    """Model forward FLOPs for one batch of shape ``x_shape`` through the
    (non-spatial) cell list. Counted on the stock conv lowering so packing
    inflation never flatters the number."""
    import jax.numpy as jnp

    from mpi4dl_tpu.parallel.partition import init_cells

    dtype = dtype or jnp.float32
    x = jax.ShapeDtypeStruct(tuple(x_shape), dtype)

    prev = os.environ.get("MPI4DL_TPU_CONV_IMPL")
    os.environ["MPI4DL_TPU_CONV_IMPL"] = "xla"
    # Packed-layout cells execute MORE device FLOPs than the model math by
    # design (scattered kernels), and PackedConv has no xla-impl escape —
    # counting them would overstate MFU (ADVICE r2). PackedConv checks this
    # env at trace time and raises, forcing callers to pass the logical
    # (stock-layout) twin.
    os.environ["MPI4DL_TPU_COUNTING_FLOPS"] = "1"
    try:
        # Init OUTSIDE the counted jaxpr (init traces each cell's forward,
        # which would triple-count every conv).
        params = jax.eval_shape(
            lambda xx: init_cells(cells, jax.random.PRNGKey(0), xx), x
        )

        def run(vs, xx):
            for cell, v in zip(cells, vs):
                xx = cell.apply(v, xx)
            return xx

        jaxpr = jax.make_jaxpr(run)(params, x)
    finally:
        os.environ.pop("MPI4DL_TPU_COUNTING_FLOPS", None)
        if prev is None:
            os.environ.pop("MPI4DL_TPU_CONV_IMPL", None)
        else:
            os.environ["MPI4DL_TPU_CONV_IMPL"] = prev
    return _jaxpr_flops(jaxpr.jaxpr)


def train_flops_per_image(cells: Sequence[Any], image_size: int, dtype=None) -> float:
    """3x-forward training FLOPs for ONE image (batch-independent)."""
    fwd = forward_flops(cells, (1, image_size, image_size, 3), dtype)
    return 3.0 * fwd


def mfu(images_per_sec: float, flops_per_image: float, n_devices: int = 1,
        device=None) -> float | None:
    """Model FLOP utilization in [0, 1]; None on CPU (no peak), an error
    on an accelerator :func:`peak_flops` does not know."""
    peak = peak_flops(device)
    if peak is None:
        return None
    return images_per_sec * flops_per_image / (peak * n_devices)
