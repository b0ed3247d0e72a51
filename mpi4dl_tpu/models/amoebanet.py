"""AmoebaNet-D — capability parity with reference ``src/models/amoebanet.py``
(plain + spatial) as one builder with per-cell ``spatial`` flags.

Architecture parity (file:line are reference cites):
- ``Operation`` factories (``amoebanet.py:88-291``): ``none`` (identity /
  FactorizedReduce at stride 2), ``avg_pool_3x3`` (count_include_pad=False),
  ``max_pool_3x3``, ``max_pool_2x2``, ``conv_1x7_7x1`` (c→c/4 bottleneck with
  1×7 then 7×1), ``conv_1x1``, ``conv_3x3`` (c→c/4 bottleneck).
- genotype tables ``NORMAL_OPERATIONS``/``NORMAL_CONCAT`` (TF-implementation
  variant ``[0,3,4,6]``), ``REDUCTION_*`` (``amoebanet.py:295-351``) — the
  AmoebaNet-D genotype from Real et al. 2018 as fixed by the GPipe paper.
- ``Stem`` (relu→3×3 s2 conv→BN, ``amoebanet.py:417-446``), ``Cell``
  (two-state DAG returning ``(concat, skip)`` — the tuple-valued stage
  interface the pipeline's MULTIPLE_INPUT/OUTPUT machinery exists for,
  ``amoebanet.py:449-532``), ``Classify`` (global avg pool → linear,
  ``amoebanet.py:401-414``).
- builders ``amoebanetd`` / ``amoebanetd_spatial`` (``amoebanet.py:535-737``):
  stem1 + 2 reduction stems + [normal×r, reduction, normal×r, reduction,
  normal×r] + classify, ``r = num_layers//3``, channels = num_filters/4
  doubled at each reduction; spatial variant flips cells plain after the SP
  stage boundary.

Deliberate deviations (documented, not accidental):
- reference ``max_pool_3x3`` constructs an **Avg**Pool2d in both branches
  (``amoebanet.py:110-125``) — an apparent copy-paste slip; we implement a
  real max pool.
- reference ``FactorizedReduce`` feeds both 1×1 convs the same input (the
  pixel-shifted second path is commented out, ``amoebanet.py:74-76``); we
  reproduce the *active* behavior.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from mpi4dl_tpu.ops.fastconv import conv_scope
from mpi4dl_tpu.ops.layers import (
    Conv2d,
    HaloExchange,
    Identity,
    Pool,
    TrainBatchNorm,
    TILE_AXES,
)


def _bn_axes(spatial: bool, cross_tile_bn: bool) -> tuple[str, ...]:
    return TILE_AXES if (spatial and cross_tile_bn) else ()


class ReluConvBn(nn.Module):
    """relu → conv → BN (ref ``relu_conv_bn``, ``amoebanet.py:365-398``)."""

    features: int
    kernel_size: Any = 1
    strides: Any = 1
    padding: Any = 0
    spatial: bool = False
    bn_reduce_axes: tuple[str, ...] = ()
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        x = nn.relu(x)
        x = Conv2d(
            features=self.features,
            kernel_size=self.kernel_size,
            strides=self.strides,
            padding=self.padding,
            use_bias=False,
            spatial=self.spatial,
            dtype=self.dtype,
            name="conv",
        )(x)
        return TrainBatchNorm(
            reduce_axes=self.bn_reduce_axes, dtype=self.dtype, name="bn"
        )(x)


class FactorizedReduce(nn.Module):
    """relu → concat(1×1 s2 conv, 1×1 s2 conv) → BN (ref ``amoebanet.py:56-78``;
    both convs see the same input — the shifted path is commented out there)."""

    features: int
    spatial: bool = False
    bn_reduce_axes: tuple[str, ...] = ()
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        x = nn.relu(x)
        common = dict(
            kernel_size=1,
            strides=2,
            padding=0,
            use_bias=False,
            spatial=self.spatial,
            dtype=self.dtype,
        )
        a = Conv2d(features=self.features // 2, name="conv1", **common)(x)
        b = Conv2d(features=self.features - self.features // 2, name="conv2", **common)(x)
        x = jnp.concatenate([a, b], axis=-1)
        return TrainBatchNorm(
            reduce_axes=self.bn_reduce_axes, dtype=self.dtype, name="bn"
        )(x)


class ConvBranch(nn.Module):
    """Shared body for the conv_* operations: an optional c→c/4 bottleneck
    around a list of (kernel, stride, padding) convs (refs
    ``conv_1x7_7x1`` ``amoebanet.py:246-291``, ``conv_1x1`` ``:240-248``,
    ``conv_3x3`` ``:250-291``)."""

    channels: int
    convs: Sequence[tuple[Any, Any, Any]]  # (kernel, stride, padding) each
    bottleneck: bool = False
    spatial: bool = False
    bn_reduce_axes: tuple[str, ...] = ()
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        c = self.channels
        inner = c // 4 if self.bottleneck else c
        common = dict(
            use_bias=False,
            spatial=self.spatial,
            dtype=self.dtype,
        )
        idx = 0
        if self.bottleneck:
            x = nn.relu(x)
            x = Conv2d(features=inner, kernel_size=1, padding=0, name=f"conv{idx}", **common)(x)
            x = TrainBatchNorm(reduce_axes=self.bn_reduce_axes, dtype=self.dtype, name=f"bn{idx}")(x)
            idx += 1
        for k, s, p in self.convs:
            x = nn.relu(x)
            x = Conv2d(features=inner, kernel_size=k, strides=s, padding=p, name=f"conv{idx}", **common)(x)
            x = TrainBatchNorm(reduce_axes=self.bn_reduce_axes, dtype=self.dtype, name=f"bn{idx}")(x)
            idx += 1
        if self.bottleneck:
            x = nn.relu(x)
            x = Conv2d(features=c, kernel_size=1, padding=0, name=f"conv{idx}", **common)(x)
            x = TrainBatchNorm(reduce_axes=self.bn_reduce_axes, dtype=self.dtype, name=f"bn{idx}")(x)
        return x


# -- operation factories (ref amoebanet.py:81-291) ---------------------------


def op_none(channels, stride, spatial, bn_axes, dtype, name):
    if stride == 1:
        return Identity(name=name)
    return FactorizedReduce(
        features=channels, spatial=spatial, bn_reduce_axes=bn_axes, dtype=dtype, name=name
    )


def op_avg_pool_3x3(channels, stride, spatial, bn_axes, dtype, name):
    return Pool(
        kind="avg",
        kernel_size=3,
        strides=stride,
        padding=1,
        spatial=spatial,
        count_include_pad=False,
        name=name,
    )


def op_max_pool_3x3(channels, stride, spatial, bn_axes, dtype, name):
    # Reference builds AvgPool2d here in both branches (amoebanet.py:110-125)
    # — we implement the op its name (and the genotype) means.
    return Pool(
        kind="max", kernel_size=3, strides=stride, padding=1, spatial=spatial, name=name
    )


def op_max_pool_2x2(channels, stride, spatial, bn_axes, dtype, name):
    return Pool(
        kind="max", kernel_size=2, strides=stride, padding=0, spatial=spatial, name=name
    )


def op_conv_1x7_7x1(channels, stride, spatial, bn_axes, dtype, name):
    return ConvBranch(
        channels=channels,
        convs=[((1, 7), (1, stride), (0, 3)), ((7, 1), (stride, 1), (3, 0))],
        bottleneck=True,
        spatial=spatial,
        bn_reduce_axes=bn_axes,
        dtype=dtype,
        name=name,
    )


def op_conv_1x1(channels, stride, spatial, bn_axes, dtype, name):
    # Reference keeps conv_1x1 plain even under SP (no halo needed for 1x1,
    # amoebanet.py:240-248) — spatial flag is harmless but kept for stride-2.
    return ConvBranch(
        channels=channels,
        convs=[(1, stride, 0)],
        bottleneck=False,
        spatial=spatial,
        bn_reduce_axes=bn_axes,
        dtype=dtype,
        name=name,
    )


def op_conv_3x3(channels, stride, spatial, bn_axes, dtype, name):
    return ConvBranch(
        channels=channels,
        convs=[(3, stride, 1)],
        bottleneck=True,
        spatial=spatial,
        bn_reduce_axes=bn_axes,
        dtype=dtype,
        name=name,
    )


# AmoebaNet-D genotype (ref amoebanet.py:295-351; NORMAL_CONCAT follows the
# TF implementation, see the long comment there).
NORMAL_OPERATIONS = [
    (1, op_conv_1x1),
    (1, op_max_pool_3x3),
    (1, op_none),
    (0, op_conv_1x7_7x1),
    (0, op_conv_1x1),
    (0, op_conv_1x7_7x1),
    (2, op_max_pool_3x3),
    (2, op_none),
    (1, op_avg_pool_3x3),
    (5, op_conv_1x1),
]
NORMAL_CONCAT = [0, 3, 4, 6]

REDUCTION_OPERATIONS = [
    (0, op_max_pool_2x2),
    (0, op_max_pool_3x3),
    (2, op_none),
    (1, op_conv_3x3),
    (2, op_conv_1x7_7x1),
    (2, op_max_pool_3x3),
    (3, op_none),
    (1, op_max_pool_2x2),
    (2, op_avg_pool_3x3),
    (3, op_conv_1x1),
]
REDUCTION_CONCAT = [4, 5, 6]


class Stem(nn.Module):
    """relu → 3×3 stride-2 conv → BN (ref ``Stem``, ``amoebanet.py:417-446``)."""

    channels: int
    spatial: bool = False
    bn_reduce_axes: tuple[str, ...] = ()
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        x = nn.relu(x)
        x = Conv2d(
            features=self.channels,
            kernel_size=3,
            strides=2,
            padding=1,
            use_bias=False,
            spatial=self.spatial,
            dtype=self.dtype,
            name="conv",
        )(x)
        return TrainBatchNorm(
            reduce_axes=self.bn_reduce_axes, dtype=self.dtype, name="bn"
        )(x)


class Classify(nn.Module):
    """Global avg pool → linear on the concat state (ref ``Classify``,
    ``amoebanet.py:401-414``)."""

    num_classes: int
    dtype: Any = None

    @nn.compact
    def __call__(self, states):
        x, _ = states
        x = jnp.mean(x, axis=(1, 2))
        with jax.named_scope(conv_scope(1, 1)):
            return nn.Dense(self.num_classes, dtype=self.dtype, name="fc")(x)


class AmoebaCell(nn.Module):
    """Two-state NAS cell (ref ``Cell``, ``amoebanet.py:449-532``).

    Input: a tensor (after the stem) or ``(s, skip)`` tuple. Output:
    ``(concat, skip)`` — the tuple stage interface that exercises the
    pipeline's pytree-valued wires.
    """

    channels_prev_prev: int
    channels_prev: int
    channels: int
    reduction: bool
    reduction_prev: bool
    spatial: bool = False
    cross_tile_bn: bool = True
    dtype: Any = None

    @nn.compact
    def __call__(self, input_or_states):
        if isinstance(input_or_states, (tuple, list)):
            s1, s2 = input_or_states
        else:
            s1 = s2 = input_or_states
        skip = s1

        bn_axes = _bn_axes(self.spatial, self.cross_tile_bn)
        common = dict(
            spatial=self.spatial, bn_reduce_axes=bn_axes, dtype=self.dtype
        )
        s1 = ReluConvBn(features=self.channels, name="reduce1", **common)(s1)
        if self.reduction_prev:
            s2 = FactorizedReduce(features=self.channels, name="reduce2", **common)(s2)
        elif self.channels_prev_prev != self.channels:
            s2 = ReluConvBn(features=self.channels, name="reduce2", **common)(s2)

        if self.reduction:
            indices_ops, concat = REDUCTION_OPERATIONS, REDUCTION_CONCAT
        else:
            indices_ops, concat = NORMAL_OPERATIONS, NORMAL_CONCAT

        states = [s1, s2]
        for i in range(0, len(indices_ops), 2):
            i1, f1 = indices_ops[i]
            i2, f2 = indices_ops[i + 1]
            stride1 = 2 if (self.reduction and i1 < 2) else 1
            stride2 = 2 if (self.reduction and i2 < 2) else 1
            h1 = f1(self.channels, stride1, self.spatial, bn_axes, self.dtype, f"op{i}")(
                states[i1]
            )
            h2 = f2(self.channels, stride2, self.spatial, bn_axes, self.dtype, f"op{i+1}")(
                states[i2]
            )
            states.append(h1 + h2)

        return jnp.concatenate([states[i] for i in concat], axis=-1), skip


# -- D2 (fused-halo) design --------------------------------------------------
#
# Reference ``src/models/amoebanet_d2.py`` (``Cell_D2`` ``:569-678``,
# padding-free op variants ``:88-313``, genotype ``NORMAL_OPERATIONS_D2``
# ``:389-456``): instead of a halo exchange inside every windowed op of every
# normal cell, the cell pre-fetches wide halos with standalone exchanges
# (there: halo 3 + halo 2 states) and runs the ops VALID, cropping as the
# halo shrinks. Here the same amortization is *derived* rather than
# hand-tabled: ``_plan_state_halos`` walks the genotype backwards and
# computes, per cell state, the widest halo any consumer chain needs; the
# two input states are exchanged ONCE at that width and every op crops its
# source down to (its target's halo + its own window need). Boundary
# semantics stay bit-exact with the per-op (D1) form by re-filling the
# outside-image ring before every windowed op (``fill_boundary_halo``) and
# masking in-flight halo out of BN statistics — divergences the reference's
# D2 silently accepts.


class ConvBranchD2(nn.Module):
    """D2 twin of :class:`ConvBranch`: input carries ``halo_in`` rows/cols of
    neighbor data; each conv runs VALID and shrinks the halo by its D1
    padding. Parameter names match :class:`ConvBranch` exactly (``conv{i}`` /
    ``bn{i}``) so plain-model parameters drop in unchanged."""

    channels: int
    convs: Sequence[tuple[Any, Any, Any]]  # (kernel, stride, d1_padding)
    halo_in: int
    bottleneck: bool = False
    bn_reduce_axes: tuple[str, ...] = ()
    dtype: Any = None

    @nn.compact
    def __call__(self, x):
        from mpi4dl_tpu.parallel.halo import fill_boundary_halo

        c = self.channels
        inner = c // 4 if self.bottleneck else c
        hh = hw = self.halo_in
        common = dict(use_bias=False, spatial=True, exchange=False, dtype=self.dtype)

        def bn(idx):
            return TrainBatchNorm(
                reduce_axes=self.bn_reduce_axes,
                interior=(hh, hw),
                dtype=self.dtype,
                name=f"bn{idx}",
            )

        idx = 0
        if self.bottleneck:
            x = nn.relu(x)
            x = Conv2d(features=inner, kernel_size=1, padding=0, name=f"conv{idx}", **common)(x)
            x = bn(idx)(x)
            idx += 1
        for k, s, p in self.convs:
            if _pair_(s) != (1, 1):
                raise ValueError("D2 conv branches are stride-1 only")
            ph, pw = _pair_(p)
            x = nn.relu(x)
            if (hh or hw) and (ph or pw):
                x = fill_boundary_halo(x, hh, hw, 0.0)
            x = Conv2d(features=inner, kernel_size=k, strides=1, padding=0, name=f"conv{idx}", **common)(x)
            hh -= ph
            hw -= pw
            if hh < 0 or hw < 0:
                raise ValueError("halo_in too small for this conv branch")
            x = bn(idx)(x)
            idx += 1
        if self.bottleneck:
            x = nn.relu(x)
            x = Conv2d(features=c, kernel_size=1, padding=0, name=f"conv{idx}", **common)(x)
            x = bn(idx)(x)
        return x


class PoolD2(nn.Module):
    """D2 twin of :class:`~mpi4dl_tpu.ops.layers.Pool` for 3×3 stride-1
    pad-1 pools: input carries ``halo_in``, output carries ``halo_in - 1``.
    Outside-image ring is re-filled with the pool's neutral element
    (``-inf`` max / excluded-from-count avg), keeping D1 bit-parity."""

    kind: str
    halo_in: int
    count_include_pad: bool = True

    @nn.compact
    def __call__(self, x):
        from jax import lax as jlax

        from mpi4dl_tpu.parallel.halo import fill_boundary_halo, zero_boundary_halo

        from mpi4dl_tpu.ops.layers import max_pool_s1_valid

        h = self.halo_in
        if h < 1:
            raise ValueError("PoolD2 needs halo_in >= 1 (3x3 pad-1 window)")
        with jax.named_scope("mpi4dl_pool"):
            if self.kind == "max":
                x = fill_boundary_halo(x, h, h, float("-inf"))
                return max_pool_s1_valid(x, 3, 3)
            if self.kind != "avg":
                raise ValueError(f"unknown pool kind {self.kind!r}")
            x = zero_boundary_halo(x, h, h)
            if self.count_include_pad:
                return nn.avg_pool(x, (3, 3), strides=(1, 1), padding="VALID")
            ones = zero_boundary_halo(jnp.ones_like(x), h, h)
            num = jlax.reduce_window(x, 0.0, jlax.add, (1, 3, 3, 1), (1, 1, 1, 1), "valid")
            den = jlax.reduce_window(ones, 0.0, jlax.add, (1, 3, 3, 1), (1, 1, 1, 1), "valid")
            return num / den


def _pair_(v):
    if isinstance(v, (tuple, list)):
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


def _crop_halo(x, d: int):
    if d == 0:
        return x
    if d < 0:
        raise ValueError("cannot crop a negative halo margin")
    return x[:, d:-d, d:-d, :]


# D1 op factory -> (halo consumed by the op's windows, D2 factory).
# D2 factories: (channels, halo_in, bn_axes, dtype, name) -> module.
def _d2_conv_1x1(c, h, bn_axes, dtype, name):
    return ConvBranchD2(
        channels=c, convs=[(1, 1, 0)], halo_in=h, bottleneck=False,
        bn_reduce_axes=bn_axes, dtype=dtype, name=name,
    )


def _d2_conv_1x7_7x1(c, h, bn_axes, dtype, name):
    return ConvBranchD2(
        channels=c,
        convs=[((1, 7), (1, 1), (0, 3)), ((7, 1), (1, 1), (3, 0))],
        halo_in=h, bottleneck=True, bn_reduce_axes=bn_axes, dtype=dtype, name=name,
    )


def _d2_conv_3x3(c, h, bn_axes, dtype, name):
    return ConvBranchD2(
        channels=c, convs=[(3, 1, 1)], halo_in=h, bottleneck=True,
        bn_reduce_axes=bn_axes, dtype=dtype, name=name,
    )


def _d2_max_pool_3x3(c, h, bn_axes, dtype, name):
    return PoolD2(kind="max", halo_in=h, name=name)


def _d2_avg_pool_3x3(c, h, bn_axes, dtype, name):
    return PoolD2(kind="avg", halo_in=h, count_include_pad=False, name=name)


def _d2_none(c, h, bn_axes, dtype, name):
    return Identity(name=name)


D2_OPS = {
    op_conv_1x1: (0, _d2_conv_1x1),
    op_conv_1x7_7x1: (3, _d2_conv_1x7_7x1),
    op_conv_3x3: (1, _d2_conv_3x3),
    op_max_pool_3x3: (1, _d2_max_pool_3x3),
    op_avg_pool_3x3: (1, _d2_avg_pool_3x3),
    op_none: (0, _d2_none),
}


def _plan_state_halos(table) -> list[int]:
    """Per-state halo widths for one D2 cell: walk the genotype backwards so
    each state carries the widest halo any consumer chain needs. States 0/1
    are the cell inputs — their plan entry is the exchange width (the role of
    the reference's hand-chosen ``s3``/``s4`` halo sizes,
    ``amoebanet_d2.py:569-632``)."""
    halos = [0] * (2 + len(table) // 2)
    for i in reversed(range(0, len(table), 2)):
        tgt = 2 + i // 2
        for src, f in table[i : i + 2]:
            need, _ = D2_OPS[f]
            halos[src] = max(halos[src], halos[tgt] + need)
    return halos


class AmoebaCellD2(nn.Module):
    """Fused-halo normal cell (ref ``Cell_D2``, ``amoebanet_d2.py:569-678``):
    one wide :class:`~mpi4dl_tpu.ops.layers.HaloExchange` per input state
    (width from :func:`_plan_state_halos`), then the whole genotype runs
    VALID with per-op crops — 2 exchanges per cell instead of ~8.
    Parameter structure matches :class:`AmoebaCell` (reduction=False), so the
    plain model initializes it and D1/D2 are interchangeable mid-zoo."""

    channels_prev_prev: int
    channels_prev: int
    channels: int
    reduction_prev: bool
    cross_tile_bn: bool = True
    dtype: Any = None

    @nn.compact
    def __call__(self, input_or_states):
        if isinstance(input_or_states, (tuple, list)):
            s1, s2 = input_or_states
        else:
            s1 = s2 = input_or_states
        skip = s1

        bn_axes = _bn_axes(True, self.cross_tile_bn)
        common = dict(spatial=True, bn_reduce_axes=bn_axes, dtype=self.dtype)
        s1 = ReluConvBn(features=self.channels, name="reduce1", **common)(s1)
        if self.reduction_prev:
            s2 = FactorizedReduce(features=self.channels, name="reduce2", **common)(s2)
        elif self.channels_prev_prev != self.channels:
            s2 = ReluConvBn(features=self.channels, name="reduce2", **common)(s2)

        table, concat = NORMAL_OPERATIONS, NORMAL_CONCAT
        halos = _plan_state_halos(table)
        states = [
            HaloExchange(halo_len=halos[0])(s1) if halos[0] else s1,
            HaloExchange(halo_len=halos[1])(s2) if halos[1] else s2,
        ]
        for i in range(0, len(table), 2):
            tgt_halo = halos[2 + i // 2]
            pair = []
            for j, (src, f) in enumerate(table[i : i + 2]):
                need, d2f = D2_OPS[f]
                xin = _crop_halo(states[src], halos[src] - (tgt_halo + need))
                pair.append(
                    d2f(self.channels, tgt_halo + need, bn_axes, self.dtype, f"op{i + j}")(xin)
                )
            states.append(pair[0] + pair[1])
        out = jnp.concatenate(
            [_crop_halo(states[i], halos[i]) for i in concat], axis=-1
        )
        return out, skip


def amoebanetd(
    num_classes: int = 10,
    num_layers: int = 4,
    num_filters: int = 512,
    spatial_cells: int = 0,
    cross_tile_bn: bool = True,
    halo_d2: bool = False,
    dtype: Any = jnp.float32,
) -> list[nn.Module]:
    """AmoebaNet-D as a flat cell list (refs ``amoebanetd``
    ``amoebanet.py:535-615`` and ``amoebanetd_spatial`` ``:618-737`` unified:
    the first ``spatial_cells`` cells are spatial, the rest plain — the
    reference's ``layers_processed >= end_layer`` flip).

    Cell sequence: stem1, 2 reduction stems, then r normal / reduction /
    r normal / reduction / r normal (r = num_layers // 3), classifier.
    """
    if num_layers % 3:
        raise ValueError("num_layers must be a multiple of 3")
    r = num_layers // 3
    channels = num_filters // 4
    cells: list[nn.Module] = []

    state = dict(
        channels_prev_prev=channels, channels_prev=channels, reduction_prev=False,
        channels=channels,
    )

    def sp():
        return len(cells) < spatial_cells

    def add_cell(reduction: bool, channels_scale: int):
        state["channels"] *= channels_scale
        spatial = sp()
        if halo_d2 and spatial and not reduction:
            # D2 fused-halo form for spatial normal cells (ref picks Cell_D2
            # for exactly these, ``amoebanet_d2.py:896-914``); reduction
            # cells keep per-op (D1) exchanges — their stride-2 windows need
            # no halo under the power-of-two tile constraint.
            cell = AmoebaCellD2(
                channels_prev_prev=state["channels_prev_prev"],
                channels_prev=state["channels_prev"],
                channels=state["channels"],
                reduction_prev=state["reduction_prev"],
                cross_tile_bn=cross_tile_bn,
                dtype=dtype,
            )
        else:
            cell = AmoebaCell(
                channels_prev_prev=state["channels_prev_prev"],
                channels_prev=state["channels_prev"],
                channels=state["channels"],
                reduction=reduction,
                reduction_prev=state["reduction_prev"],
                spatial=spatial,
                cross_tile_bn=cross_tile_bn,
                dtype=dtype,
            )
        concat = REDUCTION_CONCAT if reduction else NORMAL_CONCAT
        state["channels_prev_prev"] = state["channels_prev"]
        state["channels_prev"] = state["channels"] * len(concat)
        state["reduction_prev"] = reduction
        cells.append(cell)

    cells.append(
        Stem(
            channels=channels,
            spatial=sp(),
            bn_reduce_axes=_bn_axes(sp(), cross_tile_bn),
            dtype=dtype,
        )
    )
    add_cell(reduction=True, channels_scale=2)
    add_cell(reduction=True, channels_scale=2)
    for _ in range(r):
        add_cell(reduction=False, channels_scale=1)
    add_cell(reduction=True, channels_scale=2)
    for _ in range(r):
        add_cell(reduction=False, channels_scale=1)
    add_cell(reduction=True, channels_scale=2)
    for _ in range(r):
        add_cell(reduction=False, channels_scale=1)
    cells.append(Classify(num_classes=num_classes, dtype=dtype))
    return cells
