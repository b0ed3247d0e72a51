"""AmoebaNet-D, plain float32 reference.

The evolved cell of Real et al., "Regularized Evolution for Image
Classifier Architecture Search" (arXiv:1802.01548), in the fixed form that
GPipe (arXiv:1811.06965, AmoebaNet-D (L, D)) and torchgpipe / MPI4DL
(``amoebanet.py``) train: a stride-2 stem, two reduction cells, then three
groups of ``L/3`` normal cells with a reduction cell between groups, a
global average pool and a linear classifier. A cell takes the two previous
states, brings both to its width with a 1x1 ``relu-conv-bn``, applies five
pairs of operations and concatenates chosen states; it hands on
``(concat, previous concat)``.

Departures from the publication, all inherited from the MPI4DL code this
system rebuilds (and named there): the normal cell concatenates states
``[0, 3, 4, 6]`` (the TF implementation's choice); ``max_pool_3x3`` is a
real max pool (MPI4DL's builds an average pool by a slip); the factorised
reduction gives both of its 1x1 stride-2 convs the same, unshifted input.

Parameter names (``reduce1/conv/conv/kernel``, ``op3/bn0/scale`` ...)
are those of the served program's tree.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp

from . import plain
from .plain import batch_norm, conv, relu

# (index of the state it reads, operation) x 10; states 0 and 1 are the
# cell's two inputs, each later state is the sum of one pair.
NORMAL = (
    (1, "conv_1x1"), (1, "max_pool_3x3"),
    (1, "none"), (0, "conv_1x7_7x1"),
    (0, "conv_1x1"), (0, "conv_1x7_7x1"),
    (2, "max_pool_3x3"), (2, "none"),
    (1, "avg_pool_3x3"), (5, "conv_1x1"),
)
NORMAL_CONCAT = (0, 3, 4, 6)
REDUCTION = (
    (0, "max_pool_2x2"), (0, "max_pool_3x3"),
    (2, "none"), (1, "conv_3x3"),
    (2, "conv_1x7_7x1"), (2, "max_pool_3x3"),
    (3, "none"), (1, "max_pool_2x2"),
    (2, "avg_pool_3x3"), (3, "conv_1x1"),
)
REDUCTION_CONCAT = (4, 5, 6)


def _relu_conv_bn(scope, x, features, kernel=1, strides=1, padding=0,
                  conv_name="conv", bn_name="bn"):
    x = conv(scope.sub(conv_name).sub("conv"), relu(x), features, kernel,
             strides, padding)
    return batch_norm(scope.sub(bn_name), x)


def _factorized_reduce(scope, x, features):
    x = relu(x)
    a = conv(scope.sub("conv1").sub("conv"), x, features // 2, 1, 2)
    b = conv(scope.sub("conv2").sub("conv"), x, features - features // 2, 1, 2)
    return batch_norm(scope.sub("bn"), jnp.concatenate([a, b], axis=-1))


def _conv_branch(scope, x, channels, convs, bottleneck):
    """relu-conv-bn per entry of ``convs``, inside a c -> c/4 -> c pair of
    1x1 convs when ``bottleneck``."""
    inner = channels // 4 if bottleneck else channels
    steps = [(1, 1, 0)] if bottleneck else []
    steps += list(convs)
    widths = [inner] * len(steps)
    if bottleneck:
        steps.append((1, 1, 0))
        widths.append(channels)
    for i, ((k, s, p), width) in enumerate(zip(steps, widths)):
        x = _relu_conv_bn(scope, x, width, k, s, p, f"conv{i}", f"bn{i}")
    return x


def _operation(scope, name, x, channels, stride):
    if name == "none":
        return x if stride == 1 else _factorized_reduce(scope, x, channels)
    if name == "avg_pool_3x3":
        return plain.avg_pool(x, 3, stride, 1)
    if name == "max_pool_3x3":
        return plain.max_pool(x, 3, stride, 1)
    if name == "max_pool_2x2":
        return plain.max_pool(x, 2, stride, 0)
    if name == "conv_1x1":
        return _conv_branch(scope, x, channels, [(1, stride, 0)], False)
    if name == "conv_3x3":
        return _conv_branch(scope, x, channels, [(3, stride, 1)], True)
    if name == "conv_1x7_7x1":
        return _conv_branch(
            scope, x, channels,
            [((1, 7), (1, stride), (0, 3)), ((7, 1), (stride, 1), (3, 0))],
            True,
        )
    raise ValueError(f"unknown operation {name!r}")


@functools.lru_cache(maxsize=None)
def _stem(channels):
    def stem(scope, x):
        return _relu_conv_bn(scope, x, channels, 3, 2, 1)

    return stem


@functools.lru_cache(maxsize=None)
def _cell(channels_prev_prev, channels, reduction, reduction_prev):
    table, concat = (
        (REDUCTION, REDUCTION_CONCAT) if reduction else (NORMAL, NORMAL_CONCAT)
    )

    def cell(scope, states):
        s1, s2 = states if isinstance(states, (tuple, list)) else (states, states)
        skip = s1
        s1 = _relu_conv_bn(scope.sub("reduce1"), s1, channels)
        if reduction_prev:
            s2 = _factorized_reduce(scope.sub("reduce2"), s2, channels)
        elif channels_prev_prev != channels:
            s2 = _relu_conv_bn(scope.sub("reduce2"), s2, channels)
        states = [s1, s2]
        for i in range(0, len(table), 2):
            pair = []
            for j in (i, i + 1):
                src, name = table[j]
                stride = 2 if reduction and src < 2 else 1
                pair.append(
                    _operation(scope.sub(f"op{j}"), name, states[src],
                               channels, stride)
                )
            states.append(pair[0] + pair[1])
        return jnp.concatenate([states[i] for i in concat], axis=-1), skip

    return cell


@functools.lru_cache(maxsize=None)
def _classify(num_classes):
    def classify(scope, states):
        x, _ = states
        return plain.dense(scope.sub("fc"), jnp.mean(x, axis=(1, 2)), num_classes)

    return classify


def kinds(config: dict) -> list:
    """The kind of each cell of :func:`cells`, in order; the correctness
    check taps one cell of every kind."""
    normal = ["normal"] * (int(config["num_layers"]) // 3)
    return (["stem", "reduction", "reduction"] + normal + ["reduction"]
            + normal + ["reduction"] + normal + ["head"])


def cells(config: dict) -> list:
    """The model of ``config`` (``num_layers``, ``num_filters``,
    ``num_classes``) as a list of ``cell(scope, x)`` functions."""
    layers, filters = int(config["num_layers"]), int(config["num_filters"])
    if layers % 3:
        raise ValueError("num_layers must be a multiple of 3")
    channels = filters // 4
    out = [_stem(channels)]
    prev_prev = prev = channels
    reduction_prev = False

    def add(reduction):
        nonlocal channels, prev_prev, prev, reduction_prev
        if reduction:
            channels *= 2
        out.append(_cell(prev_prev, channels, reduction, reduction_prev))
        width = len(REDUCTION_CONCAT if reduction else NORMAL_CONCAT)
        prev_prev, prev, reduction_prev = prev, channels * width, reduction

    add(True)
    add(True)
    for group in range(3):
        if group:
            add(True)
        for _ in range(layers // 3):
            add(False)
    out.append(_classify(int(config["num_classes"])))
    return out
