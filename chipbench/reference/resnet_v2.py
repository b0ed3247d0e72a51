"""ResNet v2 (pre-activation bottleneck, depth 9n+2), plain float32 reference.

He et al., "Identity Mappings in Deep Residual Networks"
(arXiv:1603.05027), in the CIFAR-style form of the Keras example that
MPI4DL's ``resnet.py`` copies: a 3x3 conv-BN-ReLU stem of 16 channels,
three stages of ``n`` bottleneck cells (bottleneck widths 16, 64, 128;
outputs 64, 128, 256; stages two and three open with stride 2), then
BN-ReLU, an average pool over the whole map and a linear classifier.

Departures, inherited from MPI4DL and named there: the bottleneck is
3x3, 3x3, 1x1 (not 1x1, 3x3, 1x1); the very first cell skips its leading
BN-ReLU; the head pools the whole ``image/4`` map (the Keras example
pools 8x8 of a 32 px image, which is the same thing) and returns logits.

Parameter names (``r1/conv/conv/kernel`` ...) are those of the served
program's tree.
"""

from __future__ import annotations

import functools

from . import plain
from .plain import batch_norm, conv, relu


def _layer(scope, x, features, kernel=3, strides=1, pre_activation=True):
    """(BN-ReLU-)conv with bias; padding keeps the size at stride 1."""
    if pre_activation:
        x = relu(batch_norm(scope.sub("bn"), x))
    return conv(scope.sub("conv").sub("conv"), x, features, kernel, strides,
                (kernel - 1) // 2, bias=True)


@functools.lru_cache(maxsize=None)
def _stem(features):
    def stem(scope, x):
        x = conv(scope.sub("conv").sub("conv"), x, features, 3, 1, 1, bias=True)
        return relu(batch_norm(scope.sub("bn"), x))

    return stem


@functools.lru_cache(maxsize=None)
def _cell(width, features_out, strides, first_of_stage, first_of_net):
    def cell(scope, x):
        y = _layer(scope.sub("r1"), x, width, 3, strides, not first_of_net)
        y = _layer(scope.sub("r2"), y, width)
        y = _layer(scope.sub("r3"), y, features_out, 1)
        if first_of_stage:
            x = _layer(scope.sub("r4"), x, features_out, 1, strides, False)
        return x + y

    return cell


@functools.lru_cache(maxsize=None)
def _head(num_classes):
    def head(scope, x):
        x = relu(batch_norm(scope.sub("bn"), x))
        x = plain.avg_pool(x, x.shape[1:3], x.shape[1:3])
        x = x.reshape(x.shape[0], -1)
        return plain.dense(scope.sub("fc").sub("fc"), x, num_classes)

    return head


def kinds(config: dict) -> list:
    """The kind of each cell of :func:`cells`, in order; the correctness
    check taps one cell of every kind."""
    blocks = (int(config["depth"]) - 2) // 9
    out = ["stem"]
    for stage in range(3):
        first = "opening" if stage == 0 else "stride2"
        out += [first] + ["stride1"] * (blocks - 1)
    return out + ["head"]


def cells(config: dict) -> list:
    """The model of ``config`` (``depth``, ``num_classes``) as a list of
    ``cell(scope, x)`` functions."""
    depth = int(config["depth"])
    if (depth - 2) % 9:
        raise ValueError("depth must be 9n+2")
    blocks = (depth - 2) // 9
    out = [_stem(16)]
    width = 16
    for stage in range(3):
        features_out = width * (4 if stage == 0 else 2)
        for block in range(blocks):
            out.append(
                _cell(
                    width, features_out,
                    2 if stage > 0 and block == 0 else 1,
                    block == 0, stage == 0 and block == 0,
                )
            )
        width = features_out
    out.append(_head(int(config["num_classes"])))
    return out
