"""Plain float32 building blocks of the reference models.

Everything here is ``jax.numpy`` / ``lax.conv_general_dilated`` /
``lax.reduce_window`` in float32 with ``Precision.HIGHEST`` (on a TPU a
float32 convolution otherwise multiplies in bf16 passes). No kernel, no
packed layout, no remat, no ``shard_map``; nothing of ``mpi4dl_tpu`` is
imported. A model is a list of cell functions ``cell(scope, x) -> y``;
a :class:`Scope` hands a cell its parameters by name, and the names are
the ones the served program's parameter tree uses, so that the weights
the benchmark makes here can be given to the program unchanged.

``mode`` is the arithmetic of the matrix multiplications:

- ``"f32"``: the reference;
- ``"bf16"``: both operands of every conv / dense rounded to bfloat16, in
  the backward multiplications too (the output's cotangent is rounded);
- ``"fp8"``: the same with float8 — operands to e4m3, the output's
  cotangent to e5m2, each scaled per tensor by its largest magnitude, as
  fp8 training recipes do; sums stay float32. The precision below
  bfloat16, the step that would tempt a later PR. It is the *control* of
  the correctness check: the check has to refuse it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

MODES = ("f32", "bf16", "fp8")


class Scope:
    """A cell's parameters by name. With ``params=None`` it records, into
    ``spec``, the path, shape and initialiser of every parameter asked for
    (run under ``jax.eval_shape``); with a tree it hands them out."""

    def __init__(self, params=None, mode="f32", spec=None, path=()):
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
        self.params, self.mode, self.spec, self.path = params, mode, spec, path

    def sub(self, name):
        # An operation without parameters (a pool, an identity) has no
        # entry in the tree; only asking it for one is an error.
        params = (self.params or {}).get(name)
        return Scope(params, self.mode, self.spec, self.path + (name,))

    def param(self, name, shape, init):
        if self.spec is not None:
            self.spec[self.path + (name,)] = (tuple(shape), init)
            return jnp.zeros(shape, jnp.float32)
        if self.params is None or name not in self.params:
            raise KeyError("/".join(self.path + (name,)))
        return self.params[name]


# -- the lower-precision arithmetic of the control ---------------------------


def _scaled_round(x, dtype):
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _round(x, mode, backward):
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return _scaled_round(x, jnp.float8_e5m2 if backward else jnp.float8_e4m3fn)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _operand(x, mode):
    return _round(x, mode, False)


_operand.defvjp(lambda x, mode: (_round(x, mode, False), None),
                lambda mode, _, ct: (ct,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _product(y, mode):
    return y


_product.defvjp(lambda y, mode: (y, None),
                lambda mode, _, ct: (_round(ct, mode, True),))


def operand(x, mode):
    """One operand of a forward matrix multiplication in ``mode``."""
    return x if mode == "f32" else _operand(x, mode)


def product(y, mode):
    """A matrix multiplication's result: its cotangent is an operand of
    both backward multiplications, and is rounded as one."""
    return y if mode == "f32" else _product(y, mode)


# -- layers ------------------------------------------------------------------


def _pair(v):
    return (int(v[0]), int(v[1])) if isinstance(v, (tuple, list)) else (int(v), int(v))


def conv(scope, x, features, kernel, strides=1, padding=0, bias=False):
    """2-D convolution, NHWC x HWIO, symmetric zero padding."""
    kh, kw = _pair(kernel)
    ph, pw = _pair(padding)
    w = scope.param("kernel", (kh, kw, x.shape[-1], features), "fan_in")
    y = lax.conv_general_dilated(
        operand(x, scope.mode),
        operand(w, scope.mode),
        window_strides=_pair(strides),
        padding=((ph, ph), (pw, pw)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST,
    )
    y = product(y, scope.mode)
    if bias:
        y = y + scope.param("bias", (features,), "zeros")
    return y


def dense(scope, x, features):
    w = scope.param("kernel", (x.shape[-1], features), "fan_in")
    b = scope.param("bias", (features,), "zeros")
    y = jnp.dot(
        operand(x, scope.mode), operand(w, scope.mode),
        precision=lax.Precision.HIGHEST,
    )
    return product(y, scope.mode) + b


def batch_norm(scope, x, eps=1e-5):
    """Training-mode batch normalisation over (batch, height, width)."""
    c = x.shape[-1]
    scale = scope.param("scale", (c,), "ones")
    bias = scope.param("bias", (c,), "zeros")
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * scale + bias


def relu(x):
    return jnp.maximum(x, 0.0)


def _window(x, init, op, kernel, strides, padding):
    kh, kw = _pair(kernel)
    sh, sw = _pair(strides)
    ph, pw = _pair(padding)
    return lax.reduce_window(
        x, init, op, (1, kh, kw, 1), (1, sh, sw, 1),
        ((0, 0), (ph, ph), (pw, pw), (0, 0)),
    )


def max_pool(x, kernel, strides, padding=0):
    """Max pool; the padding ring never wins (torch ``MaxPool2d``)."""
    return _window(x, -jnp.inf, lax.max, kernel, strides, padding)


def avg_pool(x, kernel, strides, padding=0):
    """Average pool that divides by the number of real pixels under the
    window (torch ``count_include_pad=False``)."""
    num = _window(x, 0.0, lax.add, kernel, strides, padding)
    den = _window(jnp.ones(x.shape[1:3], x.dtype)[None, :, :, None], 0.0,
                  lax.add, kernel, strides, padding)
    return num / den


# -- parameters from a seed --------------------------------------------------


def record_specs(cells, x_shape, x_dtype=jnp.float32):
    """Per cell, ``{path: (shape, init)}`` of its parameters, found by
    running the model abstractly on an input of ``x_shape`` and ``x_dtype``
    (float32 images; a token model's ids are integers)."""
    specs = []

    def run(x):
        for cell in cells:
            spec = {}
            x = cell(Scope(spec=spec), x)
            specs.append(spec)
        return x

    jax.eval_shape(run, jax.ShapeDtypeStruct(tuple(x_shape), x_dtype))
    return specs


def takes_cotangent(x) -> bool:
    """Whether a cell's input (arrays or shapes) has a cotangent: one of
    floating leaves has, one of integer leaves (token ids) has none."""
    floating = [jnp.issubdtype(a.dtype, jnp.floating) for a in jax.tree.leaves(x)]
    if any(floating) != all(floating):
        raise TypeError("a cell's input mixes floating and integer leaves")
    return all(floating)


def vjp(apply, v, x):
    """``jax.vjp`` of ``apply(v, x)`` over both, or over ``v`` alone where
    ``x`` has no cotangent: the pull-back then returns ``(dv,)``."""
    if takes_cotangent(x):
        return jax.vjp(apply, v, x)
    return jax.vjp(lambda v_: apply(v_, x), v)


def cast_floating(x, dtype):
    """``x`` with its floating leaves in ``dtype``; integer leaves (token
    ids) as they are."""
    return jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a,
        x)


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


def params_maker(specs):
    """``seed -> parameters``: the whole tree in one jitted call, float32,
    on the default device: conv and dense kernels normal with standard
    deviation ``1/sqrt(fan_in)`` (the LeCun rule the program's layers use,
    without its truncation), a parameter whose ``init`` is a float normal
    with that standard deviation (an embedding table), scales one
    (``"ones"``), every bias zero. One
    ``{"params": {...}}`` tree per cell. (One maker per run: a second call
    finds its program already traced.)"""

    def build(key):
        out = []
        for i, spec in enumerate(specs):
            tree: dict = {}
            for j, (path, (shape, init)) in enumerate(sorted(spec.items())):
                def normal(std):
                    k = jax.random.fold_in(jax.random.fold_in(key, i), j)
                    return jax.random.normal(k, shape, jnp.float32) * std

                if init == "fan_in":
                    fan_in = 1
                    for d in shape[:-1]:
                        fan_in *= d
                    leaf = normal(fan_in**-0.5)
                elif isinstance(init, float):
                    leaf = normal(init)
                elif init == "ones":
                    leaf = jnp.ones(shape, jnp.float32)
                else:
                    leaf = jnp.zeros(shape, jnp.float32)
                node = tree
                for name in path[:-1]:
                    node = node.setdefault(name, {})
                node[path[-1]] = leaf
            out.append({"params": tree})
        return out

    jitted_build = jax.jit(build)
    return lambda seed: jitted_build(seed_key(seed))


def make_params(specs, seed: int):
    """``params_maker(specs)(seed)``, for one-off use."""
    return params_maker(specs)(seed)


@functools.lru_cache(maxsize=None)
def jitted(fn, mode):
    """``fn(scope, x)`` as a jitted ``(variables, x) -> y``; cells built
    from equal settings are one function object and so share one program."""
    return jax.jit(lambda v, x: fn(Scope(v["params"], mode), x))
