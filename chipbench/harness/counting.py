"""Operations and bytes the model needs, counted on the plain reference.

Copied from ``mpi4dl_tpu/flops.py`` (conv and matmul FLOPs read off the
forward pass's jaxpr, training = 3 x forward: forward, input gradient,
weight gradient) and pointed at the benchmark's own reference model, so
that the count cannot move with the program: the packed convolution
executes about 1.7x the model's FLOPs and recomputation executes them
again; neither counts.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference.plain import Scope, record_specs


def _forward_jaxpr(cells, x_shape, x_dtype):
    specs = record_specs(cells, x_shape, x_dtype)

    def shapes(spec):
        tree: dict = {}
        for path, (shape, _) in spec.items():
            node = tree
            for name in path[:-1]:
                node = node.setdefault(name, {})
            node[path[-1]] = jax.ShapeDtypeStruct(shape, jnp.float32)
        return tree

    params = [shapes(s) for s in specs]

    def run(ps, x):
        for cell, p in zip(cells, ps):
            x = cell(Scope(p), x)
        return x

    return jax.make_jaxpr(run)(
        params, jax.ShapeDtypeStruct(tuple(x_shape), x_dtype)
    ).jaxpr


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in _subjaxprs(val):
                yield from _walk(sub)


def _subjaxprs(val):
    if hasattr(val, "eqns"):
        yield val
    elif hasattr(val, "jaxpr"):
        yield val.jaxpr
    elif isinstance(val, (tuple, list)):
        for item in val:
            yield from _subjaxprs(item)


def _eqn_flops(eqn) -> float:
    prim = eqn.primitive.name
    if prim == "conv_general_dilated":
        out = eqn.outvars[0].aval
        rhs = eqn.invars[1].aval
        dnums = eqn.params["dimension_numbers"]
        kernel_spatial = [rhs.shape[d] for d in dnums.rhs_spec[2:]]
        cin = rhs.shape[dnums.rhs_spec[1]]
        return 2.0 * out.size * math.prod(kernel_spatial) * cin
    if prim == "dot_general":
        lhs, rhs = (v.aval for v in eqn.invars[:2])
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        batch = math.prod(lhs.shape[d] for d in lb)
        k = math.prod(lhs.shape[d] for d in lc)
        m = math.prod(s for d, s in enumerate(lhs.shape) if d not in set(lc) | set(lb))
        n = math.prod(s for d, s in enumerate(rhs.shape) if d not in set(rc) | set(rb))
        return 2.0 * batch * m * n * k
    return 0.0


def train_flops_per_sample(cells, sample_shape, x_dtype) -> float:
    """3 x the forward pass's conv and matmul FLOPs for one sample (an
    image; a sequence). Right for a model whose every parameter multiplies
    every sample. A reference that computes more than the model asks for (a
    plain expert layer multiplies every expert by every token) defines its
    own ``train_flops_per_sample(model, traffic)``, which the harness takes
    in this one's place."""
    jaxpr = _forward_jaxpr(cells, (1,) + tuple(sample_shape), x_dtype)
    return 3.0 * sum(_eqn_flops(e) for e in _walk(jaxpr))


def stride1_max_pool_bytes(cells, x_shape, x_dtype, itemsize: int) -> float:
    """The least bytes the backward passes of the model's stride-1 3x3 max
    pools must move in one step on a batch of ``x_shape``: read the pool's
    input and the output's cotangent, write the input's cotangent, each
    once, at ``itemsize`` bytes an element (the window shapes are found on
    the reference's forward pass)."""
    total = 0.0
    for eqn in _walk(_forward_jaxpr(cells, x_shape, x_dtype)):
        if eqn.primitive.name != "reduce_window_max":
            continue
        if tuple(eqn.params["window_dimensions"]) != (1, 3, 3, 1):
            continue
        if tuple(eqn.params["window_strides"]) != (1, 1, 1, 1):
            continue
        total += 2 * eqn.invars[0].aval.size + eqn.outvars[0].aval.size
    return total * itemsize
