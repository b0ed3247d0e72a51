"""What "cell" remat keeps by name (``train._cell_ckpt``,
``config.KERNEL_RESIDUAL``): what a cell's forward wrote that is dear to
compute and cheap to hold. Two tiny cells under that checkpoint and under a
bare ``jax.checkpoint``: the gradient's program holds each dear call once a
cell against the bare checkpoint's twice (so the test fails if a name or the
policy is lost, or a value is named after something has read it), and the
value and every gradient are the same bits, since what is kept is what the
replay computed.

**A fused kernel** (PR 44): every array its forward writes carries the name
in its ``custom_vjp`` forward rule. A projection, the kernel in the Pallas
interpreter, a projection: one forward and one backward call a cell, two and
one under the bare checkpoint; the causal convolution's kernels among them
since PR 47.

**The expert layer** (PR 46, ``ops/sequence.ExpertFFN``): the router's
product, ``top_k``'s choice and the chosen scores, the dispatch's integers
(``sizes``, both sorts' results, ``_by_token``'s four arrays), the gathered
rows and the grouped products' outputs carry it where they are made. A cell
``h + ExpertFFN(h)`` at tiny widths: the whole layer held (``C == P``, gated
SiLU experts), a share through ``_ranges_ffn``'s ``jit`` and ``custom_vjp``
(``C < P``, softmax scores; the conditionals' branches hold a forward and a
replay of their own in both programs, which no name reaches), and squared-
ReLU experts with a gated shared expert.

What the chip's compiler makes of it at the cells' widths is
``tests/test_tpu_compile.py``'s.
"""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.ops import (
    attention_pallas, causal_conv_pallas, delta_rule_pallas, sequence, ssd_scan_pallas)
from mpi4dl_tpu.train import _cell_ckpt

CELLS, WIDTH = 2, 32


def _attention(masked, z):
    """``z [B, S, 4 x 64]``: two query heads of 64, their one key and value;
    under the mask ``S`` is both copies' rows."""
    b, s, _ = z.shape
    q, k, v = jnp.split(z, [128, 192], axis=-1)
    return attention_pallas.attention(
        q.reshape(b, s, 1, 2, 64), k.reshape(b, s, 1, 64), v.reshape(b, s, 1, 64),
        attention_pallas.Plan(128, 2), True,
        sequence.BlockMask(s // 2, 4) if masked else None)


def _delta_rule(z):
    """``z [B, S, 4 x 128 + 4]``: one key head of 128, its two value heads."""
    b, s, _ = z.shape
    q, k, v, g, beta = jnp.split(z, [128, 256, 512, 514], axis=-1)
    q, k = (t / jnp.linalg.norm(t.astype(jnp.float32), axis=-1, keepdims=True).astype(t.dtype)
            for t in (q, k))
    return delta_rule_pallas.rule(
        q.reshape(b, s, 1, 128), k.reshape(b, s, 1, 128), v.reshape(b, s, 1, 2, 128),
        -jax.nn.softplus(g.astype(jnp.float32)).reshape(b, s, 1, 2),
        jax.nn.sigmoid(beta.astype(jnp.float32)).reshape(b, s, 1, 2),
        sequence.RULE_CHUNK, interpret=True)


def _ssd_scan(z):
    """``z [B, S, 2 x 64 + 2 x 128 + 2]``: one group of two heads of 64, a
    state of 128."""
    b, s, _ = z.shape
    x, bb, c, g = jnp.split(z, [128, 256, 384], axis=-1)
    return ssd_scan_pallas.scan(
        x.reshape(b, s, 1, 2, 64), -jax.nn.softplus(g.astype(jnp.float32)).reshape(b, s, 1, 2),
        bb.reshape(b, s, 1, 128), c.reshape(b, s, 1, 128), 128, interpret=True)


def _causal_conv(z):
    """``z [B, S, 256]``: four taps (fixed: the cell's parameters are its two
    projections) and a SiLU over 256 channels."""
    taps = jax.random.normal(jax.random.PRNGKey(7), (4, z.shape[-1])).astype(z.dtype) * 0.5
    return causal_conv_pallas.conv_silu(
        z, taps, plan=causal_conv_pallas.Plan(causal_conv_pallas.ROWS, 128), interpret=True)


# the kernel, the positions, the widths the projections go to and come from,
# and the calls' names
KERNELS = {
    "attention_causal": (functools.partial(_attention, False), 256, 256, 128,
                         (attention_pallas.FWD_NAME, attention_pallas.BWD_NAME)),
    "attention_block_mask": (functools.partial(_attention, True), 512, 256, 128,
                             (attention_pallas.BLOCKDIFF_FWD_NAME,
                              attention_pallas.BLOCKDIFF_BWD_NAME)),
    "delta_rule": (_delta_rule, 128, 516, 256,
                   (delta_rule_pallas.FWD_NAME, delta_rule_pallas.BWD_NAME)),
    "ssd_scan": (_ssd_scan, 256, 386, 128,
                 (ssd_scan_pallas.FWD_NAME, ssd_scan_pallas.BWD_NAME)),
    "causal_conv": (_causal_conv, 2 * causal_conv_pallas.ROWS, 256, 256,
                    (causal_conv_pallas.FWD_NAME, causal_conv_pallas.BWD_NAME)),
}


def _calls(jaxpr):
    """``{a pallas_call's name, any other equation's primitive: how many}``
    over a jaxpr and every jaxpr in its equations' parameters."""
    counts = collections.Counter()
    for eqn in jaxpr.eqns:
        counts[eqn.params["name"] if eqn.primitive.name == "pallas_call"
               else eqn.primitive.name] += 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    counts += _calls(inner)
    return counts


def _kernel_cells(case):
    """``(cell, its parameters a cell, the input, {call: (a cell's under
    _cell_ckpt, under a bare checkpoint)})`` of a kernel between projections."""
    kernel, length, wide, narrow, (fwd, bwd) = KERNELS[case]
    keys = jax.random.split(jax.random.PRNGKey(0), 2 * CELLS + 1)
    params = [
        ((jax.random.normal(keys[2 * i], (WIDTH, wide)) * WIDTH ** -0.5).astype(jnp.bfloat16),
         (jax.random.normal(keys[2 * i + 1], (narrow, WIDTH)) * narrow ** -0.5
          ).astype(jnp.bfloat16))
        for i in range(CELLS)]
    h = jax.random.normal(keys[-1], (1, length, WIDTH)).astype(jnp.bfloat16)

    def cell(p, h):
        out = kernel(h @ p[0])
        return h + out.reshape(*h.shape[:2], narrow) @ p[1]

    return cell, params, h, {fwd: (1, 2), bwd: (1, 1)}


# an expert layer at tiny widths and a cell's calls: forward + gradients under
# ``_cell_ckpt``, with the replay's beside them under a bare checkpoint. The
# router's product is a ``dot_general`` (two gradients), and so are a shared
# expert's two products and its gate's (whose replay keeps the gate and the
# product it scales, the way out of the shared expert: two of four)
EXPERTS = {
    "experts_whole": (
        dict(experts=4, held=4, first=0, per_token=2),
        {"ragged_dot_general": (3 + 6, 3 + 3 + 6), "top_k": (1, 2), "sort": (3, 6),
         "dot_general": (1 + 2, 1 + 1 + 2)}),
    # in the two conditionals' branches, in both programs: a forward (3
    # products, a sort), and its replay with its gradients (3 + 6, a sort)
    "experts_share_two_ranges": (
        dict(experts=16, held=4, first=4, per_token=2, scoring="softmax"),
        {"ragged_dot_general": (9 + 12, 12 + 12), "top_k": (1, 2), "sort": (3 + 2, 6 + 2),
         "dot_general": (3, 4)}),
    "experts_relu2_gated_shared": (
        dict(experts=8, held=2, first=0, per_token=2, activation="relu2",
             shared_width=24),
        {"ragged_dot_general": (6 + 8, 8 + 8), "top_k": (1, 2), "sort": (3 + 2, 6 + 2),
         "dot_general": (3 + 9 + 2, 4 + 9 + 3)}),
}


def _expert_cells(case):
    """The same of ``h + ExpertFFN(h)``: 32 tokens of width 32, experts of
    width 16."""
    fields, calls = EXPERTS[case]
    layer = sequence.ExpertFFN(hidden=WIDTH, width=16, **fields)
    keys = jax.random.split(jax.random.PRNGKey(0), CELLS + 1)
    h = jax.random.normal(keys[-1], (2, 16, WIDTH))
    return (lambda p, h: h + layer.apply(p, h),
            [layer.init(key, h) for key in keys[:CELLS]], h, calls)


CASES = {**{case: _kernel_cells for case in KERNELS},
         **{case: _expert_cells for case in EXPERTS}}


@pytest.mark.parametrize("case", CASES)
def test_cell_checkpoint_keeps_what_the_kernel_forward_wrote(case):
    cell, params, h, calls = CASES[case](case)

    def loss(ckpt, params, h):
        for p in params:
            h = ckpt(cell)(p, h)
        return jnp.mean(h.astype(jnp.float32) ** 2)

    def step(ckpt):
        return jax.value_and_grad(functools.partial(loss, ckpt), argnums=(0, 1))

    for ckpt, which in ((_cell_ckpt(), 0), (jax.checkpoint, 1)):
        found = _calls(jax.make_jaxpr(step(ckpt))(params, h).jaxpr)
        assert {name: found[name] for name in calls} == {
            name: CELLS * counts[which] for name, counts in calls.items()}

    got, want = jax.jit(step(_cell_ckpt()))(params, h), jax.jit(step(jax.checkpoint))(params, h)
    assert np.isfinite(float(got[0])) and float(got[0]) > 0
    for (path, a), b in zip(jax.tree.leaves_with_path(got), jax.tree.leaves(want)):
        # the choice has no derivative: the bias it is made on gets zeros
        assert np.any(np.asarray(a, np.float32) != 0) or "expert_bias" in jax.tree_util.keystr(path)
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
