"""What "cell" remat keeps of a fused kernel (``train._cell_ckpt``): every
array a kernel's forward writes carries ``config.KERNEL_RESIDUAL`` in its
``custom_vjp`` forward rule, and the cell's checkpoint keeps that name, so a
step runs each kernel's forward once a cell. Two tiny cells (a projection,
the kernel in the Pallas interpreter, a projection) under that checkpoint
and under a bare ``jax.checkpoint``: the gradient's program holds one forward
and one backward call a cell against the bare checkpoint's two forwards (so
the test fails if the names or the policy are lost), and the value and every
gradient are the same bits, since what is kept is what the replay computed.

What the chip's compiler makes of it at the cells' widths is
``tests/test_tpu_compile.py``'s.
"""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4dl_tpu.ops import attention_pallas, delta_rule_pallas, sequence, ssd_scan_pallas
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
}


def _calls(jaxpr):
    """``{a pallas_call's name: how many}`` over a jaxpr and every jaxpr in
    its equations' parameters."""
    counts = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[eqn.params["name"]] += 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    counts += _calls(inner)
    return counts


@pytest.mark.parametrize("case", KERNELS)
def test_cell_checkpoint_keeps_what_the_kernel_forward_wrote(case):
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

    def loss(ckpt, params, h):
        for p in params:
            h = ckpt(cell)(p, h)
        return jnp.mean(h.astype(jnp.float32) ** 2)

    def step(ckpt):
        return jax.value_and_grad(functools.partial(loss, ckpt), argnums=(0, 1))

    assert _calls(jax.make_jaxpr(step(_cell_ckpt()))(params, h).jaxpr) == {
        fwd: CELLS, bwd: CELLS}
    assert _calls(jax.make_jaxpr(step(jax.checkpoint))(params, h).jaxpr) == {
        fwd: 2 * CELLS, bwd: CELLS}

    got, want = jax.jit(step(_cell_ckpt()))(params, h), jax.jit(step(jax.checkpoint))(params, h)
    assert np.isfinite(float(got[0])) and float(got[0]) > 0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.any(np.asarray(a, np.float32) != 0)
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
