"""SDAR-MoE under block-diffusion training, at tiny widths on the CPU mesh.

The program (``mpi4dl_tpu/models/sdar.py``, the mask structure of
``ops/sequence.py`` and ``ops/attention_pallas.py``, ``Trainer``'s loss taken
from the model, ``data.BlockDiffusionTokens``, the entry script) against the
benchmark's plain float32 reference (``chipbench/reference/sdar.py``, which
imports nothing of the program) on seeded weights; the mask against a dense
one written out from its four rules; the kernels in the Pallas interpreter
against the plain path; the 16-of-128 cut tied to the whole layer.
"""

import json
import os
import runpy
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.harness import check
from chipbench.reference import plain
from chipbench.reference import sdar as ref
from chipbench.reference.step import Follower
from mpi4dl_tpu.config import ParallelConfig
from mpi4dl_tpu.data import BlockDiffusionTokens
from mpi4dl_tpu.models.sdar import block_diffusion_loss, sdar
from mpi4dl_tpu.ops import attention_pallas, sequence
from mpi4dl_tpu.train import Trainer, TrainState, default_remat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_JSON = os.path.join(REPO, "benchmarks", "layer_parallelism", "sdar_tiny.json")

# SDAR-30B-A3B-Chat's layer at toy widths; this "chip" holds experts 4-7 of 16.
MODEL = {
    "hidden_size": 32, "moe_intermediate_size": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "num_hidden_layers": 2, "num_experts": 4,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "vocab_size": 48,
    "block_length": 4,
    "cut": {"num_experts": {"published": 16, "held": 4, "first": 4},
            "num_hidden_layers": {"published": 6, "held": 2}},
}
BATCH, LENGTH = 2, 24
KINDS = ["stem", "attn_blockdiff", "moe_blockdiff", "attn_blockdiff", "moe_blockdiff", "head"]


def _seeded(model=MODEL, batch=BATCH, length=LENGTH, seed=3000000019):
    cells = ref.cells(model)
    specs = plain.record_specs(cells, (batch, 2 * length), jnp.int32)
    return cells, plain.make_params(specs, seed)


def _stream(seed=7, batch=BATCH, length=LENGTH, **kwargs):
    return BlockDiffusionTokens(
        batch, length, MODEL["vocab_size"], seed=seed, prefetch=False, **kwargs)


@pytest.fixture(scope="module")
def forced():
    """The reference's cells, seeded weights, and each cell's input on one
    batch (teacher forcing, as the benchmark's cell-by-cell check does)."""
    cells, params = _seeded()
    x, y = next(iter(_stream()))
    inputs, h = [], jnp.asarray(x)
    for fn, v in zip(cells, params):
        inputs.append(h)
        h = fn(plain.Scope(v["params"]), h)
    return cells, params, inputs, (x, y), h


def test_kinds_and_the_parameter_tree_are_the_programs(forced):
    cells, params, _, (x, _), logits = forced
    assert ref.kinds(MODEL) == KINDS
    assert ref.input_spec(MODEL, {"sequence_length": LENGTH}) == ((2 * LENGTH,), jnp.int32)
    assert logits.shape == (BATCH, LENGTH, MODEL["vocab_size"])  # the noisy rows alone
    from mpi4dl_tpu.parallel.partition import init_cells

    theirs = jax.eval_shape(
        lambda: init_cells(sdar(MODEL), jax.random.PRNGKey(0), jnp.asarray(x)))
    assert jax.tree.structure(theirs) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(theirs)] == [
        a.shape for a in jax.tree.leaves(params)]
    # a layer is two cells: the attention's parameters are a tree of their own
    assert set(params[1]["params"]) == {"input_layernorm", "self_attn"}
    assert set(params[2]["params"]) == {"post_attention_layernorm", "mlp"}
    mlp = params[2]["params"]["mlp"]
    assert mlp["gate"]["kernel"].shape == (32, 16) and set(mlp) == {"gate", "experts"}
    assert mlp["experts"]["w1"].shape == (4, 32, 16)


@pytest.mark.parametrize(
    "index", range(6), ids=["stem", "attn0", "experts0", "attn1", "experts1", "head"])
def test_each_float32_cell_and_its_vjp_agree_with_the_reference(forced, index):
    cells, params, inputs, _, _ = forced
    fn, cell, h = cells[index], sdar(MODEL)[index], inputs[index]
    y_shape = jax.eval_shape(
        lambda v, x_: fn(plain.Scope(v["params"]), x_), params[index], h)
    ct = check.seeded_cotangent(y_shape, 11, index)
    want = check.reference_cell_vjp(fn, "f32", params[index], h, ct)
    y, pull = plain.vjp(lambda v, x_: cell.apply(v, x_), params[index], h)
    got = (y,) + tuple(pull(ct))
    assert len(got) == len(want) == (2 if index == 0 else 3)
    for what, a, b in zip(("y", "dv", "dx"), got, want):
        assert check.relative_l2(a, b) < 1e-5, what


def _trainer(model, length, dtype=jnp.float32):
    """The cells under the entry points' remat rule and the model's loss."""
    cfg = ParallelConfig(
        batch_size=BATCH, split_size=1, spatial_size=0, image_size=0,
        sequence_length=length, num_classes=model["vocab_size"])
    return Trainer(sdar(model, dtype), 0, cfg, remat=default_remat(cfg.image_size),
                   loss=block_diffusion_loss)


def test_three_steps_through_trainer_follow_the_reference(forced):
    """``Trainer`` with the model's loss against ``Follower`` with the
    reference's on the same seeded weights and the stream's batches: losses,
    the parameters after (so the gradients), and the step's counters."""
    cells, params, _, _, _ = forced
    trainer = _trainer(MODEL, LENGTH)
    assert trainer.remat == "cell"
    state = TrainState(params=jax.tree.map(jnp.copy, params),
                       opt_state=trainer.tx.init(params),
                       step=jnp.zeros((), jnp.int32))
    follower = Follower(cells, jax.tree.map(jnp.copy, params), 0.001, 0.9, ref.loss)
    stream = iter(_stream(seed=5))
    for _ in range(3):
        x, y = next(stream)
        xs, ys = trainer.shard_batch(jnp.asarray(x), jnp.asarray(y))
        assert xs.shape == (BATCH, 2 * LENGTH) and ys.shape == (BATCH, LENGTH, 2)
        state, metrics = trainer.train_step(state, xs, ys)
        loss, _ = follower.step(x, y)
        assert float(metrics["loss"]) == pytest.approx(loss, rel=2e-5)
        assert 0.0 <= float(metrics["accuracy"]) <= 1.0
        # the new counter: the positions that carried loss are the masked ones
        assert int(metrics["loss_positions"]) == int(np.sum(x[:, :LENGTH] == 47))
    assert check.relative_l2(state.params, follower.params) < 1e-6
    assert check.relative_l2(
        jax.tree.map(jnp.subtract, state.params, params),
        jax.tree.map(jnp.subtract, follower.params, params)) < 1e-3
    # 2 expert layers over both copies' rows, 4 of 16 experts, 2 a token
    pairs = float(metrics["moe_pairs"])
    assert 0 < pairs <= 2 * BATCH * 2 * LENGTH * 2 and pairs == int(pairs)
    assert "moe_narrow_layers" in metrics and trainer.last_metrics is metrics


def test_the_loss_is_the_weighted_mean_written_out(forced):
    """``(1 / N) sum_n (1 / L) sum_{i masked} (1 / t_n) CE_i``, from the
    stream's own arrays; bare targets weigh 1 each."""
    _, _, _, (x, y), logits = forced
    weight = y[..., 1].view(np.float32)
    masked = x[:, :LENGTH] == 47
    assert np.array_equal(weight != 0, masked)
    logp = np.asarray(jax.nn.log_softmax(logits), np.float64)
    want = 0.0
    for n in range(BATCH):
        t = 1 / weight[n][masked[n]][0]
        want += sum(-logp[n, i, y[n, i, 0]] for i in np.flatnonzero(masked[n])) / t / LENGTH
    want /= BATCH
    assert float(ref.loss(logits, jnp.asarray(y))) == pytest.approx(want, rel=1e-5)
    mean = lambda total, labels: total / labels  # noqa: E731  one shard, no mesh
    got, _, counted = block_diffusion_loss(logits, jnp.asarray(y), mean)
    assert float(got) == pytest.approx(want, rel=1e-5)
    assert int(counted["loss_positions"]) == int(masked.sum())
    bare = jnp.asarray(y[..., 0])
    plain_ce = -np.mean(np.take_along_axis(logp, y[..., :1], axis=-1))
    assert float(ref.loss(logits, bare)) == pytest.approx(plain_ce, rel=1e-5)
    assert float(block_diffusion_loss(logits, bare, mean)[0]) == pytest.approx(plain_ce, rel=1e-5)


# -- the mask ------------------------------------------------------------------


def _dense_mask(length, block):
    """``[2 L, 2 L]`` from the four rules, one pair at a time."""
    mask = np.zeros((2 * length, 2 * length), bool)
    for r in range(2 * length):
        for s in range(2 * length):
            r_noisy, s_noisy = r < length, s < length
            br, bs = (r % length) // block, (s % length) // block
            if r_noisy and s_noisy:
                mask[r, s] = bs == br
            elif r_noisy and not s_noisy:
                mask[r, s] = bs < br
            elif not r_noisy and not s_noisy:
                mask[r, s] = bs <= br
            # a clean query never sees a noisy key
    return mask


def _qkv(length, kv=2, group=2, d=8, seed=0, dtype=jnp.float32, batch=BATCH):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (batch, 2 * length, kv, group, d), dtype)
    k = jax.random.normal(keys[1], (batch, 2 * length, kv, d), dtype)
    v = jax.random.normal(keys[2], (batch, 2 * length, kv, d), dtype)
    ct = jax.random.normal(keys[3], q.shape, dtype)
    return q, k, v, ct


def _dense_attention(q, k, v, mask):
    scores = jnp.einsum("bqkgd,bnkd->bkgqn", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bkgqn,bnkd->bqkgd", p, v)


@pytest.mark.parametrize("block", [1, 4, 24])
@pytest.mark.parametrize("rows", [5, 16, 48])
def test_the_mask_structure_is_the_dense_mask_of_the_four_rules(block, rows):
    """At ``B`` in {1, 4, L}, in blocks of query rows that do and do not
    divide a copy: the structure's own table, the reference's, and the
    blocked path's output and gradients against plain attention under the
    dense mask."""
    mask = sequence.BlockMask(LENGTH, block)
    dense = _dense_mask(LENGTH, block)
    at = jnp.arange(2 * LENGTH)
    assert np.array_equal(np.asarray(sequence._visible(at, at, mask)), dense)
    assert np.array_equal(np.asarray(
        ref.sees(at[:, None], at[None, :], LENGTH, block)), dense)
    assert dense.diagonal().all()  # every row sees itself: no softmax is empty
    # no block of queries is handed a key row none of its rows can see: nothing skipped wrongly
    for start in range(0, 2 * LENGTH, rows):
        end = min(start + rows, 2 * LENGTH)
        seen = np.zeros(2 * LENGTH, bool)
        for lo, hi in sequence._key_ranges(start, end, mask):
            seen[lo:hi] = True
        assert not dense[start:end][:, ~seen].any()
    q, k, v, ct = _qkv(LENGTH)
    want, pull = jax.vjp(lambda *a: _dense_attention(*a, dense), q, k, v)
    got, pull_got = jax.vjp(
        lambda *a: sequence.blocked_masked_attention(*a, rows, mask), q, k, v)
    assert check.relative_l2(got, want) < 1e-5
    for a, b in zip(pull_got(ct), pull(ct)):
        assert check.relative_l2(a, b) < 1e-5


def test_block_length_1_on_the_clean_copy_is_causal_attention():
    q, k, v, _ = _qkv(LENGTH)
    got = sequence.blocked_masked_attention(q, k, v, 16, sequence.BlockMask(LENGTH, 1))
    clean = slice(LENGTH, None)
    want = sequence.causal_attention(q[:, clean], k[:, clean], v[:, clean], 16)
    assert check.relative_l2(got[:, clean], want) < 1e-6
    # and the visible pairs are what the reference's FLOP count says
    for block in (1, 4, 24):
        assert _dense_mask(LENGTH, block).sum() == ref.visible_pairs(LENGTH, block)


def test_rows_r_and_r_plus_L_share_a_position():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2 * LENGTH, 2, 8))
    positions = jnp.arange(2 * LENGTH) % LENGTH
    turned = sequence.rope(x, 1e6, positions=positions)
    again = sequence.rope(x[:, LENGTH:], 1e6)  # the row's index, as before
    assert np.allclose(turned[:, LENGTH:], again, atol=1e-6)
    assert np.allclose(
        turned[:, :LENGTH], sequence.rope(x[:, :LENGTH], 1e6), atol=1e-6)
    assert np.allclose(turned, ref.rope(x, 1e6, positions), atol=1e-6)


def test_a_noisy_token_moves_only_its_own_blocks_noisy_rows(forced):
    """Through the whole model: position 9's noisy token (block 2) is seen
    by the noisy rows of block 2 and nothing else; its clean token by the
    clean rows from block 2 on and the noisy rows from block 3 on."""
    cells, params, _, (x, _), _ = forced

    def hidden(ids):  # the last layer's output, both copies' rows
        h = jnp.asarray(ids)
        for fn, v in zip(cells[:-1], params):
            h = fn(plain.Scope(v["params"]), h)
        return np.asarray(h[0])

    base = hidden(x)
    noisy, clean = x.copy(), x.copy()
    noisy[0, 9] = (x[0, 9] + 1) % 47
    clean[0, LENGTH + 9] = (x[0, LENGTH + 9] + 1) % 47
    moved = np.abs(hidden(noisy) - base).max(axis=-1) > 1e-6
    assert set(np.flatnonzero(moved)) == {8, 9, 10, 11}
    moved = np.abs(hidden(clean) - base).max(axis=-1) > 1e-6
    assert set(np.flatnonzero(moved)) == set(range(12, LENGTH)) | set(
        range(LENGTH + 8, 2 * LENGTH))


@pytest.mark.parametrize("fault", ["causal", "leak", "block8", "unnormalised", "next_share"])
def test_a_planted_fault_shows_in_its_cells_parameter_cotangents(forced, fault):
    """``chipbench/tools/blockdiff_probe.py``'s planted faults, as the chip
    reads them: the program's cell with the fault against the reference's
    numbers. A cell's output is its input plus a small term, so a fault
    shows least there; the parameters' cotangents are the mixer's alone and
    show it whole."""
    import types

    from chipbench.tools import blockdiff_probe

    cells, params, inputs, _, _ = forced
    index = 1 if blockdiff_probe.fault_kind(fault) == "attn_blockdiff" else 2
    assert ref.kinds(MODEL)[index] == blockdiff_probe.fault_kind(fault)
    trainer = types.SimpleNamespace(cells=list(sdar(MODEL)))
    fn, h = cells[index], inputs[index]
    ct = check.seeded_cotangent(jax.eval_shape(
        lambda v, x_: fn(plain.Scope(v["params"]), x_), params[index], h), 11, index)
    want = check.reference_cell_vjp(fn, "f32", params[index], h, ct)

    def errors():
        y, pull = plain.vjp(lambda v, x_: trainer.cells[index].apply(v, x_), params[index], h)
        return [check.relative_l2(a, b) for a, b in zip((y,) + tuple(pull(ct)), want)]

    kept = (sequence.block_diffusion_attention, sequence._visible, sequence._key_ranges,
            trainer.cells[index])
    with blockdiff_probe.planted(fault, trainer, index):
        y_err, dv_err, dx_err = errors()
    assert kept == (sequence.block_diffusion_attention, sequence._visible,
                    sequence._key_ranges, trainer.cells[index])
    assert dv_err > 0.2 and dv_err > 5 * y_err
    assert max(errors()) < 1e-5  # and the sound cell again


def test_the_probe_names_the_leaf_that_norm_gaps_calls_worst(forced):
    from chipbench.tools import blockdiff_probe

    _, params, _, _, _ = forced
    names = blockdiff_probe.leaf_names(params)
    assert len(names) == len(jax.tree.leaves(params)) == len(set(names))
    assert "cell01/self_attn/q_proj/kernel" in names and "cell02/mlp/experts/w2" in names
    reference = [float(v) for v in check.leaf_norms(params)]
    program = list(reference)
    at = names.index("cell04/mlp/gate/kernel")
    program[at] *= 1.25
    worst = blockdiff_probe.worst_leaves(names, program, reference)
    assert worst[0]["leaf"] == "cell04/mlp/gate/kernel"
    assert worst[0]["gap"] == pytest.approx(check.norm_gaps(program, reference)[0])


def test_the_probe_counts_the_rows_that_pick_with_the_mask_token(forced):
    """Rows that enter an expert cell alike pick one set of experts; the
    report says how many sets the masked rows pick and which experts the
    two commonest differ in."""
    from chipbench.tools import blockdiff_probe

    cells, params, inputs, (x, _), _ = forced
    masked = np.concatenate([x[0, :LENGTH] == 47, np.zeros(LENGTH, bool)])
    assert masked.sum() > 1
    h = jnp.asarray(inputs[2])
    alike = h.at[:, masked].set(h[0, np.flatnonzero(masked)[0]])
    report = blockdiff_probe.routing_report(ref, MODEL, params[2], alike, masked)
    assert report["masked_rows"] == masked.sum()
    assert report["masked_distinct_sets"] == 1 and report["masked_commonest_share"] == 1.0
    assert 0 <= report["commonest_held"] <= MODEL["num_experts_per_tok"]
    report = blockdiff_probe.routing_report(ref, MODEL, params[2], h, masked)
    assert report["masked_distinct_sets"] >= 1
    assert set(report["rows_moved_by_bf16_input"]) == {"masked", "others"}


# -- the kernels, in the interpreter ---------------------------------------------


@pytest.mark.parametrize("heads", [1, 2])
@pytest.mark.parametrize("d, unit", [(64, 4), (128, 4), (128, 128)])
def test_the_kernels_under_the_mask_are_the_plain_path(d, unit, heads):
    """Forward and backward in the Pallas interpreter, float32, two and
    three blocks of 128 rows a copy, against ``blocked_masked_attention``
    (itself held to the dense mask above)."""
    length = 256 if d == 64 else 384
    mask = sequence.BlockMask(length, unit)
    q, k, v, ct = _qkv(length, kv=1, group=2, d=d, batch=1)
    plan = attention_pallas.Plan(128, heads)
    got, pull_got = jax.vjp(
        lambda *a: attention_pallas.attention(*a, plan, True, mask), q, k, v)
    want, pull = jax.vjp(
        lambda *a: sequence.blocked_masked_attention(*a, 128, mask), q, k, v)
    assert check.relative_l2(got, want) < 1e-5
    for what, a, b in zip(("dq", "dk", "dv"), pull_got(ct), pull(ct)):
        assert check.relative_l2(a, b) < 1e-5, what


def test_the_plan_under_the_mask_comes_from_the_shape():
    bf16 = jnp.bfloat16
    cell = ((1, 16384, 4, 8, 128), (1, 16384, 4, 128))  # the SDAR cell's
    mask = sequence.BlockMask(8192, 4)
    assert attention_pallas.plan_for(*cell, bf16, mask) == attention_pallas.Plan(512, 1)
    assert attention_pallas.plan_for(*cell, bf16) == attention_pallas.Plan(512, 1)
    # a copy that is not whole kernel blocks, rows that are not two copies,
    # diffusion blocks that do not divide a kernel block, float32: no plan
    assert attention_pallas.plan_for(
        (1, 400, 4, 8, 128), (1, 400, 4, 128), bf16, sequence.BlockMask(200, 4)) is None
    assert attention_pallas.plan_for(*cell, bf16, sequence.BlockMask(4096, 4)) is None
    assert attention_pallas.plan_for(*cell, bf16, sequence.BlockMask(8192, 3)) is None
    assert attention_pallas.plan_for(*cell, jnp.float32, mask) is None
    assert attention_pallas.plan_for(
        (1, 512, 4, 8, 128), (1, 512, 4, 128), bf16, sequence.BlockMask(256, 4)
    ) == attention_pallas.Plan(256, 2)


# -- the stream -------------------------------------------------------------------


def test_the_stream_is_seeded_by_seed_and_batch_index():
    a, b = list(zip(range(3), _stream(seed=3))), list(zip(range(3), _stream(seed=3)))
    for (_, (xa, ya)), (_, (xb, yb)) in zip(a, b):
        assert np.array_equal(xa, xb) and np.array_equal(ya, yb)
    other = next(iter(_stream(seed=4)))
    assert not np.array_equal(a[0][1][0], other[0])
    assert not np.array_equal(a[0][1][0], a[1][1][0])  # every batch new
    x, y = a[0][1]
    assert x.dtype == y.dtype == np.int32
    assert x.shape == (BATCH, 2 * LENGTH) and y.shape == (BATCH, LENGTH, 2)
    noisy, clean = x[:, :LENGTH], x[:, LENGTH:]
    assert clean.max() < 47 and np.array_equal(clean, y[..., 0])  # no shift, no mask id as data
    masked = noisy == 47
    assert np.array_equal(noisy[~masked], clean[~masked])
    weight = y[..., 1].view(np.float32)
    for n in range(BATCH):  # one noise level a sequence, weight 1 / t on its masked positions
        levels = np.unique(weight[n][masked[n]])
        assert len(levels) == 1 and 1.0 <= levels[0] <= 1e3
        assert not weight[n][~masked[n]].any()


def test_the_masked_share_follows_the_noise_level():
    stream = BlockDiffusionTokens(64, 512, 1000, seed=11, prefetch=False)
    x, y = next(iter(stream))
    weight = y[..., 1].view(np.float32)
    share = (x[:, :512] == 999).mean(axis=1)
    t = 1 / weight.max(axis=1)
    assert np.all(np.abs(share - t) < 4 * np.sqrt(t * (1 - t) / 512) + 1 / 512)
    assert 0.3 < share.mean() < 0.7  # t uniform: half the positions on average
    assert t.min() >= 1e-3 and t.max() <= 1.0 and t.max() > 0.9  # all of [t_min, 1]
    # a row that draws no masked position is given one
    x, _ = next(iter(BlockDiffusionTokens(256, 4, 10, t_min=1e-3, seed=1, prefetch=False)))
    assert (x[:, :4] == 9).any(axis=1).all()


# -- the share --------------------------------------------------------------------


def test_the_eight_shares_add_up_to_the_uncut_layer():
    whole = {**MODEL, "num_experts": 128, "num_experts_per_tok": 8}
    del whole["cut"]
    s = ref.sizes(whole)
    spec: dict = {}
    x_shape = jax.ShapeDtypeStruct((BATCH, 2 * LENGTH, s.hidden), jnp.float32)
    jax.eval_shape(lambda x: ref.expert_ffn(plain.Scope(spec=spec), x, s), x_shape)
    params = plain.make_params([spec], 1)[0]["params"]
    x = jax.random.normal(jax.random.PRNGKey(2), x_shape.shape)
    want = ref.expert_ffn(plain.Scope(params), x, s)
    from_reference, from_program = 0.0, 0.0
    for first in range(0, 128, 16):
        model = dict(whole, num_experts=16,
                     cut={"num_experts": {"published": 128, "held": 16, "first": first}})
        held = dict(params, experts={
            name: w[first:first + 16] for name, w in params["experts"].items()})
        share = ref.sizes(model)
        from_reference += ref.expert_ffn(plain.Scope(held), x, share)
        layer = sequence.ExpertFFN(
            share.hidden, share.expert_width, share.experts, share.held, share.first,
            share.per_token, share.norm_topk, expert_bias=False, dtype=jnp.float32,
            scoring="softmax")
        part = layer.apply({"params": held}, x)
        from_program += part
    assert check.relative_l2(from_reference, want) < 1e-6
    assert check.relative_l2(from_program, want) < 1e-6
    assert check.relative_l2(part, want) > 0.3  # a share alone is a part


# -- the trainer's loss, the entry script -----------------------------------------


def test_a_trainer_without_a_models_loss_keeps_the_position_cross_entropy():
    from mpi4dl_tpu.train import position_cross_entropy

    cfg = ParallelConfig(batch_size=BATCH, split_size=1, spatial_size=0, image_size=0,
                         sequence_length=LENGTH, num_classes=48)
    assert Trainer(sdar(MODEL), 0, cfg, remat="cell").loss is position_cross_entropy
    assert _trainer(MODEL, LENGTH).loss is block_diffusion_loss


def test_the_pipeline_trainers_refuse_a_models_loss():
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import common

    args = common.token_model_args(
        ["--sequence-length", "24", "--split-size", "2"], model=MODEL)
    cfg = common.build_config(args, spatial=False)
    with pytest.raises(ValueError, match="single-program"):
        common.make_trainer(args, cfg, sdar(MODEL), sdar(MODEL), loss=block_diffusion_loss)


def test_the_entry_script_trains_the_tiny_cut(monkeypatch, capsys):
    script = os.path.join(REPO, "benchmarks", "layer_parallelism", "benchmark_sdar_lp.py")
    monkeypatch.setattr(sys, "argv", [
        script, "--model-config", TINY_JSON, "--sequence-length", "32",
        "--batch-size", "2", "--max-steps", "3", "-v"])
    runpy.run_path(script, run_name="__main__")
    out = capsys.readouterr().out
    assert "remat policy: cell (@32 tokens)" in out
    losses = [float(line.split("loss ")[1].split()[0])
              for line in out.splitlines() if " loss " in line]
    assert len(losses) == 3 and all(np.isfinite(losses))
    assert "benchmark_sdar_lp: Mean" in out and "seq/s" in out
    with open(TINY_JSON) as f:
        assert json.load(f)["block_length"] == 4
