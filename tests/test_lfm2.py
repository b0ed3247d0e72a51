"""LFM2-MoE on the training path, at tiny widths on the CPU mesh.

The program (``mpi4dl_tpu/models/lfm2.py``, ``ops/sequence.py``, ``Trainer``'s
token family, ``data.SyntheticTokens``, the entry script) against the
benchmark's plain float32 reference (``chipbench/reference/lfm2_moe.py``,
which imports nothing of the program) on seeded weights; the 8-of-32 cut
tied to the whole layer; no token dropped; causality; and the benchmark's
own run on the tiny cell. (Its traced step, with the image models', is
pinned in ``tests/test_jaxpr_pins.py``.)
"""

import json
import os
import re
import runpy
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.harness import check
from chipbench.reference import lfm2_moe as ref
from chipbench.reference import plain
from chipbench.reference.step import Follower
from mpi4dl_tpu.config import ParallelConfig
from mpi4dl_tpu.data import SyntheticTokens
from mpi4dl_tpu.models.lfm2 import lfm2
from mpi4dl_tpu.ops import sequence
from mpi4dl_tpu.train import Trainer, TrainState, default_remat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_JSON = os.path.join(REPO, "benchmarks", "layer_parallelism", "lfm2_tiny.json")

# LFM2-8B-A1B's held pattern (dense conv, then attention / conv expert
# layers) at toy widths; this "chip" holds experts 2-3 of 8.
MODEL = {
    "hidden_size": 32, "intermediate_size": 64, "moe_intermediate_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "conv_L_cache": 3,
    "conv_bias": False, "norm_eps": 1e-5, "rope_theta": 1000000,
    "layer_types": ["conv", "full_attention", "conv", "conv"],
    "num_hidden_layers": 4, "num_dense_layers": 1,
    "num_experts": 2, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 48,
    "cut": {"num_experts": {"published": 8, "held": 2, "first": 2}},
}
BATCH, LENGTH = 2, 24


def _seeded(model=MODEL, batch=BATCH, length=LENGTH, seed=3000000019):
    cells = ref.cells(model)
    specs = plain.record_specs(cells, (batch, length), jnp.int32)
    return cells, plain.make_params(specs, seed)


def _ids(batch=BATCH, length=LENGTH, vocab=MODEL["vocab_size"], seed=7):
    return next(iter(SyntheticTokens(batch, length, vocab, seed=seed, prefetch=False)))


@pytest.fixture(scope="module")
def forced():
    """The reference's cells, seeded weights, and each cell's input on one
    batch (teacher forcing, as the benchmark's cell-by-cell check does)."""
    cells, params = _seeded()
    x, y = _ids()
    inputs, h = [], jnp.asarray(x)
    for fn, v in zip(cells, params):
        inputs.append(h)
        h = fn(plain.Scope(v["params"]), h)
    return cells, params, inputs, (x, y)


def test_kinds_and_the_parameter_tree_are_the_programs(forced):
    cells, params, _, (x, _) = forced
    assert ref.kinds(MODEL) == [
        "stem", "dense_conv", "moe_attention", "moe_conv", "moe_conv", "head"]
    from mpi4dl_tpu.parallel.partition import init_cells

    theirs = jax.eval_shape(
        lambda: init_cells(lfm2(MODEL), jax.random.PRNGKey(0), jnp.asarray(x)))
    assert jax.tree.structure(theirs) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(theirs)] == [
        a.shape for a in jax.tree.leaves(params)]
    # the router is as wide as the published model, the experts are the share
    ffn = params[2]["params"]["feed_forward"]
    assert ffn["gate"]["kernel"].shape == (32, 8) and ffn["expert_bias"].shape == (8,)
    assert ffn["experts"]["w1"].shape == (2, 32, 16)
    assert ffn["experts"]["w2"].shape == (2, 16, 32)


@pytest.mark.parametrize("index", range(6), ids=ref.kinds(MODEL))
def test_each_float32_cell_and_its_vjp_agree_with_the_reference(forced, index):
    cells, params, inputs, _ = forced
    fn, cell, h = cells[index], lfm2(MODEL)[index], inputs[index]
    y_shape = jax.eval_shape(
        lambda v, x_: fn(plain.Scope(v["params"]), x_), params[index], h)
    ct = check.seeded_cotangent(y_shape, 11, index)
    want = check.reference_cell_vjp(fn, "f32", params[index], h, ct)
    y, pull = plain.vjp(lambda v, x_: cell.apply(v, x_), params[index], h)
    got = (y,) + tuple(pull(ct))
    # token ids have no cotangent; every other cell's input has
    assert len(got) == len(want) == (2 if index == 0 else 3)
    for what, a, b in zip(("y", "dv", "dx"), got, want):
        assert check.relative_l2(a, b) < 1e-5, what
    if index in (2, 3, 4):  # only the choice of experts reads the bias
        bias_grad = got[1]["params"]["feed_forward"]["expert_bias"]
        assert not np.any(np.asarray(bias_grad))


def _trainer(model, length):
    """Float32 cells under the entry points' remat rule."""
    cfg = ParallelConfig(
        batch_size=BATCH, split_size=1, spatial_size=0, image_size=0,
        sequence_length=length, num_classes=model["vocab_size"])
    return Trainer(lfm2(model), 0, cfg, remat=default_remat(cfg.image_size))


def test_a_model_without_an_image_takes_cell_remat_and_token_specs():
    assert default_remat(0) == "cell" and default_remat(1024) is False
    trainer = _trainer(MODEL, LENGTH)
    assert trainer.remat == "cell"
    assert tuple(trainer.x_spec) == tuple(trainer.y_spec) == ("data", None)
    assert trainer.config.input_spec(BATCH) == ((BATCH, LENGTH), jnp.int32)
    with pytest.raises(ValueError, match="no image"):
        ParallelConfig(batch_size=1, split_size=1, image_size=32, sequence_length=8)


def test_three_steps_through_trainer_follow_the_reference(forced):
    """``Trainer`` (float32 cells, "cell" remat, its own loss over positions)
    against ``Follower`` on the same seeded weights and batches: losses,
    accuracy as a mean over positions, the parameters after, and the step's
    counters."""
    cells, params, _, _ = forced
    trainer = _trainer(MODEL, LENGTH)
    state = TrainState(params=jax.tree.map(jnp.copy, params),
                       opt_state=trainer.tx.init(params),
                       step=jnp.zeros((), jnp.int32))
    # both donate their state: each gets a copy of the fixture's weights
    follower = Follower(cells, jax.tree.map(jnp.copy, params), 0.001, 0.9, ref.loss)
    stream = iter(SyntheticTokens(BATCH, LENGTH, MODEL["vocab_size"], seed=5,
                                  prefetch=False))
    for _ in range(3):
        x, y = next(stream)
        xs, ys = trainer.shard_batch(jnp.asarray(x), jnp.asarray(y))
        assert xs.shape == ys.shape == (BATCH, LENGTH) and xs.dtype == jnp.int32
        state, metrics = trainer.train_step(state, xs, ys)
        loss, _ = follower.step(x, y)
        assert float(metrics["loss"]) == pytest.approx(loss, rel=2e-5)
        assert 0.0 <= float(metrics["accuracy"]) <= 1.0
    assert check.relative_l2(state.params, follower.params) < 1e-6
    assert check.relative_l2(
        jax.tree.map(jnp.subtract, state.params, params),
        jax.tree.map(jnp.subtract, follower.params, params)) < 1e-3
    # 3 expert layers, 2 of 8 experts held, 2 experts a token: about
    # 3 x 48 x 2 x 2/8 pairs, and never more than every token's every pick
    assert trainer.last_metrics is metrics
    pairs = float(metrics["moe_pairs"])
    assert 0 < pairs <= 3 * BATCH * LENGTH * 2 and pairs == int(pairs)
    assert 0.5 <= float(metrics["moe_max_share"]) <= 1.0


def _whole_and_shares(seed=1):
    """An uncut expert layer's parameters (8 experts) and, for each of 4
    shares of 2 experts, the cut model and the same parameters' share."""
    whole = dict(MODEL, num_experts=8)
    del whole["cut"]
    s = ref.sizes(whole)
    spec: dict = {}
    x_shape = jax.ShapeDtypeStruct((BATCH, LENGTH, s.hidden), jnp.float32)
    jax.eval_shape(lambda x: ref.expert_ffn(plain.Scope(spec=spec), x, s), x_shape)
    params = plain.make_params([spec], seed)[0]["params"]
    # an expert_bias that moves the choice, as a trained model's does
    params["expert_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(seed), (8,))
    shares = []
    for first in range(0, 8, 2):
        model = dict(MODEL, cut={"num_experts": {"published": 8, "held": 2, "first": first}})
        held = dict(params, experts={k: w[first:first + 2]
                                     for k, w in params["experts"].items()})
        shares.append((model, held))
    return s, params, shares


def _program_ffn(model, dtype=jnp.float32):
    s = ref.sizes(model)
    return sequence.ExpertFFN(
        s.hidden, s.expert_width, s.experts, s.held, s.first, s.per_token,
        s.norm_topk, s.scaling, s.expert_bias, dtype)


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    s, params, shares = _whole_and_shares()
    x = jax.random.normal(jax.random.PRNGKey(2), (BATCH, LENGTH, s.hidden))
    whole = ref.expert_ffn(plain.Scope(params), x, s)
    from_reference = sum(
        ref.expert_ffn(plain.Scope(held), x, ref.sizes(model)) for model, held in shares)
    from_program = sum(
        _program_ffn(model).apply({"params": held}, x) for model, held in shares)
    assert check.relative_l2(from_reference, whole) < 1e-6
    assert check.relative_l2(from_program, whole) < 1e-6
    # and a share alone is a part, not the whole
    assert check.relative_l2(
        _program_ffn(shares[0][0]).apply({"params": shares[0][1]}, x), whole) > 0.3


@pytest.mark.parametrize("favoured,pairs_per_token", [((2, 7), 1), ((2, 3), 2), ((6, 7), 0)],
                         ids=["one_held_expert", "every_pick_held", "none_held"])
def test_no_token_expert_pair_on_a_held_expert_is_dropped(favoured, pairs_per_token):
    """A bias that sends every token to the same two experts: all to one
    held expert (all in one group, and exactly the prefix of the sorted rows
    that a holder of 2 of 8 experts always computes), both picks held (every
    row used, so the rows past the prefix run: the worst case the layer is
    sized for), or none held (an all-zero part)."""
    s, params, shares = _whole_and_shares()
    model, held = shares[1]  # experts 2 and 3
    bias = jnp.zeros((8,)).at[jnp.asarray(favoured)].set(10.0)
    held = dict(held, expert_bias=bias)
    x = jax.random.normal(jax.random.PRNGKey(3), (BATCH, LENGTH, s.hidden))
    want = ref.expert_ffn(plain.Scope(held), x, ref.sizes(model))
    got, sown = _program_ffn(model).apply({"params": held}, x, mutable=[sequence.COUNTERS])
    counted = np.asarray(sown[sequence.COUNTERS]["expert_pairs"][0])
    tokens = BATCH * LENGTH
    assert counted.sum() == pairs_per_token * tokens
    assert list(counted) == [tokens * (2 + e in favoured) for e in range(2)]
    # 96 sorted pair rows, the prefix 2 * 96 * 2 / 8 = 48 of them
    assert sequence._prefix_rows(2 * tokens, 2, 8) == tokens
    assert int(sown[sequence.COUNTERS]["prefix_alone"][0]) == (pairs_per_token < 2)
    if pairs_per_token:
        assert check.relative_l2(got, want) < 1e-6
        assert float(jnp.min(jnp.linalg.norm(got, axis=-1))) > 0  # every token served
    else:
        assert not np.any(np.asarray(got)) and not np.any(np.asarray(want))
    # the gradient passes the rows that were not computed by
    grads = jax.grad(lambda v: jnp.sum(_program_ffn(model).apply({"params": v}, x) ** 2))(held)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in jax.tree.leaves(grads))


def _bias_for_held_pairs(held_pairs, params, x):
    """An ``expert_bias`` under which experts 2 and 3 (the share's) compute
    exactly ``held_pairs`` of the 96 token-expert pairs of ``x``."""
    tokens = BATCH * LENGTH
    if held_pairs is None:  # no bias: the router alone, near even, about 24
        return jnp.zeros((8,))
    bias = jnp.zeros((8,)).at[2].set(10.0).at[7].set(5.0)  # every token: 2, then 7
    extra = held_pairs - tokens  # tokens whose second pick is expert 3
    if extra == tokens:
        return bias.at[3].set(7.0)
    if extra:
        scores = jax.nn.sigmoid(jnp.matmul(
            x.reshape(tokens, -1), params["gate"]["kernel"], precision="highest"))
        lead = np.sort(np.asarray(scores[:, 3] - scores[:, 7]))[::-1]
        bias = bias.at[3].set(5.0 - (lead[extra - 1] + lead[extra]) / 2)
    return bias


@pytest.mark.parametrize("held_pairs", [None, 48, 49, 96],
                         ids=["below_prefix", "exactly_prefix", "one_past_prefix", "every_pair"])
def test_the_two_row_ranges_are_the_one_range_layer(held_pairs, monkeypatch):
    """A share of 2 of 8 experts, 2 a token: 96 sorted pair rows of which the
    first 48 always run and the other 48 only when held pairs lie there.
    Value against the float32 reference; value and every gradient (input,
    router, the three expert arrays) against the same layer made to compute
    all 96 rows as one range."""
    s, params, shares = _whole_and_shares()
    model, held = shares[1]  # experts 2 and 3
    x = jax.random.normal(jax.random.PRNGKey(3), (BATCH, LENGTH, s.hidden))
    held = dict(held, expert_bias=_bias_for_held_pairs(held_pairs, held, x))
    ffn = _program_ffn(model)
    ct = jax.random.normal(jax.random.PRNGKey(4), x.shape)

    def loss(v, x_):
        y, sown = ffn.apply({"params": v}, x_, mutable=[sequence.COUNTERS])
        return jnp.sum(y * ct), (y, sown[sequence.COUNTERS])

    def value_and_grads():
        (_, (y, counted)), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(held, x)
        return y, counted, grads

    y, counted, (dv, dx) = value_and_grads()
    n = int(counted["expert_pairs"][0].sum())
    assert n == (held_pairs or n) and (held_pairs or 10 < n < 40)
    assert int(counted["prefix_alone"][0]) == (n <= 48)
    assert check.relative_l2(y, ref.expert_ffn(plain.Scope(held), x, ref.sizes(model))) < 1e-6
    monkeypatch.setattr(sequence, "_prefix_rows", lambda pairs, held, experts: pairs)
    y1, counted1, (dv1, dx1) = value_and_grads()
    assert int(counted1["prefix_alone"][0]) == 1  # one range: nothing past it
    assert check.relative_l2(y, y1) < 1e-6 and check.relative_l2(dx, dx1) < 1e-6
    for got, want in ((dv["gate"]["kernel"], dv1["gate"]["kernel"]),
                      *((dv["experts"][w], dv1["experts"][w]) for w in ("w1", "w3", "w2"))):
        assert np.any(np.asarray(want)) and check.relative_l2(got, want) < 1e-6


def test_the_step_counts_the_expert_layers_that_stayed_on_the_prefix(forced):
    """``moe_narrow_layers`` through ``Trainer``: the tiny model's three
    expert layers hold 2 of 8 experts (a prefix of half the rows); seeded
    weights route evenly and all three stay on it, a bias that crowds the
    held experts in two of them leaves one."""
    _, params, _, (x, y) = forced
    trainer = _trainer(MODEL, LENGTH)

    def narrow_layers(params):
        params = jax.tree.map(jnp.copy, params)  # the step donates its state
        state = TrainState(params=params, opt_state=trainer.tx.init(params),
                           step=jnp.zeros((), jnp.int32))
        _, metrics = trainer.train_step(state, *trainer.shard_batch(jnp.asarray(x), jnp.asarray(y)))
        return float(metrics["moe_narrow_layers"]), float(metrics["moe_pairs"])

    even, pairs = narrow_layers(params)
    assert even == 3.0 and pairs < 3 * BATCH * LENGTH
    crowded = jax.tree.map(jnp.copy, params)
    for index in (3, 4):  # the two conv expert layers; the attention one stays even
        ffn = crowded[index]["params"]["feed_forward"]
        ffn["expert_bias"] = ffn["expert_bias"].at[jnp.asarray([2, 3])].set(10.0)
    fewer, pairs = narrow_layers(crowded)
    assert fewer == 1.0 and pairs > 2 * 2 * BATCH * LENGTH


def _plain_attention(q, k, v):
    """One softmax over the whole square, no blocks."""
    scores = jnp.einsum("bqkgd,bnkd->bkgqn", q, k) * q.shape[-1] ** -0.5
    mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
    p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bkgqn,bnkd->bqkgd", p, v)


def test_blocked_attention_is_plain_attention_beyond_one_block():
    length, block = 40, 16  # two whole blocks and a part of one
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (2, length, 2, 2, 8))
    k = jax.random.normal(keys[1], (2, length, 2, 8))
    v = jax.random.normal(keys[2], (2, length, 2, 8))
    got = sequence.causal_attention(q, k, v, block)
    assert check.relative_l2(got, _plain_attention(q, k, v)) < 1e-6
    grads = jax.grad(lambda *a: jnp.sum(sequence.causal_attention(*a, block) ** 2),
                     argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_plain_attention(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    assert check.relative_l2(grads, want) < 1e-5


@pytest.mark.parametrize("operator", ["conv", "full_attention"])
def test_position_t_does_not_see_t_plus_1(operator):
    """Perturb one position of a layer's input: no earlier position's output
    moves, that position's and later ones' do (program and reference)."""
    model = dict(MODEL, layer_types=[operator], num_hidden_layers=1)
    cells, params = _seeded(model, length=40)
    layer, v = lfm2(model)[1], params[1]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 40, 32))
    moved = x.at[0, 25].add(1.0)
    for apply in (lambda a: layer.apply(v, a),
                  lambda a: cells[1](plain.Scope(v["params"]), a)):
        delta = np.abs(np.asarray(apply(moved) - apply(x))).max(axis=-1)[0]
        assert np.all(delta[:25] == 0.0) and np.all(delta[25:28] > 0)
    if operator == "conv":  # three taps: t sees t-2..t and no further back
        conv = sequence.causal_depthwise_conv1d
        kernel = jnp.ones((3, 32))
        delta = np.abs(np.asarray(conv(moved, kernel) - conv(x, kernel))).max(axis=-1)[0]
        assert np.flatnonzero(delta).tolist() == [25, 26, 27]


def test_attention_blocks_in_the_layer_do_not_leak_across_a_block_edge():
    """The layer's own attention at a block of 16 over 40 positions."""
    attn = sequence.Attention(32, 4, 2, 1e-5, 1e6, block=16, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 40, 32))
    v = attn.init(jax.random.PRNGKey(6), x)
    whole = sequence.Attention(32, 4, 2, 1e-5, 1e6, block=64, dtype=jnp.float32)
    assert check.relative_l2(attn.apply(v, x), whole.apply(v, x)) < 1e-6
    moved = x.at[0, 16].add(1.0)  # the first row of the second block
    delta = np.abs(np.asarray(attn.apply(v, moved) - attn.apply(v, x))).max(axis=-1)[0]
    assert np.all(delta[:16] == 0.0) and np.all(delta[16:] > 0)


def test_synthetic_tokens_are_seeded_and_labels_are_the_next_token():
    a = list(zip(range(3), SyntheticTokens(2, 16, 48, seed=2147483659 + 5)))
    b = list(zip(range(3), SyntheticTokens(2, 16, 48, seed=2147483659 + 5, prefetch=False)))
    for (_, (xa, ya)), (_, (xb, yb)) in zip(a, b):
        assert xa.dtype == ya.dtype == np.int32 and xa.shape == ya.shape == (2, 16)
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)
        np.testing.assert_array_equal(xa[:, 1:], ya[:, :-1])  # the next token
        assert 0 <= xa.min() and max(xa.max(), ya.max()) < 48
    assert not np.array_equal(a[0][1][0], a[1][1][0])  # every batch new
    other = next(iter(SyntheticTokens(2, 16, 48, seed=1, prefetch=False)))
    assert not np.array_equal(other[0], a[0][1][0])


def _tiny_cell(tmp_path, limits=None):
    from chipbench.tests import tiny

    return tiny, tiny.tiny_cell(tmp_path, "lfm2_8b_a1b_share4", limits=limits)


# Tiny-size readings on the CPU (seeds 5, 6, 97, 98, 2147483659, 3000000019;
# PR 31): the bf16 program's largest beside the fp8 control's smallest.
LIMITS = {
    "cell_y_err": 0.03,       # 0.0143; control 0.0600
    "cell_dv_err": 0.08,      # 0.0404; control 0.1236
    "cell_dx_err": 0.05,      # 0.0275; control 0.1012
    "loss_gap_step1": 0.01,   # 0.0007; held against garbage
    "change_norm_gap": 0.5,   # 0.0017; a state left unchanged reads 1.0
}


def test_the_benchmarks_run_passes_the_program_and_fails_the_fp8_control(tmp_path, capsys):
    from chipbench import run
    from chipbench.harness import spec
    from chipbench.harness.session import Session

    tiny, cell = _tiny_cell(tmp_path, LIMITS)
    result = run.run(tiny.options(cell.name, seed=2147483659 + 11), jax.devices(),
                     cell=cell, peaks=tiny.PEAKS)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"images_per_s", "step_ms_p90", "setup_s"}
    assert {k: v["limit"] for k, v in result["compared"].items() if v["limit"]} == LIMITS
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    build = next(l for l in lines if l.get("phase") == "build")
    assert build["remat"] == "cell" and build["cells"] == 5 and build["spatial_cells"] == 0
    kinds = next(l for l in lines if l.get("phase") == "reference")["tap_kinds"]
    assert kinds == ["stem", "dense_conv", "moe_attention", "moe_conv", "head"]

    session = Session(cell)
    first = session.first_steps(2147483659 + 11, session.check_steps)
    context = {"trainer": session.trainer, "session": session, "cell": cell,
               "reduced": None}
    per_token = spec.metric_reader("layer_metrics", "moe_pairs_per_token")(context)
    # 2 expert layers; 2 experts a token of which 4 of 8 are held: 1.0 expected
    assert 0.7 < per_token < 1.3
    # the tiny cut holds 4 of 8 experts: the prefix is every row, so both of
    # its expert layers count as narrow
    narrow = spec.metric_reader("layer_metrics", "moe_narrow_layers")
    assert narrow(context) == 2.0
    assert narrow(dict(context, trainer=object())) is None  # a program that does not count it
    readers = {name: spec.metric_reader("layer_metrics", name)
               for name in ("moe_ms", "attn_ms", "shortconv_ms")}
    assert all(read(context) is None for read in readers.values())  # not traced
    # a trace names ops by their HLO instruction; the compiled step's text
    # gives each its name stack, where the program's scopes are. One event of
    # 1 ms for every instruction of the step, two steps in the window:
    from chipbench.harness import scopes, xtrace

    op_names = scopes.step_op_names(context)
    events = [xtrace.Event(f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p)", i * 1e6, 1e6, {})
              for i, name in enumerate(op_names)]
    events.append(xtrace.Event("%ragged-dot-none.3 = f32[8]{0} custom-call()", -1e6, 1e6, {}))
    context["reduced"] = xtrace.Reduced(
        steps=2, window_s=len(events) / 1e3, busy_s=len(events) / 1e3,
        chips=[{"window": (-1e6, len(op_names) * 1e6), "ops": events}],
        device_ops=[], idle_gaps=[])
    for name, scope in (("moe_ms", "lfm2_moe"), ("attn_ms", "lfm2_attention"),
                        ("shortconv_ms", "lfm2_shortconv")):
        carried = sum(scope in stack for stack in op_names.values())
        assert carried > 10, scope  # forward, recomputed forward and backward
        assert readers[name](context) == pytest.approx(
            (carried + (name == "moe_ms")) / 2)
    # PR 41: every cell, the optimiser and the loss under names of their own
    from step_scope_checks import check_step_scopes

    check_step_scopes(context, op_names, session.trainer)
    context["trainer"] = object()  # a program without ``compiled_step``, as the parent
    del context["_step_op_names"]
    assert readers["attn_ms"](context) is None
    context["trainer"] = session.trainer
    first.loop.state = None
    _, control = session.compare(first, control="fp8")
    correct, compared = check.verdict(control, LIMITS)
    assert correct is False
    assert compared["cell_y_err"]["value"] > LIMITS["cell_y_err"]


def test_the_entry_script_trains_the_tiny_cut(monkeypatch, capsys):
    """``benchmark_lfm2_lp.py`` with a real argv: ``build_config``,
    ``build_lfm2``, ``make_trainer`` and ``run_training``, nothing else."""
    script = os.path.join(REPO, "benchmarks", "layer_parallelism", "benchmark_lfm2_lp.py")
    argv = ["--model-config", TINY_JSON, "--sequence-length", "32",
            "--batch-size", "2", "--max-steps", "3", "--verbose"]
    monkeypatch.setattr(sys, "argv", [os.path.basename(script)] + argv)
    monkeypatch.setattr(sys, "path", list(sys.path))
    cache_dir = jax.config.jax_compilation_cache_dir
    try:
        runpy.run_path(script, run_name="__main__")
    finally:  # build_config points the persistent cache at the program's own
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    out = capsys.readouterr().out
    assert "remat policy: cell (@32 tokens)" in out
    losses = [float(m) for m in re.findall(r"loss (\d+\.\d+)", out)]
    assert len(losses) == 3 and all(np.isfinite(losses))
    assert "benchmark_lfm2_lp: Mean" in out and "seq/s" in out
