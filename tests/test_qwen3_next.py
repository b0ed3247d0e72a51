"""Qwen3-Next on the training path, at tiny widths on the CPU mesh.

The program (``mpi4dl_tpu/models/qwen3_next.py``, ``ops/sequence.py``'s Gated
DeltaNet, gated attention and expert layer with its shared expert, the entry
script) against the benchmark's plain float32 reference
(``chipbench/reference/qwen3_next.py``, which imports nothing of the program
and runs the delta rule position by position) on seeded weights; the chunked
rule against the recurrence; the 2-of-8 cut tied to the whole layer; the
expert layer's further row ranges under softmax scores; causality; and the
benchmark's own run on the tiny cell.
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
from chipbench.reference import plain
from chipbench.reference import qwen3_next as ref
from chipbench.reference.step import Follower
from mpi4dl_tpu.config import ParallelConfig
from mpi4dl_tpu.data import SyntheticTokens
from mpi4dl_tpu.models.qwen3_next import Qwen3NextConfig, qwen3_next
from mpi4dl_tpu.ops import sequence
from mpi4dl_tpu.train import Trainer, TrainState, default_remat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_JSON = os.path.join(REPO, "benchmarks", "layer_parallelism", "qwen3_next_tiny.json")

# Qwen3-Next's period (three Gated DeltaNet layers, one gated attention
# layer, an expert layer with its shared expert in each) at toy widths; this
# "chip" holds experts 4-5 of 16, 3 a token.
MODEL = {
    "hidden_size": 32, "num_hidden_layers": 4, "full_attention_interval": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4, "rms_norm_eps": 1e-6,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "num_experts": 2, "num_experts_per_tok": 3, "norm_topk_prob": True,
    "vocab_size": 48,
    "cut": {"num_experts": {"published": 16, "held": 2, "first": 4}},
}
BATCH, LENGTH = 2, 150  # two chunks of 64 positions and a part of a third


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
        "stem", "moe_linear", "moe_linear", "moe_linear", "moe_attention", "head"]
    from mpi4dl_tpu.parallel.partition import init_cells

    theirs = jax.eval_shape(
        lambda: init_cells(qwen3_next(MODEL), jax.random.PRNGKey(0), jnp.asarray(x)))
    assert jax.tree.structure(theirs) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(theirs)] == [
        a.shape for a in jax.tree.leaves(params)]
    # the router is as wide as the published model, the experts are the share,
    # the shared expert is whole; q_proj carries the output gate
    mlp = params[1]["params"]["mlp"]
    assert mlp["gate"]["kernel"].shape == (32, 16)
    assert mlp["experts"]["w1"].shape == (2, 32, 16)
    assert mlp["shared_expert"]["w2"]["kernel"].shape == (16, 32)
    assert mlp["shared_expert_gate"]["kernel"].shape == (32, 1)
    assert params[4]["params"]["self_attn"]["q_proj"]["kernel"].shape == (32, 4 * 2 * 16)
    mixer = params[1]["params"]["linear_attn"]
    assert mixer["in_proj_qkvz"]["kernel"].shape == (32, 2 * 16 + 2 * 32)
    assert mixer["conv"]["kernel"].shape == (4, 2 * 16 + 32)
    assert mixer["A_log"].shape == mixer["dt_bias"].shape == (4,)


@pytest.mark.parametrize("index", range(6), ids=[
    "stem", "moe_linear_0", "moe_linear_1", "moe_linear_2", "moe_attention", "head"])
def test_each_float32_cell_and_its_vjp_agree_with_the_reference(forced, index):
    cells, params, inputs, _ = forced
    fn, cell, h = cells[index], qwen3_next(MODEL)[index], inputs[index]
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


def _trainer(model, length):
    """Float32 cells under the entry points' remat rule."""
    cfg = ParallelConfig(
        batch_size=BATCH, split_size=1, spatial_size=0, image_size=0,
        sequence_length=length, num_classes=model["vocab_size"])
    return Trainer(qwen3_next(model), 0, cfg, remat=default_remat(cfg.image_size))


def test_three_steps_through_trainer_follow_the_reference(forced):
    """``Trainer`` (float32 cells, "cell" remat) against ``Follower`` on the
    same seeded weights and batches: losses, the parameters after, and the
    step's counters."""
    cells, params, _, _ = forced
    trainer = _trainer(MODEL, LENGTH)
    assert trainer.remat == "cell"
    state = TrainState(params=jax.tree.map(jnp.copy, params),
                       opt_state=trainer.tx.init(params),
                       step=jnp.zeros((), jnp.int32))
    follower = Follower(cells, jax.tree.map(jnp.copy, params), 0.001, 0.9, ref.loss)
    stream = iter(SyntheticTokens(BATCH, LENGTH, MODEL["vocab_size"], seed=5,
                                  prefetch=False))
    for _ in range(3):
        x, y = next(stream)
        xs, ys = trainer.shard_batch(jnp.asarray(x), jnp.asarray(y))
        state, metrics = trainer.train_step(state, xs, ys)
        loss, _ = follower.step(x, y)
        assert float(metrics["loss"]) == pytest.approx(loss, rel=2e-5)
    assert check.relative_l2(state.params, follower.params) < 1e-6
    assert check.relative_l2(
        jax.tree.map(jnp.subtract, state.params, params),
        jax.tree.map(jnp.subtract, follower.params, params)) < 1e-3
    # 4 expert layers, 2 of 16 experts held, 3 experts a token: about
    # 4 x 300 x 3 x 2/16 = 450 pairs; all four layers on their prefix
    pairs = float(metrics["moe_pairs"])
    assert 200 < pairs < 800 and pairs == int(pairs)
    assert float(metrics["moe_narrow_layers"]) == 4.0


# -- the chunked rule against the recurrence ---------------------------------


def _rule_inputs(length, decay, beta_shift, seed=0):
    """``q, k`` of unit length (``q`` scaled), ``v``, ``g = -decay *
    softplus(.)`` and ``beta = sigmoid(. + beta_shift)`` for 2 key heads of
    2 value heads each."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    b, h, r, d, e = 2, 2, 2, 16, 8

    def unit(t):
        return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

    q = unit(jax.random.normal(keys[0], (b, length, h, d))) * d ** -0.5
    k = unit(jax.random.normal(keys[1], (b, length, h, d)))
    v = jax.random.normal(keys[2], (b, length, h, r, e))
    g = -decay * jax.nn.softplus(jax.random.normal(keys[3], (b, length, h, r)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, length, h, r)) + beta_shift)
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta):
    """The reference's position-by-position rule on the program's layout."""
    b, s, h, r, e = v.shape
    out = ref.delta_rule(
        jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2), v.reshape(b, s, h * r, e),
        g.reshape(b, s, h * r), beta.reshape(b, s, h * r))
    return out.reshape(v.shape)


@pytest.mark.parametrize("length,decay,beta_shift", [
    (64, 0.1, 0.0), (256, 0.1, 0.0), (200, 1.0, 0.0), (192, 20.0, 6.0), (192, 0.01, 6.0),
    (40, 0.1, 0.0)],
    ids=["one_chunk", "four_chunks", "not_whole_chunks", "strong_decay_beta_near_1",
         "hardly_any_decay_beta_near_1", "less_than_a_chunk"])
def test_the_chunked_rule_is_the_recurrence(length, decay, beta_shift):
    """Value and all five gradients; ``g`` near -20 a position underflows a
    chunk's decay to the 0 it is, a ``beta`` near 1 with hardly any decay
    makes the triangular system as full as it gets."""
    args = _rule_inputs(length, decay, beta_shift)
    got, want = sequence.gated_delta_rule(*args), _recurrence(*args)
    assert got.shape == want.shape and np.all(np.isfinite(np.asarray(got)))
    assert check.relative_l2(got, want) < 1e-5
    ct = jax.random.normal(jax.random.PRNGKey(9), got.shape)
    grads = jax.grad(lambda *a: jnp.sum(sequence.gated_delta_rule(*a) * ct),
                     argnums=(0, 1, 2, 3, 4))(*args)
    wanted = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * ct),
                      argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v g beta".split(), grads, wanted):
        assert check.relative_l2(a, b) < 1e-4, name


def test_the_state_is_handed_from_chunk_to_chunk():
    """With hardly any decay a change at position 3 reaches position 190,
    three chunks on; with none of the state handed on it could not."""
    q, k, v, g, beta = _rule_inputs(192, 0.01, 0.0)
    moved = v.at[:, 3].add(1.0)
    delta = np.abs(np.asarray(
        sequence.gated_delta_rule(q, k, moved, g, beta)
        - sequence.gated_delta_rule(q, k, v, g, beta))).max(axis=(0, 2, 3, 4))
    assert np.all(delta[:3] == 0.0) and delta[3] > 0 and delta[190] > 1e-4


def test_the_series_inverts_a_unit_triangular_matrix():
    lower = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 64, 64)), -1) * 0.3
    want = jnp.linalg.inv(jnp.eye(64) - lower)
    assert check.relative_l2(sequence._nilpotent_inverse(lower), want) < 1e-5
    ct = jax.random.normal(jax.random.PRNGKey(1), lower.shape)
    got = jax.grad(lambda a: jnp.sum(sequence._nilpotent_inverse(a) * ct))(lower)
    wanted = jax.grad(lambda a: jnp.sum(jnp.linalg.inv(jnp.eye(64) - a) * ct))(lower)
    assert check.relative_l2(got, wanted) < 1e-4


# -- the expert layer: the share, softmax scores, the further row ranges -----


def _expert_layer(held, first, experts=16, favoured=(), boost=0.0, seed=1):
    """``(sizes, parameters, input, the program's layer)`` of a share of
    ``held`` experts from ``first`` on. ``favoured``: experts whose router
    column is raised along a constant feature of the input, so that every
    token picks them."""
    model = dict(MODEL, num_experts=held,
                 cut={"num_experts": {"published": experts, "held": held, "first": first}})
    s = ref.sizes(model)
    spec: dict = {}
    shape = (BATCH, 40, s.hidden)
    jax.eval_shape(lambda x: ref.expert_layer(plain.Scope(spec=spec), x, s),
                   jax.ShapeDtypeStruct(shape, jnp.float32))
    params = plain.make_params([spec], seed)[0]["params"]
    x = jax.random.normal(jax.random.PRNGKey(2), shape)
    if favoured:
        x = x.at[..., 0].set(4.0)
        params["gate"]["kernel"] = params["gate"]["kernel"].at[
            0, jnp.asarray(favoured)].add(boost)
    layer = sequence.ExpertFFN(
        s.hidden, s.expert_width, s.experts, s.held, s.first, s.per_token, s.norm_topk,
        expert_bias=False, dtype=jnp.float32, scoring="softmax",
        shared_width=s.shared_width)
    return s, params, x, layer


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """8 experts in 4 shares of 2: the four shares' routed parts plus the
    shared expert counted once are the uncut reference's layer."""
    whole = dict(MODEL, num_experts=8)
    del whole["cut"]
    s = ref.sizes(whole)
    spec: dict = {}
    shape = (BATCH, 40, s.hidden)
    jax.eval_shape(lambda x: ref.expert_layer(plain.Scope(spec=spec), x, s),
                   jax.ShapeDtypeStruct(shape, jnp.float32))
    params = plain.make_params([spec], 1)[0]["params"]
    x = jax.random.normal(jax.random.PRNGKey(2), shape)
    want = ref.expert_layer(plain.Scope(params), x, s)
    shared = ref.shared_expert(plain.Scope(params), x, s)
    from_reference, from_program = shared, shared
    for first in range(0, 8, 2):
        cut = ref.sizes(dict(MODEL, cut={"num_experts": {
            "published": 8, "held": 2, "first": first}}))
        held = dict(params, experts={k: w[first:first + 2]
                                     for k, w in params["experts"].items()})
        from_reference = from_reference + ref.routed_experts(plain.Scope(held), x, cut)
        layer = sequence.ExpertFFN(
            cut.hidden, cut.expert_width, cut.experts, cut.held, cut.first,
            cut.per_token, cut.norm_topk, expert_bias=False, dtype=jnp.float32,
            scoring="softmax", shared_width=cut.shared_width)
        # the program's layer adds the shared expert every time: take it off
        from_program = from_program + layer.apply({"params": held}, x) - shared
    assert check.relative_l2(from_reference, want) < 1e-6
    assert check.relative_l2(from_program, want) < 1e-6
    assert check.relative_l2(shared, want) > 0.3  # the shared expert alone is a part


@pytest.mark.parametrize("held,favoured,boost,trips", [
    (2, (), 0.0, 0), (2, (4,), 3.0, 1), (2, (4, 5), 0.4, 1), (2, (4, 5), 3.0, 2),
    (3, (), 0.0, 0), (3, (4, 5), 0.5, 1), (3, (4, 5, 6), 3.0, 2)],
    ids=["2of16_below_prefix", "2of16_one_range_past", "2of16_just_past",
         "2of16_two_ranges_past", "3of16_below_prefix", "3of16_one_range_past",
         "3of16_every_pair_last_range_moved_back"])
def test_the_row_ranges_past_the_prefix_under_softmax_scores(held, favoured, boost, trips):
    """A share of 2 (3) of 16 experts, 3 a token, 80 tokens: 240 sorted pair
    rows, a prefix of 60 (90); the rows past it run in ranges of the prefix's
    width, as many as the held pairs reach, the last moved back to end with
    the rows (3 of 16: 90, 90, then 60 that are computed as rows 150-240).
    Value and every gradient against the float32 reference."""
    s, params, x, layer = _expert_layer(held, 4, favoured=favoured, boost=boost)
    ct = jax.random.normal(jax.random.PRNGKey(4), x.shape)

    def loss(v, x_):
        y, sown = layer.apply({"params": v}, x_, mutable=[sequence.COUNTERS])
        return jnp.sum(y * ct), (y, sown[sequence.COUNTERS])

    (_, (y, counted)), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(params, x)
    prefix = sequence._prefix_rows(240, held, 16)
    n = int(counted["expert_pairs"][0].sum())
    assert prefix == 30 * held
    assert -(-max(n - prefix, 0) // prefix) == trips, n
    assert int(counted["prefix_alone"][0]) == (trips == 0)
    assert check.relative_l2(y, ref.expert_layer(plain.Scope(params), x, s)) < 1e-6
    wanted = jax.grad(
        lambda v, x_: jnp.sum(ref.expert_layer(plain.Scope(v), x_, s) * ct),
        argnums=(0, 1))(params, x)
    assert check.relative_l2(grads, wanted) < 1e-5
    for leaf in ("w1", "w3", "w2"):
        assert check.relative_l2(grads[0]["experts"][leaf], wanted[0]["experts"][leaf]) < 1e-5
    assert check.relative_l2(grads[0]["gate"], wanted[0]["gate"]) < 1e-4


# -- attention's options, causality ------------------------------------------


def test_the_rotary_embedding_turns_the_leading_dims_alone():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 2, 16))
    turned = sequence.rope(x, 1e7, 4)
    np.testing.assert_array_equal(np.asarray(turned[..., 4:]), np.asarray(x[..., 4:]))
    np.testing.assert_allclose(
        np.asarray(turned[..., :4]), np.asarray(sequence.rope(x[..., :4], 1e7)), rtol=1e-6)
    assert check.relative_l2(turned, ref.rope(x, 1e7, 4)) < 1e-6
    np.testing.assert_array_equal(np.asarray(turned[:, 0]), np.asarray(x[:, 0]))  # position 0


@pytest.mark.parametrize("mixer", ["linear_attention", "full_attention"])
def test_position_t_does_not_see_t_plus_1(mixer):
    """Perturb one position of a layer's input: no earlier position's output
    moves, that position's and later ones' do (program and reference), across
    a chunk's edge too."""
    interval = 4 if mixer == "linear_attention" else 1
    model = dict(MODEL, num_hidden_layers=1, full_attention_interval=interval)
    assert Qwen3NextConfig.from_dict(model).mixer(0) == mixer
    cells, params = _seeded(model, length=100)
    layer, v = qwen3_next(model)[1], params[1]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 100, 32))
    moved = x.at[0, 62].add(1.0)
    for apply in (lambda a: layer.apply(v, a),
                  lambda a: cells[1](plain.Scope(v["params"]), a)):
        delta = np.abs(np.asarray(apply(moved) - apply(x))).max(axis=-1)[0]
        assert np.all(delta[:62] == 0.0) and np.all(delta[62:70] > 0)


def test_a_config_with_a_dense_layer_or_rope_scaling_is_refused():
    with pytest.raises(ValueError, match="expert layer"):
        Qwen3NextConfig.from_dict(dict(MODEL, mlp_only_layers=[0]))
    with pytest.raises(ValueError, match="rope_scaling"):
        Qwen3NextConfig.from_dict(dict(MODEL, rope_scaling={"type": "yarn"}))
    config = Qwen3NextConfig.from_dict(dict(MODEL, num_hidden_layers=8))
    assert [config.mixer(i) for i in range(8)] == 2 * (
        3 * ["linear_attention"] + ["full_attention"])
    assert config.router_experts == 16 and config.first_expert == 4


def test_the_flop_count_is_the_models_least_work():
    """The configuration's file: 10 x 32 / 512 pairs a token, the router at
    its full width, the shared expert, attention over the causal half, the
    recurrence's three products; recomputation not counted."""
    with open(os.path.join(REPO, "chipbench", "configs",
                           "qwen3_next_80b_a3b_share16.json")) as f:
        model = json.load(f)
    linear = 2 * 2048 * (12288 + 64) + 2 * 4096 * 2048 + 3 * 2 * 32 * 128 * 128
    attention = 2 * 2048 * (8192 + 1024) + 2 * 4096 * 2048 + 2 * 2 * 4096 * 8192 / 2
    moe = 2 * 2048 * 512 + 0.625 * 3 * 2 * 2048 * 512 + 3 * 2 * 2048 * 512 + 2 * 2048
    want = 3 * linear + attention + 4 * moe + 2 * 2048 * 18992
    assert ref.forward_flops_per_token(model, 8192) == pytest.approx(want, rel=1e-12)
    assert ref.train_flops_per_sample(model, {"sequence_length": 8192}) == pytest.approx(
        3 * 8192 * want, rel=1e-12)
    specs = plain.record_specs(ref.cells(model), (1, 128), jnp.int32)
    held = sum(int(np.prod(shape)) for spec in specs for shape, _ in spec.values())
    assert held == model["parameters"]["held"] == 625667136


# -- the benchmark's run on the tiny cell, the entry script ------------------

# Tiny-size readings on the CPU (seeds 5, 6, 97, 2147483659, 2147483670,
# 3000000019; PR 37): the bf16 program's largest beside the fp8 control's
# smallest.
LIMITS = {
    "cell_y_err": 0.03,       # 0.0130; control 0.0555
    "cell_dx_err": 0.2,       # 0.1038; control 0.3916 (hidden 64, key dim 16:
                              # the projections' rounding shows in the backward
                              # at this width as it does not at 256 and 64)
    "loss_gap_step1": 0.01,
    "change_norm_gap": 0.5,   # a state left unchanged reads 1.0
}


def test_the_benchmarks_run_passes_the_program_and_fails_the_fp8_control(tmp_path, capsys):
    from chipbench import run
    from chipbench.harness import scopes, spec, xtrace
    from chipbench.harness.session import Session
    from chipbench.tests import tiny

    cell = tiny.tiny_cell(tmp_path, "qwen3_next_80b_a3b_share16", limits=LIMITS)
    result = run.run(tiny.options(cell.name, seed=2147483659 + 11), jax.devices(),
                     cell=cell, peaks=tiny.PEAKS)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"images_per_s", "step_ms_p90", "setup_s"}
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    build = next(l for l in lines if l.get("phase") == "build")
    assert build["remat"] == "cell" and build["cells"] == 6 and build["spatial_cells"] == 0
    kinds = next(l for l in lines if l.get("phase") == "reference")["tap_kinds"]
    assert kinds == ["stem", "moe_linear", "moe_attention", "head"]

    session = Session(cell)
    first = session.first_steps(2147483659 + 11, session.check_steps)
    context = {"trainer": session.trainer, "session": session, "cell": cell,
               "reduced": None}
    per_token = spec.metric_reader("layer_metrics", "moe_held_pairs_per_token")(context)
    # 4 expert layers; 3 experts a token of which 4 of 16 are held: 0.75 expected
    assert 0.5 < per_token < 1.0
    prefix_layers = spec.metric_reader("layer_metrics", "moe_prefix_layers")
    assert prefix_layers(context) == 4.0
    assert prefix_layers(dict(context, trainer=object())) is None
    by_scope = {"linear_attn_ms": "gated_delta", "delta_rule_ms": "gated_delta_rule",
                "gated_attn_ms": "lfm2_attention", "sparse_moe_ms": "lfm2_moe",
                "shared_expert_ms": "shared_expert"}
    readers = {name: spec.metric_reader("layer_metrics", name) for name in by_scope}
    assert all(read(context) is None for read in readers.values())  # not traced
    # one event of 1 ms for every instruction of the compiled step that runs
    # (a parameter, named after its path in the state, is no device event),
    # two steps
    op_names = {name: stack for name, stack in scopes.step_op_names(context).items()
                if not name.startswith("state_")}
    events = [xtrace.Event(f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p)", i * 1e6, 1e6, {})
              for i, name in enumerate(op_names)]
    context["reduced"] = xtrace.Reduced(
        steps=2, window_s=len(events) / 1e3, busy_s=len(events) / 1e3,
        chips=[{"window": (0.0, len(op_names) * 1e6), "ops": events}],
        device_ops=[], idle_gaps=[])
    read = {name: readers[name](context) for name in by_scope}
    for name, scope in by_scope.items():
        carried = sum(scope in stack for stack in op_names.values())
        assert carried > 5, scope  # forward, recomputed forward and backward
        assert read[name] == pytest.approx(carried / 2)
    # a scope inside another is a part of it
    assert read["delta_rule_ms"] < read["linear_attn_ms"]
    assert read["shared_expert_ms"] < read["sparse_moe_ms"]
    # PR 41: every cell, the optimiser and the loss under names of their own
    from step_scope_checks import check_step_scopes

    check_step_scopes(context, op_names, session.trainer)
    context["trainer"] = object()  # a program without ``compiled_step``, as the parent
    del context["_step_op_names"]
    assert all(readers[name](context) is None for name in by_scope)
    context["trainer"] = session.trainer
    first.loop.state = None
    _, control = session.compare(first, control="fp8")
    correct, compared = check.verdict(control, LIMITS)
    assert correct is False
    assert compared["cell_y_err"]["value"] > LIMITS["cell_y_err"]


def test_the_entry_script_trains_the_tiny_cut(monkeypatch, capsys):
    """``benchmark_qwen3_next_lp.py`` with a real argv: ``build_config``,
    ``build_qwen3_next``, ``make_trainer`` and ``run_training``, nothing else."""
    script = os.path.join(REPO, "benchmarks", "layer_parallelism",
                          "benchmark_qwen3_next_lp.py")
    argv = ["--model-config", TINY_JSON, "--sequence-length", "160",
            "--batch-size", "2", "--max-steps", "3", "--verbose"]
    monkeypatch.setattr(sys, "argv", [os.path.basename(script)] + argv)
    monkeypatch.setattr(sys, "path", list(sys.path))
    cache_dir = jax.config.jax_compilation_cache_dir
    try:
        runpy.run_path(script, run_name="__main__")
    finally:  # build_config points the persistent cache at the program's own
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    out = capsys.readouterr().out
    assert "remat policy: cell (@160 tokens)" in out
    losses = [float(m) for m in re.findall(r"loss (\d+\.\d+)", out)]
    assert len(losses) == 3 and all(np.isfinite(losses))
    assert "benchmark_qwen3_next_lp: Mean" in out and "seq/s" in out
