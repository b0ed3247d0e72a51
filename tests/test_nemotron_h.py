"""Nemotron-H on the training path, at tiny widths on the CPU mesh.

The program (``mpi4dl_tpu/models/nemotron_h.py``, ``ops/sequence.py``'s
Mamba-2 mixer and chunked scan, attention without a positional embedding,
the squared-ReLU expert layer with its ungated shared expert, the entry
script) against the benchmark's plain float32 reference
(``chipbench/reference/nemotron_h.py``, which imports nothing of the program
and runs the state-space layer position by position) on seeded weights; the
chunked scan against the recurrence; the 2-of-8 cut tied to the whole layer;
causality of the three mixers; refused configurations; and the benchmark's
own run on the tiny cell.
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
from chipbench.reference import nemotron_h as ref
from chipbench.reference import plain
from chipbench.reference.step import Follower
from mpi4dl_tpu.config import ParallelConfig
from mpi4dl_tpu.data import SyntheticTokens
from mpi4dl_tpu.models.nemotron_h import NemotronHConfig, nemotron_h
from mpi4dl_tpu.ops import sequence
from mpi4dl_tpu.train import Trainer, TrainState, default_remat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_JSON = os.path.join(REPO, "benchmarks", "layer_parallelism", "nemotron_h_tiny.json")

# The first nine letters of the published pattern at toy widths; this "chip"
# holds experts 4-5 of 16, 3 a token. Chunks of 32 positions.
MODEL = {
    "hidden_size": 32, "num_hidden_layers": 9, "hybrid_override_pattern": "MEMEM*EME",
    "layer_norm_epsilon": 1e-5,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "chunk_size": 32, "use_conv_bias": True,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_intermediate_size": 16, "moe_shared_expert_intermediate_size": 24,
    "n_routed_experts": 2, "n_shared_experts": 1, "num_experts_per_tok": 3,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5, "n_group": 1, "topk_group": 1,
    "mlp_hidden_act": "relu2", "mlp_bias": False, "attention_bias": False,
    "use_bias": False, "vocab_size": 48,
    "cut": {"n_routed_experts": {"published": 16, "held": 2, "first": 4}},
}
BATCH, LENGTH = 2, 75  # two chunks of 32 positions and a part of a third
CELL_IDS = ["stem"] + [f"{i}_{k}" for i, k in enumerate(ref.kinds(MODEL)[1:-1])] + ["head"]


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
        "stem", "mamba", "moe_relu2", "mamba", "moe_relu2", "mamba", "attention",
        "moe_relu2", "mamba", "moe_relu2", "head"]
    from mpi4dl_tpu.parallel.partition import init_cells

    program = nemotron_h(MODEL)
    theirs = jax.eval_shape(
        lambda: init_cells(program, jax.random.PRNGKey(0), jnp.asarray(x)))
    assert jax.tree.structure(theirs) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(theirs)] == [
        a.shape for a in jax.tree.leaves(params)]
    # only the expert cells name a counters collection
    assert [cell.counters for cell in program[1:-1]] == [
        sequence.COUNTERS if kind == "E" else None for kind in MODEL["hybrid_override_pattern"]]
    # one mixer a layer; the router is as wide as the published model, the
    # experts are the share and have two arrays each, the shared expert is
    # whole and has no gate; attention has neither q/k norm nor a gate
    mamba, moe, attn = (params[i]["params"] for i in (1, 2, 6))
    assert set(mamba) == set(moe) == set(attn) == {"norm", "mixer"}
    assert mamba["mixer"]["in_proj"]["kernel"].shape == (32, 2 * 32 + 2 * 2 * 16 + 4)
    assert mamba["mixer"]["conv"]["kernel"].shape == (4, 32 + 2 * 2 * 16)
    assert mamba["mixer"]["conv_bias"].shape == (32 + 2 * 2 * 16,)
    assert (mamba["mixer"]["A_log"].shape == mamba["mixer"]["dt_bias"].shape
            == mamba["mixer"]["D"].shape == (4,))
    assert mamba["mixer"]["norm_scale"].shape == (32,)
    assert moe["mixer"]["gate"]["kernel"].shape == (32, 16)
    assert moe["mixer"]["expert_bias"].shape == (16,)
    assert set(moe["mixer"]["experts"]) == {"w1", "w2"}
    assert moe["mixer"]["experts"]["w1"].shape == (2, 32, 16)
    assert set(moe["mixer"]["shared_expert"]) == {"w1", "w2"}
    assert moe["mixer"]["shared_expert"]["w2"]["kernel"].shape == (24, 32)
    assert set(attn["mixer"]) == {"q_proj", "k_proj", "v_proj", "out_proj"}
    assert attn["mixer"]["q_proj"]["kernel"].shape == (32, 4 * 16)


def test_a_fresh_programs_own_initialisers_are_the_published_ones():
    """``Mamba2``'s own ``init`` (the entry script's path): ``A`` uniform over
    (1, 16), ``dt = softplus(dt_bias)`` log-uniform over the time-step range."""
    layer = sequence.Mamba2(32, 64, 8, 2, 16, 4, 32, 1e-5, (0.001, 0.1, 1e-4), jnp.float32)
    v = layer.init(jax.random.PRNGKey(1), jnp.zeros((1, 8, 32)))["params"]
    a, dt = np.exp(np.asarray(v["A_log"])), np.asarray(jax.nn.softplus(v["dt_bias"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 and a.max() - a.min() > 8
    assert dt.min() >= 0.001 * 0.999 and dt.max() <= 0.1 * 1.001 and dt.max() / dt.min() > 10
    np.testing.assert_array_equal(np.asarray(v["D"]), 1.0)
    np.testing.assert_array_equal(np.asarray(v["conv_bias"]), 0.0)


@pytest.mark.parametrize("index", range(11), ids=CELL_IDS)
def test_each_float32_cell_and_its_vjp_agree_with_the_reference(forced, index):
    cells, params, inputs, _ = forced
    fn, cell, h = cells[index], nemotron_h(MODEL)[index], inputs[index]
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
    return Trainer(nemotron_h(model), 0, cfg, remat=default_remat(cfg.image_size))


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
    # 4 x 150 x 3 x 2/16 = 225 pairs; all four layers on their prefix
    pairs = float(metrics["moe_pairs"])
    assert 100 < pairs < 400 and pairs == int(pairs)
    assert float(metrics["moe_narrow_layers"]) == 4.0


# -- the chunked scan against the recurrence ---------------------------------


def _scan_inputs(length, rate, seed=0):
    """``x`` (the layer's ``dt x``), ``g = -rate * softplus(.)``, ``b, c``
    for 2 groups of 2 heads each, head dim 8, state 16."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    batch, groups, per, dim, state = 2, 2, 2, 8, 16
    x = jax.random.normal(keys[0], (batch, length, groups, per, dim))
    g = -rate * jax.nn.softplus(jax.random.normal(keys[1], (batch, length, groups, per)))
    b = jax.random.normal(keys[2], (batch, length, groups, state))
    c = jax.random.normal(keys[3], (batch, length, groups, state))
    return x, g, b, c


def _recurrence(x, g, b, c):
    """The reference's position-by-position recurrence on the program's
    layout: ``dt`` one and ``A = g`` a position, so that ``exp(dt A)`` is the
    decay and ``dt x`` the input."""
    batch, length, groups, per, dim = x.shape
    heads = groups * per

    def position(state, at):
        x_t, g_t, b_t, c_t = at
        state = state * jnp.exp(g_t)[..., None, None] + x_t[..., None] * b_t[:, :, None]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t, precision="highest")

    rows = (x.reshape(batch, length, heads, dim), g.reshape(batch, length, heads),
            jnp.repeat(b, per, axis=2), jnp.repeat(c, per, axis=2))
    zero = jnp.zeros((batch, heads, dim, b.shape[-1]))
    _, out = jax.lax.scan(position, zero, tuple(jnp.moveaxis(t, 1, 0) for t in rows))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape)


def test_the_references_recurrence_is_this_files():
    """``ref.recurrence`` (``dt`` and ``A`` apart, blocks under a checkpoint,
    padding) against the four lines above."""
    x, g, b, c = _scan_inputs(75, 0.3)
    heads = x.shape[2] * x.shape[3]
    a = -jnp.linspace(0.5, 2.0, heads)
    dt = (g.reshape(*g.shape[:2], heads) / a)
    u = x.reshape(*x.shape[:2], heads, -1) / dt[..., None]
    got = ref.recurrence(u, dt, a, jnp.repeat(b, 2, axis=2), jnp.repeat(c, 2, axis=2))
    assert check.relative_l2(got.reshape(x.shape), _recurrence(x, g, b, c)) < 1e-5


@pytest.mark.parametrize("length,rate", [
    (32, 0.1), (128, 0.1), (75, 1.0), (96, 30.0), (96, 0.001), (20, 0.1)],
    ids=["one_chunk", "four_chunks", "not_whole_chunks", "fast_decay",
         "hardly_any_decay", "less_than_a_chunk"])
def test_the_chunked_scan_is_the_recurrence(length, rate):
    """Value and all four gradients at chunks of 32; ``g`` near -30 a
    position underflows a chunk's decay to the 0 it is, a ``g`` near 0 makes
    every chunk's state reach every later chunk."""
    args = _scan_inputs(length, rate)
    got, want = sequence.ssd_scan(*args, 32), _recurrence(*args)
    assert got.shape == want.shape and np.all(np.isfinite(np.asarray(got)))
    assert check.relative_l2(got, want) < 1e-5
    ct = jax.random.normal(jax.random.PRNGKey(9), got.shape)
    grads = jax.grad(lambda *a: jnp.sum(sequence.ssd_scan(*a, 32) * ct),
                     argnums=(0, 1, 2, 3))(*args)
    wanted = jax.grad(lambda *a: jnp.sum(_recurrence(*a) * ct),
                      argnums=(0, 1, 2, 3))(*args)
    for name, a, b in zip("x g b c".split(), grads, wanted):
        assert np.all(np.isfinite(np.asarray(a))), name
        assert check.relative_l2(a, b) < 1e-4, name


def test_the_state_is_handed_from_chunk_to_chunk():
    """With hardly any decay a change at position 3 reaches position 120,
    three chunks on; with none of the state handed on it could not."""
    x, g, b, c = _scan_inputs(128, 0.001)
    moved = x.at[:, 3].add(1.0)
    delta = np.abs(np.asarray(
        sequence.ssd_scan(moved, g, b, c, 32)
        - sequence.ssd_scan(x, g, b, c, 32))).max(axis=(0, 2, 3, 4))
    assert np.all(delta[:3] == 0.0) and delta[3] > 0 and delta[120] > 1e-3


# -- the expert layer: the share, squared-ReLU experts, the ungated shared one


def _expert_layer(cut):
    return sequence.ExpertFFN(
        cut.hidden, cut.expert_width, cut.experts, cut.held, cut.first, cut.per_token,
        cut.norm_topk, cut.scaling, dtype=jnp.float32, shared_width=cut.shared_width,
        activation="relu2", shared_gate=False)


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """8 experts in 4 shares of 2: the four shares' routed parts plus the
    shared expert counted once are the uncut reference's layer; a moved
    ``expert_bias`` changes the choice and not the weights."""
    whole = dict(MODEL, n_routed_experts=8)
    del whole["cut"]
    s = ref.sizes(whole)
    spec: dict = {}
    shape = (BATCH, 40, s.hidden)
    jax.eval_shape(lambda x: ref.expert_layer(plain.Scope(spec=spec), x, s),
                   jax.ShapeDtypeStruct(shape, jnp.float32))
    params = plain.make_params([spec], 1)[0]["params"]
    params["expert_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (8,))
    x = jax.random.normal(jax.random.PRNGKey(2), shape)
    want = ref.expert_layer(plain.Scope(params), x, s)
    shared = ref.shared_expert(plain.Scope(params), x, s)
    from_reference, from_program = shared, shared
    for first in range(0, 8, 2):
        cut = ref.sizes(dict(MODEL, cut={"n_routed_experts": {
            "published": 8, "held": 2, "first": first}}))
        held = dict(params, experts={k: w[first:first + 2]
                                     for k, w in params["experts"].items()})
        from_reference = from_reference + ref.routed_experts(plain.Scope(held), x, cut)
        # the program's layer adds the shared expert every time: take it off
        from_program = from_program + _expert_layer(cut).apply({"params": held}, x) - shared
    assert check.relative_l2(from_reference, want) < 1e-6
    assert check.relative_l2(from_program, want) < 1e-6
    assert check.relative_l2(shared, want) > 0.3  # the shared expert alone is a part
    # 3 experts a token, their weights sum to the scaling factor
    _, weights = ref.routing(plain.Scope(params), x, s)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 2.5, rtol=1e-6)


@pytest.mark.parametrize("favoured,boost,trips", [((), 0.0, 0), ((4, 5), 6.0, 2)],
                         ids=["below_prefix", "two_ranges_past"])
def test_the_squared_relu_experts_gradients_in_and_past_the_prefix(favoured, boost, trips):
    """A share of 2 of 16 experts, 3 a token, 80 tokens: 240 sorted pair rows,
    a prefix of 60; value and every gradient of the two-array expert layer
    against the float32 reference, on the prefix alone and with every token
    on both held experts (two further ranges)."""
    s = ref.sizes(MODEL)
    spec: dict = {}
    shape = (BATCH, 40, s.hidden)
    jax.eval_shape(lambda x: ref.expert_layer(plain.Scope(spec=spec), x, s),
                   jax.ShapeDtypeStruct(shape, jnp.float32))
    params = plain.make_params([spec], 1)[0]["params"]
    x = jax.random.normal(jax.random.PRNGKey(2), shape)
    if favoured:
        x = x.at[..., 0].set(4.0)
        params["gate"]["kernel"] = params["gate"]["kernel"].at[
            0, jnp.asarray(favoured)].add(boost)
    layer = _expert_layer(s)
    ct = jax.random.normal(jax.random.PRNGKey(4), x.shape)

    def loss(v, x_):
        y, sown = layer.apply({"params": v}, x_, mutable=[sequence.COUNTERS])
        return jnp.sum(y * ct), (y, sown[sequence.COUNTERS])

    (_, (y, counted)), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(params, x)
    n = int(counted["expert_pairs"][0].sum())
    assert -(-max(n - 60, 0) // 60) == trips, n
    assert int(counted["prefix_alone"][0]) == (trips == 0)
    assert check.relative_l2(y, ref.expert_layer(plain.Scope(params), x, s)) < 1e-6
    wanted = jax.grad(
        lambda v, x_: jnp.sum(ref.expert_layer(plain.Scope(v), x_, s) * ct),
        argnums=(0, 1))(params, x)
    assert check.relative_l2(grads, wanted) < 1e-5
    for leaf in ("w1", "w2"):
        assert check.relative_l2(grads[0]["experts"][leaf], wanted[0]["experts"][leaf]) < 1e-5
    assert check.relative_l2(grads[0]["gate"], wanted[0]["gate"]) < 1e-4


# -- attention's options, causality, refused configurations ------------------


def test_attention_can_say_no_rotary_embedding_and_no_qk_norm():
    """``rotary_dim`` None turns every dim (LFM2's call), a number the leading
    dims (Qwen3-Next's), 0 none; ``qk_norm`` False leaves q and k as
    projected and makes no parameter for a norm."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 2, 16))
    np.testing.assert_array_equal(
        np.asarray(sequence.rope(x, 1e4)), np.asarray(sequence.rope(x, 1e4, 16)))
    assert not np.allclose(np.asarray(sequence.rope(x, 1e4))[:, 1:], np.asarray(x)[:, 1:])
    np.testing.assert_array_equal(np.asarray(sequence.rope(x, 1e4, 0)), np.asarray(x))
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 20, 32))
    kw = dict(dtype=jnp.float32, head_dim=16)
    bare = sequence.Attention(32, 4, 2, 1e-5, 1e4, rotary_dim=0, qk_norm=False, **kw)
    v = bare.init(jax.random.PRNGKey(2), h)
    assert set(v["params"]) == {"q_proj", "k_proj", "v_proj", "out_proj"}
    normed = sequence.Attention(32, 4, 2, 1e-5, 1e4, rotary_dim=0, **kw)
    with_norms = normed.init(jax.random.PRNGKey(2), h)
    assert set(with_norms["params"]) - set(v["params"]) == {"q_layernorm", "k_layernorm"}
    turned = sequence.Attention(32, 4, 2, 1e-5, 1e4, qk_norm=False, **kw)
    assert not np.allclose(np.asarray(turned.apply(v, h)), np.asarray(bare.apply(v, h)))
    # without positions a permutation of the earlier tokens changes nothing
    # for the last one
    swapped = h.at[:, [0, 5]].set(h[:, [5, 0]])
    np.testing.assert_allclose(np.asarray(bare.apply(v, swapped))[:, -1],
                               np.asarray(bare.apply(v, h))[:, -1], rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("letter", ["M", "E", "*"], ids=["mamba", "moe", "attention"])
def test_position_t_does_not_see_t_plus_1(letter):
    """Perturb one position of a layer's input: no earlier position's output
    moves, that position's does (program and reference), and for the two
    mixers that mix positions later ones do too, across a chunk's edge."""
    model = dict(MODEL, num_hidden_layers=1, hybrid_override_pattern=letter)
    cells, params = _seeded(model, length=100)
    layer, v = nemotron_h(model)[1], params[1]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 100, 32))
    moved = x.at[0, 30].add(1.0)
    for apply in (lambda a: layer.apply(v, a),
                  lambda a: cells[1](plain.Scope(v["params"]), a)):
        delta = np.abs(np.asarray(apply(moved) - apply(x))).max(axis=-1)[0]
        assert np.all(delta[:30] == 0.0) and delta[30] > 0
        if letter == "E":  # an expert layer mixes nothing
            assert np.all(delta[31:] == 0.0)
        else:
            assert np.all(delta[30:40] > 0)


@pytest.mark.parametrize("change,message", [
    ({"hybrid_override_pattern": "MEMEM-EME"}, "a layer is one of"),
    ({"hybrid_override_pattern": "MEMEM*EM"}, "num_hidden_layers layers"),
    ({"n_group": 2}, "group-limited"),
    ({"topk_group": 2}, "group-limited"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"attention_bias": True}, "attention_bias"),
    ({"use_bias": True}, "use_bias"),
    ({"mlp_hidden_act": "silu"}, "relu2"),
], ids=["a_dense_layer", "pattern_length", "n_group", "topk_group", "mlp_bias",
        "attention_bias", "use_bias", "another_activation"])
def test_a_configuration_the_model_cannot_say_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        NemotronHConfig.from_dict(dict(MODEL, **change))


def test_the_configuration_reads_its_cut():
    config = NemotronHConfig.from_dict(MODEL)
    assert config.pattern == "MEMEM*EME"
    assert config.router_experts == 16 and config.first_expert == 4
    assert config.n_routed_experts == 2 and config.time_step == (0.001, 0.1, 1e-4)
    whole = dict(MODEL, n_routed_experts=16)
    del whole["cut"]
    config = NemotronHConfig.from_dict(whole)
    assert config.router_experts == 16 and config.first_expert == 0


def test_the_flop_count_is_the_models_least_work():
    """The configuration's file: 6 x 8 / 128 pairs a token, the router at its
    full width, the ungated shared expert, attention over the causal half,
    the recurrence's three products; recomputation not counted. And the
    file's own account of itself: every catalog key, the parameter count."""
    with open(os.path.join(REPO, "chipbench", "configs",
                           "nemotron_twotower_30b_a3b_share16.json")) as f:
        model = json.load(f)
    mamba = 2 * 2688 * 10304 + 2 * 4096 * 2688 + 3 * 2 * 64 * 64 * 128
    attention = 2 * 2688 * (4096 + 512) + 2 * 4096 * 2688 + 2 * 2 * 4096 * 8192 / 2
    moe = 2 * 2688 * 128 + 0.375 * 2 * 2 * 2688 * 1856 + 2 * 2 * 2688 * 3712
    want = 4 * mamba + attention + 4 * moe + 2 * 2688 * 16384
    assert ref.forward_flops_per_token(model, 8192) == pytest.approx(want, rel=1e-12)
    assert ref.train_flops_per_sample(model, {"sequence_length": 8192}) == pytest.approx(
        3 * 8192 * want, rel=1e-12)
    specs = plain.record_specs(ref.cells(model), (1, 128), jnp.int32)
    held = sum(int(np.prod(shape)) for spec in specs for shape, _ in spec.values())
    assert held == model["parameters"]["held"] == 666963456
    assert model["hybrid_override_pattern"] == model["cut"]["hybrid_override_pattern"][
        "published"][:9]
    for word in ("denoiser", "adaLN", "cross-tower", "block length", "noise schedule"):
        assert word in model["assumed"]["denoiser_tower"], word


# -- the benchmark's run on the tiny cell, the entry script ------------------

# Tiny-size readings on the CPU (seeds 5, 6, 97, 2147483659, 2147483670,
# 3000000019; PR 39): the bf16 program's largest beside the fp8 control's
# smallest.
LIMITS = {
    "cell_y_err": 0.013,      # 0.0045; control 0.0368
    "cell_dx_err": 0.032,     # 0.0118; control 0.0897
    "loss_gap_step1": 0.01,   # 0.0022
    "change_norm_gap": 0.5,   # 0.0036; a state left unchanged reads 1.0
}
BY_SCOPE = {"mamba_mixer_ms": "mamba2", "ssd_scan_ms": "ssd_scan",
            "nemotron_attn_ms": "lfm2_attention", "nemotron_moe_ms": "lfm2_moe",
            "nemotron_shared_expert_ms": "shared_expert"}


def test_the_benchmarks_run_passes_the_program_and_fails_the_fp8_control(tmp_path, capsys):
    from chipbench import run
    from chipbench.harness import scopes, spec, xtrace
    from chipbench.harness.session import Session
    from chipbench.tests import tiny

    cell = tiny.tiny_cell(tmp_path, "nemotron_twotower_30b_a3b_share16", limits=LIMITS)
    result = run.run(tiny.options(cell.name, seed=2147483659 + 11), jax.devices(),
                     cell=cell, peaks=tiny.PEAKS)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"images_per_s", "step_ms_p90", "setup_s"}
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    build = next(l for l in lines if l.get("phase") == "build")
    assert build["remat"] == "cell" and build["cells"] == 11 and build["spatial_cells"] == 0
    kinds = next(l for l in lines if l.get("phase") == "reference")["tap_kinds"]
    assert sorted(kinds) == sorted(["stem", "mamba", "moe_relu2", "attention", "head"])

    session = Session(cell)
    first = session.first_steps(2147483659 + 11, session.check_steps)
    context = {"trainer": session.trainer, "session": session, "cell": cell,
               "reduced": None}
    per_token = spec.metric_reader("layer_metrics", "nemotron_moe_held_pairs_per_token")(context)
    # 4 expert layers; 3 experts a token of which 4 of 16 are held: 0.75 expected
    assert 0.5 < per_token < 1.0
    prefix_layers = spec.metric_reader("layer_metrics", "nemotron_moe_prefix_layers")
    assert prefix_layers(context) == 4.0
    assert prefix_layers(dict(context, trainer=object())) is None
    readers = {name: spec.metric_reader("layer_metrics", name) for name in BY_SCOPE}
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
    read = {name: readers[name](context) for name in BY_SCOPE}
    for name, scope in BY_SCOPE.items():
        carried = sum(scope in stack for stack in op_names.values())
        assert carried > 5, scope  # forward, recomputed forward and backward
        assert read[name] == pytest.approx(carried / 2)
    # a scope inside another is a part of it
    assert read["ssd_scan_ms"] < read["mamba_mixer_ms"]
    assert read["nemotron_shared_expert_ms"] < read["nemotron_moe_ms"]
    # PR 41: every cell, the optimiser and the loss under names of their own
    from step_scope_checks import check_step_scopes

    check_step_scopes(context, op_names, session.trainer)
    context["trainer"] = object()  # a program without ``compiled_step``, as the parent
    del context["_step_op_names"]
    assert all(readers[name](context) is None for name in BY_SCOPE)
    context["trainer"] = session.trainer
    first.loop.state = None
    _, control = session.compare(first, control="fp8")
    correct, compared = check.verdict(control, LIMITS)
    assert correct is False
    assert compared["cell_y_err"]["value"] > LIMITS["cell_y_err"]


def test_the_entry_script_trains_the_tiny_cut(monkeypatch, capsys):
    """``benchmark_nemotron_h_lp.py`` with a real argv: ``build_config``,
    ``build_nemotron_h``, ``make_trainer`` and ``run_training``, nothing else."""
    script = os.path.join(REPO, "benchmarks", "layer_parallelism",
                          "benchmark_nemotron_h_lp.py")
    argv = ["--model-config", TINY_JSON, "--sequence-length", "80",
            "--batch-size", "2", "--max-steps", "3", "--verbose"]
    monkeypatch.setattr(sys, "argv", [os.path.basename(script)] + argv)
    monkeypatch.setattr(sys, "path", list(sys.path))
    cache_dir = jax.config.jax_compilation_cache_dir
    try:
        runpy.run_path(script, run_name="__main__")
    finally:  # build_config points the persistent cache at the program's own
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    out = capsys.readouterr().out
    assert "remat policy: cell (@80 tokens)" in out
    losses = [float(m) for m in re.findall(r"loss (\d+\.\d+)", out)]
    assert len(losses) == 3 and all(np.isfinite(losses))
    assert "benchmark_nemotron_h_lp: Mean" in out and "seq/s" in out


def test_an_expert_width_of_broken_tiles_is_padded_and_changes_nothing():
    """``_whole_tiles``: 300 columns become 512 (two tiles of 256) with zeros
    that add nothing; value and gradients are those of the unpadded arrays;
    256 and 16 columns pass as they are."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (24, 32))
    w1 = jax.random.normal(keys[1], (2, 32, 300))
    w2 = jax.random.normal(keys[2], (2, 300, 32))
    groups = jnp.asarray([10, 14], jnp.int32)
    padded = sequence._whole_tiles((w1, w2))
    assert padded[0].shape == (2, 32, 512) and padded[1].shape == (2, 512, 32)
    for same in ((w1[..., :256], w2[:, :256]), (w1[..., :16], w2[:, :16])):
        assert sequence._whole_tiles(same) is same

    def out(w1_, w2_, pad):
        experts = sequence._whole_tiles((w1_, w2_)) if pad else (w1_, w2_)
        return jnp.sum(jnp.sin(sequence._grouped_ffn(x, experts, groups)))

    for argnum in (0, 1):
        got = jax.grad(out, argnum)(w1, w2, True)
        want = jax.grad(out, argnum)(w1, w2, False)
        assert got.shape == want.shape and check.relative_l2(got, want) < 1e-6
    assert float(out(w1, w2, True)) == pytest.approx(float(out(w1, w2, False)), rel=1e-5)
