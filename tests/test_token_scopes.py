"""The names the device trace splits a token step by (PR 45): inside the
scopes the mixers had, every line of the four token models falls under one
part (``mpi4dl_part_proj``, ``_conv``, ``_gates_norms``, ``_qk_prep``,
``_attn_core``, ``_router``, ``_dispatch``, ``_expert_products``, ``_block``;
the recurrences under ``ssd_scan`` and ``gated_delta_rule``), in the forward,
the recomputed forward and the backward, the ``custom_vjp`` rules included.
Read off the *lowered* step of a tiny model of each family as its entry point
builds it (``tests/test_step_scopes.py``'s way: what the program controls),
and, for the fused kernels, off the traced step at shapes they take (their
calls are made only for a TPU: the gates are steered here, nothing runs).
``chipbench/harness/token_parts.py`` is the reader; its ``scope_of`` says
what a stack means.
"""

import collections
import glob
import os
import re
import warnings

import jax
import jax.numpy as jnp
import pytest

import test_lfm2  # the tiny token models of these four files
import test_nemotron_h
import test_qwen3_next
import test_sdar
from benchmarks import common
from chipbench.harness import counting, spec, step_classes, token_parts

# family: (its tests' tiny model, what the kernels need of it, rows of a
# sequence of L positions)
FAMILIES = {
    "lfm2": (test_lfm2, dict(
        hidden_size=128, num_attention_heads=2, num_key_value_heads=1), 1),
    "qwen3_next": (test_qwen3_next, dict(
        num_hidden_layers=2, full_attention_interval=2, linear_num_key_heads=1,
        linear_num_value_heads=2, linear_key_head_dim=128, linear_value_head_dim=128,
        head_dim=64, num_attention_heads=2, num_key_value_heads=1), 1),
    "nemotron_h": (test_nemotron_h, dict(
        hybrid_override_pattern="M*E", num_hidden_layers=3, mamba_num_heads=2,
        mamba_head_dim=64, n_groups=1, ssm_state_size=128, chunk_size=128, head_dim=64,
        num_attention_heads=2, num_key_value_heads=1), 1),
    "sdar": (test_sdar, dict(
        head_dim=64, num_attention_heads=2, num_key_value_heads=1, num_hidden_layers=1), 2),
}
# the parts each family's layers have, beside those all four have
EVERY = {"proj", "attn_core", "router", "dispatch", "expert_products", "block"}
PARTS = {
    "lfm2": EVERY | {"conv", "qk_prep"},
    "qwen3_next": EVERY | {"conv", "gates_norms", "recurrence", "qk_prep"},
    "nemotron_h": EVERY | {"conv", "gates_norms", "recurrence", "qk_prep"},
    "sdar": EVERY | {"qk_prep"},
}
PROJECTIONS = {"in_proj", "out_proj", "in_proj_qkvz", "q_proj", "k_proj", "v_proj",
               "w1", "w2", "w3", "shared_expert_gate"}
NO_OPS = ("parameter", "constant", "tuple", "get-tuple-element")
NEW = sorted(s for s in token_parts.PART_SCOPES if s.startswith("mpi4dl_part_"))


def _step(family, length, **changed):
    """``(trainer, (state, x, y) as shapes)`` of the family's tiny model in
    bfloat16, built as ``entry_point.build_trainer`` builds a cell's."""
    tests, _, copies = FAMILIES[family]
    config = dict(tests.MODEL, **changed, entry_point={
        "argv": ["--sequence-length", str(length), "--precision", "bf16"]})
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # the float32 twin's plain paths, said aloud
        trainer, _ = getattr(common, family + "_trainer")(config, tests.BATCH)
        rows = copies * length
        state = jax.eval_shape(lambda: trainer.init(
            jax.random.PRNGKey(0), (tests.BATCH, rows), jnp.int32))
    labels = (tests.BATCH, length, 2) if copies == 2 else (tests.BATCH, length)
    return trainer, (state, jax.ShapeDtypeStruct((tests.BATCH, rows), jnp.int32),
                     jax.ShapeDtypeStruct(labels, jnp.int32))


def _operators(trainer, arguments):
    """``[(Instruction, name stack)]`` of the lowered step, a call site's
    stack in front of its callee's (``tests/test_step_scopes.py``)."""
    from jax._src.lib import xla_client

    options = xla_client._xla.HloPrintOptions()
    options.print_metadata = True
    text = trainer._jit_step.lower(*arguments).compiler_ir(
        dialect="hlo").as_hlo_module().to_string(options)
    computations = step_classes.parse(text)
    entry = re.search(r"^ENTRY %?([\w.\-]+)", text, re.M).group(1)

    def walk(name, prefix):
        for ins in computations[name]:
            stack = "/".join(part for part in (prefix, ins.op_name) if part)
            if ins.opcode == "call":
                yield from walk(ins.calls, stack)
            else:
                yield ins, stack

    return list(walk(entry, ""))


def _part_words(stack):
    return [w for w in token_parts._WORD.findall(stack)
            if token_parts.PART_SCOPES.get(w) not in (None, "optimizer", "loss")]


def _pass(stack):
    if "rematted_computation" in stack:
        return "recomputed"
    return "backward" if "transpose(" in stack else "forward"


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def step(request):
    family = request.param
    trainer, arguments = _step(family, FAMILIES[family][0].LENGTH)
    return family, trainer, _operators(trainer, arguments)


def test_every_op_of_a_layer_carries_one_part_and_no_part_lies_outside_a_cell(step):
    family, trainer, operators = step
    head = f"{len(trainer.cells) - 1:02d}"
    seen = collections.defaultdict(set)
    for ins, stack in operators:
        cell, mixer, part = token_parts.scope_of(stack)
        words = _part_words(stack)
        if words:
            assert cell is not None and cell != head, (ins.name, stack)
        if ins.opcode in NO_OPS or cell is None or cell == head:
            continue
        assert part is not None, (ins.name, stack)
        # one part; the grouped products' alone lies inside the dispatch that
        # calls them, and the innermost is the op's
        nested = ["mpi4dl_part_dispatch", "mpi4dl_part_expert_products"]
        assert len(set(words)) == 1 or (
            sorted(set(words)) == nested and words[-1] == nested[1]), (ins.name, stack)
        if mixer is None:  # around the mixers: the models' files, a dense feed-forward
            assert part in ("block", "proj"), (ins.name, stack)
        seen[part].add(_pass(stack))
    assert set(seen) == PARTS[family], sorted(seen)
    for part, passes in seen.items():
        assert passes == {"forward", "recomputed", "backward"}, (part, passes)


def test_products_sorts_and_loops_fall_in_their_mixers_parts(step):
    family, trainer, operators = step
    allowed = {
        "lfm2_shortconv": {"dot": {"proj"}},
        "lfm2_attention": {"dot": {"proj", "attn_core"}},
        "blockdiff_attention": {"dot": {"proj", "attn_core"}},
        "gated_delta": {"dot": {"proj", "recurrence"}, "while": {"recurrence"}},
        "mamba2": {"dot": {"proj", "recurrence"}, "while": {"recurrence"}},
        "lfm2_moe": {"dot": {"router", "expert_products"}, "sort": {"dispatch"},
                     "while": {"dispatch"}, "conditional": {"dispatch"}},
        "shared_expert": {"dot": {"proj"}},
        None: {"dot": {"proj"}},  # LFM2's dense layer
    }
    seen = collections.Counter()
    for ins, stack in operators:
        cell, mixer, part = token_parts.scope_of(stack)
        if cell is None or cell == f"{len(trainer.cells) - 1:02d}":
            continue
        if ins.opcode in ("dot", "convolution", "sort", "while", "conditional"):
            assert part in allowed[mixer].get(ins.opcode, ()), (ins.name, mixer, stack)
            seen[mixer, ins.opcode, part, _pass(stack)] += 1
        if ins.opcode == "dot" and PROJECTIONS & set(stack.split("/")):
            assert part == "proj", (ins.name, stack)
    for (mixer, opcode, part, _), n in list(seen.items()):
        if opcode == "dot":
            # in all three passes, but where a product's result is kept by
            # name (``sequence._kept``, PR 46): the replay of a cell holds no
            # router's product and no grouped product, and of a dense
            # feed-forward (a shared expert, LFM2's dense layer) only what a
            # gate on its result has it make again
            passes = {which for which in ("forward", "recomputed", "backward")
                      if seen[mixer, "dot", part, which]}
            assert {"forward", "backward"} <= passes, (mixer, part, passes)
            if mixer not in ("shared_expert", None):
                assert ("recomputed" in passes) == (mixer != "lfm2_moe"), (mixer, part)
    assert any(k[:3] == ("lfm2_moe", "dot", "expert_products") for k in seen)
    assert any(k[:3] == ("lfm2_moe", "sort", "dispatch") for k in seen)
    for mixer in ("gated_delta", "mamba2"):
        if any(k[0] == mixer for k in seen):
            assert any(k[:3] == (mixer, "while", "recurrence") for k in seen), mixer


def test_the_backward_rules_the_file_writes_carry_their_part(step):
    family, trainer, operators = step
    # ``_ranges_bwd``, ``_token_sums_bwd`` and ``_token_rows``' rule run under
    # ``jit(_two_ranges)``'s transpose: bookkeeping but the products
    ranges = [(ins, token_parts.scope_of(stack)[2]) for ins, stack in operators
              if "jit(_two_ranges)" in stack and _pass(stack) == "backward"
              and ins.opcode not in NO_OPS]
    assert ranges and {part for _, part in ranges} == {"dispatch", "expert_products"}
    assert any(ins.opcode == "conditional" and part == "dispatch" for ins, part in ranges)
    assert all(part == "expert_products" for ins, part in ranges if ins.opcode == "dot")
    assert any(ins.opcode == "gather" and part == "dispatch" for ins, part in ranges)
    # ``_attention_bwd``: the weight-side products exist only there
    theirs = [token_parts.scope_of(stack)[2] for ins, stack in operators
              if ins.opcode == "dot" and "bkgqn,bqkgd->bnkd" in stack]
    assert theirs and set(theirs) == {"attn_core"}
    if family == "qwen3_next":  # ``_inverse_bwd``: float32 products at "highest"
        exact = [token_parts.scope_of(stack) for ins, stack in operators
                 if ins.opcode == "dot" and "gated_delta_rule" in stack.split("/")
                 and _pass(stack) == "backward"]
        assert exact and {found[1:] for found in exact} == {("gated_delta", "recurrence")}


def test_no_new_name_holds_or_is_held_by_a_name_an_accepted_reader_searches_for(step):
    family, trainer, operators = step
    accepted = set(step_classes.CLASS_SCOPES) | {"mpi4dl_cell"}
    for path in glob.glob(os.path.join(spec.BENCH_DIR, "layer_metrics", "*.py")):
        with open(path) as f:
            source = f.read()
        for found in re.findall(r"^(?:SCOPES|KERNEL)\s*=\s*(.+)$", source, re.M):
            accepted.update(re.findall(r'"([^"]+)"', found))
    assert {"mamba2", "gated_delta_rule", "sdar_moe", "ragged-dot",
            "mpi4dl_blockdiff_attention"} <= accepted
    assert len(NEW) == 9
    for new in NEW:
        assert not [old for old in accepted if new in old or old in new], new
        assert not [other for other in NEW if other != new and new in other], new
    # and no other word of a stack holds a new name
    words = {w for _, stack in operators for w in token_parts._WORD.findall(stack)}
    assert not [(new, w) for new in NEW for w in words - set(NEW) if new in w]


def _equations(jaxpr, prefix=""):
    """``(equation, name stack)`` over a jaxpr and those in its equations'
    parameters, an outer equation's stack in front of its body's."""
    for eqn in jaxpr.eqns:
        stack = "/".join(p for p in (prefix, str(eqn.source_info.name_stack)) if p)
        yield eqn, stack
        for value in eqn.params.values():
            for inner in counting._subjaxprs(value):
                yield from _equations(inner, stack)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_fused_kernels_calls_carry_their_part(family, monkeypatch):
    """At shapes the kernels take, with the gates told the backend is a TPU
    (traced, never lowered: the CPU has no such calls): every ``pallas_call``
    of the step, forward and backward, lies in a layer's cell under the
    attention's core, a recurrence's scope or, the causal convolution's
    (PR 47: its name has no rule of its own in ``token_parts.NAMED_PARTS``,
    the scope it stands in says ``conv``), the convolution's part."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    trainer, arguments = _step(family, 128, **FAMILIES[family][1])
    calls = collections.Counter()
    for eqn, stack in _equations(jax.make_jaxpr(trainer._train_step)(*arguments).jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        name = stack.rsplit("/", 1)[-1]
        cell, mixer, part = token_parts.scope_of(stack)
        assert cell is not None and len(set(_part_words(stack))) == 1, stack
        assert token_parts.scope_of("", name)[2] in (part, None), stack  # as its own name says
        assert (mixer, part) in (
            ("lfm2_attention", "attn_core"), ("blockdiff_attention", "attn_core"),
            ("gated_delta", "recurrence"), ("mamba2", "recurrence"),
            ("gated_delta", "conv"), ("mamba2", "conv")), stack
        calls[mixer, part, _pass(stack)] += 1
    attention = ("lfm2_attention", "attn_core")
    mixers = {"lfm2": [attention], "sdar": [("blockdiff_attention", "attn_core")],
              "qwen3_next": [("gated_delta", "recurrence"), ("gated_delta", "conv"), attention],
              "nemotron_h": [("mamba2", "recurrence"), ("mamba2", "conv"), attention]}
    # "cell" remat keeps what a kernel's forward wrote: no second forward
    layers = sum(kind == "full_attention" for kind in trainer.cells[1].config.layer_types) \
        if family == "lfm2" else 1
    # ... and the convolution's kernels run once each for q, k, v / x, B, C
    assert calls == {(*mixer, which): layers * (3 if mixer[1] == "conv" else 1)
                     for mixer in mixers[family] for which in ("forward", "backward")}, calls


def _attention(hidden, heads, kv_heads, head_dim, gate=False):
    return [(hidden, heads * head_dim * (2 if gate else 1)), (hidden, kv_heads * head_dim),
            (hidden, kv_heads * head_dim), (heads * head_dim, hidden)]


def _feed_forward(hidden, width, arrays):
    return [(hidden, width)] * (arrays - 1) + [(width, hidden)]


def _hand_projections(model):
    """``(in, out)`` of every dense product a row passes through under
    ``mpi4dl_part_proj``, written down from the four families' published
    layouts: what ``tok_proj_roofline``'s walk of the program's forward has
    to find (a mixer's projections in and out, a dense or shared
    feed-forward with the shared expert's gate; not the router, the experts,
    the head or the embedding)."""
    kind, hidden = model["model_type"], int(model["hidden_size"])
    heads = int(model.get("num_attention_heads", 0))
    kv_heads = int(model.get("num_key_value_heads", 0))
    if kind == "lfm2_moe":
        dense = _feed_forward(hidden, int(model["intermediate_size"]), 3)
        return [pair for i, operator in enumerate(model["layer_types"]) for pair in (
            ([(hidden, 3 * hidden), (hidden, hidden)] if operator == "conv"
             else _attention(hidden, heads, kv_heads, hidden // heads))
            + (dense if i < int(model["num_dense_layers"]) else []))]
    if kind == "qwen3_next":
        value_heads = int(model["linear_num_value_heads"])
        keys = int(model["linear_num_key_heads"]) * int(model["linear_key_head_dim"])
        values = value_heads * int(model["linear_value_head_dim"])
        shared = _feed_forward(
            hidden, int(model["shared_expert_intermediate_size"]), 3) + [(hidden, 1)]
        linear = [(hidden, 2 * keys + 2 * values), (hidden, 2 * value_heads), (values, hidden)]
        full = _attention(hidden, heads, kv_heads, int(model["head_dim"]), gate=True)
        every = int(model["full_attention_interval"])
        return [pair for i in range(int(model["num_hidden_layers"]))
                for pair in (linear if (i + 1) % every else full) + shared]
    if kind == "nemotron_h":
        mamba_heads = int(model["mamba_num_heads"])
        inner = mamba_heads * int(model["mamba_head_dim"])
        mixed = inner + 2 * int(model["n_groups"]) * int(model["ssm_state_size"])
        layers = {
            "M": [(hidden, inner + mixed + mamba_heads), (inner, hidden)],
            "E": _feed_forward(hidden, int(model["moe_shared_expert_intermediate_size"]), 2),
            "*": _attention(hidden, heads, kv_heads, int(model["head_dim"])),
        }
        return [pair for letter in model["hybrid_override_pattern"] for pair in layers[letter]]
    assert kind == "sdar_moe", kind
    return _attention(hidden, heads, kv_heads, int(model["head_dim"])) * int(
        model["num_hidden_layers"])


CONFIGS = ["lfm2_8b_a1b_share4", "qwen3_next_80b_a3b_share16",
           "nemotron_twotower_30b_a3b_share16", "sdar_30b_a3b_share8"]


@pytest.mark.parametrize("config", CONFIGS)
def test_the_readers_split_the_compiled_step_of_the_tiny_cell(config, tmp_path):
    """The benchmark's own path at a tiny size on the CPU: the cell's
    session, the step its stream's labels compile (block diffusion's carry a
    weight a position: the reader must ask with them, or it reads another
    step), one event of 1 ms for every instruction that runs as an op, two
    steps in the window. Every ``tok_*`` metric reads; the parts add up to the
    busy time; the one op no part reaches is the step counter's add."""
    from chipbench.harness import xtrace
    from chipbench.harness.session import Session
    from chipbench.tests import tiny

    cell = tiny.tiny_cell(tmp_path, config)
    session = Session(cell)
    session.first_steps(2147483659 + 45, session.check_steps)
    context = {"trainer": session.trainer, "session": session, "cell": cell,
               "peaks": tiny.PEAKS, "reduced": None}
    names = [m["name"] for m in spec.benchmark()["per_layer"] if m["name"].startswith("tok_")]
    assert len(names) == 13
    assert all(spec.metric_reader("layer_metrics", n)(context) is None for n in names)
    lowerings = []
    lower = session.trainer._jit_step.lower
    session.trainer._jit_step = type("Counting", (), {"lower": staticmethod(
        lambda *a: lowerings.append(a) or lower(*a))})()
    text = token_parts.step_text(context)
    # the step the loop ran, handed back (one lowering at most: the loop's own
    # call compiled through jit, not through ``compiled_step``)
    assert len(lowerings) <= 1 and token_parts.has_parts(text)
    computations = step_classes.parse(text)
    fused = {ins.calls for body in computations.values() for ins in body
             if ins.opcode == "fusion"}
    regions = set(re.findall(r"to_apply=%?([\w.\-]+)", text))
    ran = [ins for name, body in computations.items() if name not in fused | regions
           for ins in body if ins.opcode not in NO_OPS + ("bitcast",)]
    events = [xtrace.Event(f"%{ins.name} = f32[8]{{0}} {ins.opcode}(f32[8]{{0}} %p)",
                           i * 1e6, 1e6, {}) for i, ins in enumerate(ran)]
    context = dict(context, reduced=xtrace.Reduced(
        steps=2, window_s=len(events) / 1e3, busy_s=len(events) / 1e3,
        chips=[{"window": (0, len(events) * 1e6), "ops": events}],
        device_ops=[], idle_gaps=[]))
    context.pop(token_parts._SPLIT)
    read = {n: spec.metric_reader("layer_metrics", n)(context) for n in names}
    assert all(isinstance(v, float) for v in read.values()), read
    split = token_parts.split(context)
    assert sum(split.values()) == pytest.approx(len(events) / 2)
    table = token_parts.classify(text)
    lost = [ins for ins in ran if table[ins.name].part == token_parts.UNSCOPED]
    assert [ins.op_name for ins in lost] == ["jit(_train_step)/add"]
    assert read["tok_parts_unscoped_ms"] == 0.5
    for name in ("tok_proj_ms", "tok_attn_core_ms", "tok_router_ms", "tok_dispatch_ms",
                 "tok_expert_products_ms", "tok_block_ms"):
        assert read[name] > 0, name
    assert 0 < read["tok_proj_roofline"] < 100
    # the least work the share is taken against, read off the program's forward
    # (no family named there), is the published layout's hand count
    rows = session.x_shape[0] * session.x_shape[1]
    proj = spec.load_module(
        spec.BENCH_DIR + "/layer_metrics/tok_proj_roofline.py", "tok_proj_roofline")
    assert proj.least_flops_per_step(context) == 3 * 2.0 * rows * sum(
        a * b for a, b in _hand_projections(cell.model))
    assert 0 < read["tok_expert_products_roofline"] < 100
    assert (read["tok_conv_ms"] > 0) == (config != "sdar_30b_a3b_share8")
