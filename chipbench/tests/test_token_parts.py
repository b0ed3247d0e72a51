"""The split of a token cell's traced step by model cell, mixer and part
(``harness/token_parts.py``) on a hand-built compiled text and trace whose
answers are known: two whole steps of 100 ms on one chip. And the least work
of the two shares of the peak against a hand count at one small shape."""

import types

import pytest

from chipbench.harness import spec, token_parts, xtrace
from chipbench.harness.xtrace import Event
from chipbench.tools import token_table

MS = 1_000_000  # ns
STEPS = 2
_FWD = "jit(_train_step)/jvp()/mpi4dl_cell01/Layer"
_BWD = "jit(_train_step)/transpose(jvp())/checkpoint/mpi4dl_cell01/Layer"
_MOE = "jit(_train_step)/jvp()/mpi4dl_cell02/Layer/mixer/lfm2_moe"
_HEAD = "jit(_train_step)/jvp()/mpi4dl_cell03/Head"


def _meta(stack):
    return f'metadata={{op_name="{stack}" stack_frame_id=7}}'


# What the chip's compiler leaves of a token step, in small. Cell 01 is a
# Mamba-2 layer: a parameter's cast and two copies in a chain feed the input
# projection, which is fused with the gate that follows it; a fusion of gates
# and norms that holds one residual add; the scan as a while whose body holds a
# fusion of its own and a copy that names nothing; the scan's kernel, which
# lost its stack. Cell 02 an expert layer: the router, a sort, a grouped
# product the compiler renamed and whose stack it cut at the jit, the copy of
# its expert array (it kept the stack of the cast it was cut from), a
# conditional whose branch holds a stackless add. Cell 03 the head; then the
# loss, the optimiser, and the step counter's copy, which nobody claims.
TEXT = f"""HloModule jit__train_step, entry_computation_layout={{()->()}}

%add_region (a: f32[], b: f32[]) -> f32[] {{
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.0 = f32[] add(%a, %b)
}}

%fused_in_proj (p0: bf16[16,8], p1: bf16[8,32]) -> bf16[16,32] {{
  %p0 = bf16[16,8]{{1,0}} parameter(0)
  %p1 = bf16[8,32]{{1,0}} parameter(1)
  %dot.1 = bf16[16,32]{{1,0}} dot(%p0, %p1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, {_meta(_FWD + "/mixer/mamba2/mpi4dl_part_proj/dot_general")}
  %logistic.1 = bf16[16,32]{{1,0}} logistic(%dot.1), {_meta(_FWD + "/mixer/mamba2/mpi4dl_part_gates_norms/logistic")}
  ROOT %multiply.1 = bf16[16,32]{{1,0}} multiply(%dot.1, %logistic.1), {_meta(_FWD + "/mixer/mamba2/mpi4dl_part_gates_norms/mul")}
}}

%fused_norm (p0.1: bf16[16,32], p1.1: bf16[16,32]) -> bf16[16,32] {{
  %p0.1 = bf16[16,32]{{1,0}} parameter(0)
  %p1.1 = bf16[16,32]{{1,0}} parameter(1)
  %multiply.2 = bf16[16,32]{{1,0}} multiply(%p0.1, %p0.1), {_meta(_BWD + "/mixer/mamba2/mpi4dl_part_gates_norms/mul")}
  %rsqrt.2 = bf16[16,32]{{1,0}} rsqrt(%multiply.2), {_meta(_BWD + "/mixer/mamba2/mpi4dl_part_gates_norms/rsqrt")}
  ROOT %add.2 = bf16[16,32]{{1,0}} add(%rsqrt.2, %p1.1), {_meta(_BWD + "/mpi4dl_part_block/add")}
}}

%fused_scan (p0.3: bf16[16,32]) -> bf16[16,32] {{
  %p0.3 = bf16[16,32]{{1,0}} parameter(0)
  ROOT %exponential.4 = bf16[16,32]{{1,0}} exponential(%p0.3), {_meta(_FWD + "/mixer/mamba2/ssd_scan/while/body/exp")}
}}

%fused_copy (p0.4: bf16[16,8]) -> bf16[16,8] {{
  %p0.4 = bf16[16,8]{{1,0}} parameter(0)
  %copy.40 = bf16[16,8]{{0,1}} copy(%p0.4)
  ROOT %bitcast.40 = bf16[16,8]{{1,0}} bitcast(%copy.40)
}}

%fused_head_wgrad (p0.5: f32[16,8], p1.5: f32[16,32], p2.5: f32[8,32]) -> f32[8,32] {{
  %p0.5 = f32[16,8]{{1,0}} parameter(0)
  %p1.5 = f32[16,32]{{1,0}} parameter(1)
  %p2.5 = f32[8,32]{{1,0}} parameter(2)
  %dot.50 = f32[8,32]{{1,0}} dot(%p0.5, %p1.5), lhs_contracting_dims={{0}}, rhs_contracting_dims={{0}}, {_meta("jit(_train_step)/transpose(jvp())/checkpoint/mpi4dl_cell03/Head/lm_head/dot_general")}
  %multiply.50 = f32[8,32]{{1,0}} multiply(%dot.50, %p2.5), {_meta("jit(_train_step)/mpi4dl_optimizer/mul")}
  ROOT %add.50 = f32[8,32]{{1,0}} add(%multiply.50, %p2.5), {_meta("jit(_train_step)/mpi4dl_optimizer/add")}
}}

%fused_opt (p0.2: f32[8,32], p1.2: f32[8,32]) -> f32[8,32] {{
  %p0.2 = f32[8,32]{{1,0}} parameter(0)
  %p1.2 = f32[8,32]{{1,0}} parameter(1)
  ROOT %add.3 = f32[8,32]{{1,0}} add(%p0.2, %p1.2), {_meta("jit(_train_step)/mpi4dl_optimizer/add")}
}}

%body (carry: (bf16[16,32])) -> (bf16[16,32]) {{
  %carry = (bf16[16,32]{{1,0}}) parameter(0)
  %get-tuple-element.9 = bf16[16,32]{{1,0}} get-tuple-element(%carry), index=0
  %fusion.9 = bf16[16,32]{{1,0}} fusion(%get-tuple-element.9), kind=kLoop, calls=%fused_scan, {_meta(_FWD + "/mixer/mamba2/ssd_scan/while/body/exp")}
  ROOT %tuple.9 = (bf16[16,32]{{1,0}}) tuple(%fusion.9)
}}

%cond (carry.1: (bf16[16,32])) -> pred[] {{
  %carry.1 = (bf16[16,32]{{1,0}}) parameter(0)
  ROOT %constant.8 = pred[] constant(true)
}}

%lone_body (carry.2: (s32[4])) -> (s32[4]) {{
  %carry.2 = (s32[4]{{0}}) parameter(0)
  %get-tuple-element.10 = s32[4]{{0}} get-tuple-element(%carry.2), index=0
  %copy.10 = s32[4]{{0}} copy(%get-tuple-element.10)
  ROOT %tuple.10 = (s32[4]{{0}}) tuple(%copy.10)
}}

%taken (arg: (f32[16,8])) -> (f32[16,8]) {{
  %arg = (f32[16,8]{{1,0}}) parameter(0)
  %get-tuple-element.11 = f32[16,8]{{1,0}} get-tuple-element(%arg), index=0
  %add.11 = f32[16,8]{{1,0}} add(%get-tuple-element.11, %get-tuple-element.11)
  ROOT %tuple.11 = (f32[16,8]{{1,0}}) tuple(%add.11)
}}

%not_taken (arg.1: (f32[16,8])) -> (f32[16,8]) {{
  ROOT %arg.1 = (f32[16,8]{{1,0}}) parameter(0)
}}

ENTRY %main.1 (param.0: s32[16], param.1: f32[8,32], param.2: f32[8,32], param.3: s32[]) -> f32[8,32] {{
  %param.0 = s32[16]{{0}} parameter(0), metadata={{op_name="x"}}
  %param.1 = f32[8,32]{{1,0}} parameter(1), metadata={{op_name="state.params[1]"}}
  %param.2 = f32[8,32]{{1,0}} parameter(2), metadata={{op_name="state.opt_state[0].trace[1]"}}
  %param.3 = s32[]{{:T(128)}} parameter(3), metadata={{op_name="state.step"}}
  %gather.1 = bf16[16,8]{{1,0}} gather(%param.1, %param.0), offset_dims={{1}}, {_meta("jit(_train_step)/jvp()/mpi4dl_cell00/Embed/mpi4dl_part_block/embed_tokens/gather")}
  %convert.1 = bf16[8,32]{{1,0}} convert(%param.1)
  %copy.1 = bf16[8,32]{{0,1}} copy(%convert.1)
  %copy.2 = bf16[8,32]{{1,0:T(8,128)(2,1)}} copy(%copy.1)
  %fusion.40 = bf16[16,8]{{1,0}} fusion(%gather.1), kind=kLoop, calls=%fused_copy
  %fusion.1 = bf16[16,32]{{1,0}} fusion(%fusion.40, %copy.2), kind=kOutput, calls=%fused_in_proj, {_meta(_FWD + "/mixer/mamba2/mpi4dl_part_gates_norms/mul")}
  %tuple.1 = (bf16[16,32]{{1,0}}) tuple(%fusion.1)
  %while.1 = (bf16[16,32]{{1,0}}) while(%tuple.1), condition=%cond, body=%body, {_meta(_FWD + "/mixer/mamba2/ssd_scan/while")}
  %get-tuple-element.1 = bf16[16,32]{{1,0}} get-tuple-element(%while.1), index=0
  %mpi4dl_ssd_scan_fwd.1 = bf16[16,32]{{1,0}} custom-call(%get-tuple-element.1), custom_call_target="tpu_custom_call"
  %fusion.2 = bf16[16,32]{{1,0}} fusion(%mpi4dl_ssd_scan_fwd.1, %fusion.1), kind=kLoop, calls=%fused_norm, {_meta(_BWD + "/mpi4dl_part_block/add")}
  %dot.5 = f32[16,8]{{1,0}} dot(%fusion.2, %param.1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{1}}, {_meta(_MOE + "/mpi4dl_part_router/dot_general")}
  %iota.5 = s32[4]{{0}} iota(), iota_dimension=0, {_meta(_MOE + "/mpi4dl_part_dispatch/iota")}
  %tuple.5 = (s32[4]{{0}}) tuple(%iota.5)
  %while.5 = (s32[4]{{0}}) while(%tuple.5), condition=%cond, body=%lone_body, {_meta(_MOE + "/mpi4dl_part_dispatch/while")}
  %get-tuple-element.5 = s32[4]{{0}} get-tuple-element(%while.5), index=0
  %sort.5 = s32[4]{{0}} sort(%get-tuple-element.5), dimensions={{0}}, to_apply=%add_region, {_meta(_MOE + "/mpi4dl_part_dispatch/sort")}
  %convert.6 = bf16[8,32]{{1,0}} convert(%param.2), {_meta(_MOE + "/mpi4dl_part_expert_products/convert_element_type")}
  %copy.6 = bf16[8,32]{{0,1}} copy(%convert.6), {_meta(_MOE + "/mpi4dl_part_expert_products/convert_element_type")}
  %ragged-dot-none.6 = f32[16,8]{{1,0}} custom-call(%sort.5, %fusion.2, %copy.6), custom_call_target="tpu_custom_call", {_meta(_MOE + "/mpi4dl_part_dispatch/jit(_two_ranges)/ragged-dot-none")}
  %tuple.6 = (f32[16,8]{{1,0}}) tuple(%ragged-dot-none.6)
  %conditional.6 = (f32[16,8]{{1,0}}) conditional(%constant.80, %tuple.6, %tuple.6), branch_computations={{%taken, %not_taken}}, {_meta(_MOE + "/mpi4dl_part_dispatch/jit(_two_ranges)/cond")}
  %get-tuple-element.6 = f32[16,8]{{1,0}} get-tuple-element(%conditional.6), index=0
  %dot.7 = f32[16,32]{{1,0}} dot(%get-tuple-element.6, %param.1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, {_meta(_HEAD + "/lm_head/dot_general")}
  %reduce.7 = f32[] reduce(%dot.7, %constant.81), dimensions={{0,1}}, to_apply=%add_region, {_meta("jit(_train_step)/jvp()/mpi4dl_loss/reduce_sum")}
  %fusion.50 = f32[8,32]{{1,0}} fusion(%get-tuple-element.6, %dot.7, %param.2), kind=kOutput, calls=%fused_head_wgrad, {_meta("jit(_train_step)/mpi4dl_optimizer/add")}
  %copy.8 = s32[]{{:T(128)}} copy(%param.3)
  ROOT %fusion.3 = f32[8,32]{{1,0}} fusion(%param.1, %param.2), kind=kLoop, calls=%fused_opt, {_meta("jit(_train_step)/mpi4dl_optimizer/add")}
}}
"""

# instruction: (start ms, length ms) inside a step of 100 ms; each while
# spans its body's ops (the scan's two trips); idle 95-100
TIMES = {
    "gather.1": (0, 2), "convert.1": (2, 1), "copy.1": (3, 2), "copy.2": (5, 3),
    "fusion.40": (8, 2), "fusion.1": (10, 12), "while.1": (22, 16), "fusion.9": (23, 6),
    "mpi4dl_ssd_scan_fwd.1": (38, 7), "fusion.2": (45, 5), "dot.5": (50, 4),
    "iota.5": (54, 1), "while.5": (55, 4), "copy.10": (56, 2), "sort.5": (59, 3),
    "convert.6": (62, 2), "copy.6": (64, 3), "ragged-dot-none.6": (67, 9),
    "conditional.6": (76, 3), "add.11": (77, 1), "dot.7": (79, 6), "reduce.7": (85, 2),
    "copy.8": (87, 1), "fusion.3": (88, 7),
}
BUSY = 95.0


def _events():
    out = []
    for k in range(STEPS + 1):
        t = k * 100 * MS
        for name, (start, length) in TIMES.items():
            opcode = name.rsplit(".", 1)[0]
            out.append(Event(f"%{name} = f32[16,8]{{1,0}} {opcode}(f32[8]{{0}} %x)",
                             t + start * MS, length * MS, {}))
        out.append(Event("%fusion.9 = f32[16,8]{1,0} fusion(f32[8]{0} %x)",
                         t + 30 * MS, 6 * MS, {}))  # the body's second trip
    return out


def _reduced():
    return xtrace.Reduced(
        steps=STEPS, window_s=STEPS * 100e-3, busy_s=STEPS * BUSY * 1e-3,
        chips=[{"window": (0, STEPS * 100 * MS), "ops": _events()}],
        device_ops=[], idle_gaps=[])


MODEL = {"hidden_size": 8, "moe_intermediate_size": 6, "mlp_hidden_act": "relu2"}
ROWS, HIDDEN = 32, 8
# forward FLOPs under mpi4dl_part_proj of _Layer below, by hand: 2 x rows x in x
# out of the product into the mixer (8 x 50), the one out of it inside a jit
# (8 x 8), and a scanned one's three trips (8 x 8)
PROJ_FORWARD = 2 * ROWS * HIDDEN * (50 + 8 + 3 * 8)


class _Layer:
    """A cell as the trainer holds one (``apply(params, x)``), whose products
    stand under the names a token model gives them."""

    def apply(self, p, h):
        import jax

        with jax.named_scope("mpi4dl_cell01"), jax.named_scope("mamba2"):
            with jax.named_scope("mpi4dl_part_proj"):
                wide = h @ p["in_proj"]
                h = jax.jit(lambda a, w: a @ w)(wide[..., :HIDDEN], p["out_proj"])
                h, _ = jax.lax.scan(lambda c, w: (c @ w, None), h, p["stacked"])
                # the innermost part wins: this one is the router's
                with jax.named_scope("mpi4dl_part_router"):
                    h = h @ p["out_proj"]
            with jax.named_scope("mpi4dl_part_router"):
                scores = h @ p["router"]
            with jax.named_scope("mpi4dl_part_gates_norms"):
                return h * scores.sum(-1, keepdims=True)


def _params(seed):
    import jax.numpy as jnp

    shapes = {"in_proj": (HIDDEN, 50), "out_proj": (HIDDEN, HIDDEN),
              "stacked": (3, HIDDEN, HIDDEN), "router": (HIDDEN, 4)}
    return [{k: jnp.zeros(v, jnp.float32) for k, v in shapes.items()}]


def _session():
    import jax.numpy as jnp

    return types.SimpleNamespace(
        x_shape=(2, 16, HIDDEN), x_dtype=jnp.float32, make_params=_params)


@pytest.fixture()
def context():
    trainer = types.SimpleNamespace(last_metrics={"moe_pairs": 20.0}, cells=[_Layer()])
    return {"reduced": _reduced(), "trainer": trainer, "_token_parts_text": TEXT,
            "session": _session(), "cell": types.SimpleNamespace(model=MODEL),
            "peaks": {"bf16_flops_per_s": 1e9}}


def test_every_instruction_lands_in_its_cell_mixer_and_part():
    table = token_parts.classify(TEXT)
    want = {
        "gather.1": ("00", None, "block", False),
        # a chain of copies, a cast and a fusion of one copy: the projection's
        "convert.1": ("01", "mamba2", "proj", False),
        "copy.1": ("01", "mamba2", "proj", True),
        "copy.2": ("01", "mamba2", "proj", True),
        "fusion.40": ("01", "mamba2", "proj", True),
        # its dot's part, not its epilogue's nor the name it happens to carry
        "fusion.1": ("01", "mamba2", "proj", False),
        "while.1": ("01", "mamba2", "recurrence", False),
        "fusion.9": ("01", "mamba2", "recurrence", False),
        # by its own name; the cell and the mixer from its consumer
        "mpi4dl_ssd_scan_fwd.1": ("01", "mamba2", "recurrence", False),
        "fusion.2": ("01", "mamba2", "gates_norms", False),  # two of its three
        "dot.5": ("02", "lfm2_moe", "router", False),
        "while.5": ("02", "lfm2_moe", "dispatch", False),
        "copy.10": ("02", "lfm2_moe", "dispatch", True),      # its loop's
        "sort.5": ("02", "lfm2_moe", "dispatch", False),
        "copy.6": ("02", "lfm2_moe", "expert_products", True),  # a copy is layout
        # renamed, its stack cut where it still said dispatch
        "ragged-dot-none.6": ("02", "lfm2_moe", "expert_products", False),
        "conditional.6": ("02", "lfm2_moe", "dispatch", False),
        "add.11": ("02", "lfm2_moe", "dispatch", False),       # its conditional's
        "dot.7": ("03", None, "head", False),
        # the head's weight gradient with its parameter's update fused in: the dot's
        "fusion.50": ("03", None, "head", False),
        "reduce.7": (None, None, "loss", False),
        "fusion.3": (None, None, "optimizer", False),
        "copy.8": (None, None, "unscoped", True),
    }
    assert {k: tuple(table[k]) for k in want} == want
    assert "dot.1" not in table and "add.3" not in table  # fused: their fusion's


def test_scopes_are_whole_words_and_the_innermost_part_wins():
    of = token_parts.scope_of
    assert of("a/gated_delta/mpi4dl_part_conv/mul") == (None, "gated_delta", "conv")
    assert of("a/mpi4dl_cell04/gated_delta/gated_delta_rule/while") == (
        "04", "gated_delta", "recurrence")
    assert of("a/lfm2_moe/mpi4dl_part_dispatch/jit(_two_ranges)/"
              "jvp(mpi4dl_part_expert_products)/dot_general")[2] == "expert_products"
    assert of("a/lfm2_moe/shared_expert/mpi4dl_part_proj/w1")[1] == "shared_expert"
    assert of("a/mpi4dl_cells03to05/x")[0] == "03to05"
    assert of("jit(f)/mpi4dl_part_projection/x") == (None, None, None)
    assert of("", "mpi4dl_blockdiff_attention_bwd.3")[2] == "attn_core"
    assert of("a/mpi4dl_part_dispatch/x", "ragged-dot-transpose.4")[2] == "expert_products"


def test_parts_and_unscoped_add_up_to_the_busy_time_and_a_while_counts_once(context):
    ms = token_parts.split(context)
    by_part = {}
    for found, v in ms.items():
        by_part[found.part] = by_part.get(found.part, 0.0) + v
    assert by_part == pytest.approx({
        "block": 2.0, "proj": 1 + 2 + 3 + 2 + 12,
        # the loop's 16 ms once: 12 in its body's two trips, 4 its own
        "recurrence": 16 + 7, "gates_norms": 5.0, "router": 4.0,
        "dispatch": 1 + 4 + 3 + 3, "expert_products": 2 + 3 + 9, "head": 6.0,
        "loss": 2.0, "optimizer": 7.0, "unscoped": 1.0})
    assert sum(ms.values()) == pytest.approx(BUSY)
    chip = context["reduced"].chips[0]
    assert 1e3 * xtrace.union_seconds(xtrace.clip(chip["ops"], *chip["window"])) / STEPS == (
        pytest.approx(BUSY))
    assert token_parts.ms(context, ("recurrence",)) == pytest.approx(23.0)
    assert token_parts.ms(context, ("conv",)) == 0.0  # the scopes are there, no op is
    # the layout turns: the chain into the projection, the loop's copy, the
    # expert array's; the step counter's lies in no cell
    assert token_parts.ms(context, layout_only=True, cells_only=True) == pytest.approx(
        2 + 3 + 2 + 2 + 3)
    assert token_parts.ms(context, layout_only=True) == pytest.approx(13.0)


NAMES = ("tok_proj_ms", "tok_proj_roofline", "tok_conv_ms", "tok_gates_norms_ms",
         "tok_qk_prep_ms", "tok_attn_core_ms", "tok_router_ms", "tok_dispatch_ms",
         "tok_expert_products_ms", "tok_expert_products_roofline", "tok_block_ms",
         "tok_layout_ms", "tok_parts_unscoped_ms")


def test_the_metrics_read_the_split(context):
    read = {name: spec.metric_reader("layer_metrics", name)(context) for name in NAMES}
    assert read == pytest.approx({
        "tok_proj_ms": 20.0, "tok_conv_ms": 0.0, "tok_gates_norms_ms": 5.0,
        "tok_qk_prep_ms": 0.0, "tok_attn_core_ms": 0.0, "tok_router_ms": 4.0,
        "tok_dispatch_ms": 11.0, "tok_expert_products_ms": 14.0, "tok_block_ms": 2.0,
        "tok_layout_ms": 12.0, "tok_parts_unscoped_ms": 1.0,
        # forward and two gradients of the layer's projections over 1 GFLOP/s,
        # against 20 ms
        "tok_proj_roofline": 100 * (3 * PROJ_FORWARD / 1e9) / 20e-3,
        # 20 pairs x 8 x 6 x two arrays x 6 FLOPs, against 14 ms
        "tok_expert_products_roofline": 100 * (6 * 20 * 8 * 6 * 2 / 1e9) / 14e-3})


def test_the_least_work_is_the_hand_count(context):
    import jax

    proj = spec.load_module(
        spec.BENCH_DIR + "/layer_metrics/tok_proj_roofline.py", "tok_proj_roofline")
    assert proj.least_flops_per_step(context) == 3 * PROJ_FORWARD
    layer, (params,) = _Layer(), _params(0)
    jaxpr = jax.make_jaxpr(layer.apply)(params, jax.numpy.zeros((2, 16, HIDDEN))).jaxpr
    assert proj.forward_flops(jaxpr) == PROJ_FORWARD
    # the two products the router's name is innermost on; no other part has one
    assert proj.forward_flops(jaxpr, "router") == 2 * ROWS * HIDDEN * (HIDDEN + 4)
    assert proj.forward_flops(jaxpr, "gates_norms") == 0.0
    experts = spec.load_module(
        spec.BENCH_DIR + "/layer_metrics/tok_expert_products_roofline.py", "tok_experts")
    assert experts.least_flops_per_step(MODEL, 20.0) == 6 * 20 * 8 * 6 * 2
    assert experts.least_flops_per_step(dict(MODEL, mlp_hidden_act="silu"), 20.0) == (
        6 * 20 * 8 * 6 * 3)


def test_a_forward_without_a_projection_reads_no_share_and_names_no_family(context):
    """A model none of whose products stands under ``mpi4dl_part_proj`` reads
    None where the others read their share, and nothing raises; the counting
    asks the program's forward and not the configuration's ``model_type``."""
    reader = spec.metric_reader("layer_metrics", "tok_proj_roofline")
    context["cell"] = types.SimpleNamespace(model={"model_type": "a_fifth_family"})
    assert reader(context) == pytest.approx(100 * (3 * PROJ_FORWARD / 1e9) / 20e-3)
    context["trainer"].cells = [types.SimpleNamespace(apply=lambda p, h: h * 2.0)]
    context.pop("_tok_proj_least_flops")
    assert reader(context) is None
    assert spec.metric_reader("layer_metrics", "tok_proj_ms")(context) == pytest.approx(20.0)
    with open(spec.BENCH_DIR + "/layer_metrics/tok_proj_roofline.py") as f:
        assert "model_type" not in f.read()


@pytest.mark.parametrize("case", ["untraced", "no_scopes", "no_compiled_step"])
def test_nothing_to_read_reads_none_and_raises_nothing(context, case):
    if case == "untraced":
        context["reduced"] = None
    elif case == "no_scopes":  # a tree before the part scopes
        context["_token_parts_text"] = TEXT.replace("mpi4dl_part_", "")
    else:  # the toy family's trainer has no compiled_step
        from chipbench.tests import toy_tokens_program

        del context["_token_parts_text"]
        context["trainer"] = toy_tokens_program.build_trainer(
            {"vocab_size": 16, "hidden_size": 8,
             "optimizer": {"learning_rate": 0.1, "momentum": 0.9}}, 2)[0]
    for name in NAMES:
        assert spec.metric_reader("layer_metrics", name)(context) is None, name


def test_the_table_prints_cells_mixers_layout_and_families():
    lines = token_table.table_lines(TEXT, _events(), (0, STEPS * 100 * MS), STEPS)
    text = "\n".join(lines)
    assert "busy (union of the op intervals) 95.000; parts + unscoped 95.000" in text
    cells = lines[1].split()
    row = next(line.split() for line in lines if line.startswith("01 "))
    assert dict(zip(cells, row[1:]))["recurrence"] == "23.000"
    mamba = next(line.split() for line in lines if line.startswith("mamba2 "))
    assert mamba[-1] == "48.000"  # proj 20 + recurrence 23 + gates_norms 5
    assert "expert_products: ragged-dot-none 9.000 (x1), copy 3.000 (x1)" in text
    assert "    layout: copy f32[16,8] 3.000 (x1)" in text
    bare = token_table.table_lines(
        TEXT.replace("mpi4dl_part_", ""), _events(), (0, STEPS * 100 * MS), STEPS)
    assert len(bare) == 1 and "nothing to split" in bare[0]
