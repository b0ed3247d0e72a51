"""The split of a traced step by model cell and operator class
(``harness/step_classes.py``), on a hand-built compiled text and trace whose
answers are known: two whole steps of 100 us on one chip."""

import pytest

from chipbench.harness import spec, step_classes, xtrace
from chipbench.harness.xtrace import Event

US = 1000  # ns
STEPS = 2
_CELL3 = "jit(_train_step)/jvp()/shard_map/mpi4dl_cell03/AmoebaCell"
_BACK3 = "jit(_train_step)/transpose(jvp())/shard_map/mpi4dl_cell03/AmoebaCell"


def _meta(stack):
    return f'metadata={{op_name="{stack}" stack_frame_id=7}}'


# What the chip's compiler leaves of a step, in small: a convolution fused
# with its BatchNorm epilogue under the epilogue's op_name, a BatchNorm fusion
# with one ReLU in it, a copy the compiler made to feed a pool, a
# select-and-scatter and a rewritten convolution that lost their stacks, a
# BatchNorm all-reduce, the gradients' asynchronous sum whose -done has no
# stack, a while whose body is another cell's, the optimiser, and a copy
# nobody claims.
TEXT = f"""HloModule jit__train_step, entry_computation_layout={{()->()}}

%max_region (a: bf16[], b: bf16[]) -> bf16[] {{
  %a = bf16[] parameter(0)
  %b = bf16[] parameter(1)
  ROOT %maximum.0 = bf16[] maximum(%a, %b)
}}

%fused_conv (p0: bf16[2,8,8,4], p1: bf16[3,3,4,4]) -> bf16[2,8,8,4] {{
  %p0 = bf16[2,8,8,4]{{3,2,1,0}} parameter(0)
  %p1 = bf16[3,3,4,4]{{3,2,1,0}} parameter(1)
  %convolution.1 = bf16[2,8,8,4]{{3,2,1,0}} convolution(%p0, %p1), window={{size=3x3 pad=1_1x1_1}}, dim_labels=b01f_01io->b01f, {_meta(_CELL3 + "/op3/conv1/mpi4dl_convkxk/conv/conv_general_dilated")}
  %multiply.1 = bf16[2,8,8,4]{{3,2,1,0}} multiply(%convolution.1, %convolution.1), {_meta(_CELL3 + "/op3/bn1/mpi4dl_batchnorm/mul")}
  %add.1 = bf16[2,8,8,4]{{3,2,1,0}} add(%multiply.1, %convolution.1), {_meta(_CELL3 + "/op3/bn1/mpi4dl_batchnorm/add")}
  ROOT %maximum.1 = bf16[2,8,8,4]{{3,2,1,0}} maximum(%add.1, %add.1), {_meta(_CELL3 + "/op4/jit(relu)/max")}
}}

%fused_bn (p0.1: bf16[2,8,8,4]) -> bf16[2,8,8,4] {{
  %p0.1 = bf16[2,8,8,4]{{3,2,1,0}} parameter(0)
  %multiply.2 = bf16[2,8,8,4]{{3,2,1,0}} multiply(%p0.1, %p0.1), {_meta(_BACK3 + "/op3/bn1/mpi4dl_batchnorm/mul")}
  %subtract.2 = bf16[2,8,8,4]{{3,2,1,0}} subtract(%multiply.2, %p0.1), {_meta(_BACK3 + "/op3/bn1/mpi4dl_batchnorm/sub")}
  ROOT %select.2 = bf16[2,8,8,4]{{3,2,1,0}} select(%subtract.2, %subtract.2, %p0.1), {_meta(_BACK3 + "/op4/jit(relu)/select_n")}
}}

%fused_opt (p0.2: f32[3,3,4,4], p1.2: f32[3,3,4,4]) -> f32[3,3,4,4] {{
  %p0.2 = f32[3,3,4,4]{{3,2,1,0}} parameter(0)
  %p1.2 = f32[3,3,4,4]{{3,2,1,0}} parameter(1)
  %multiply.3 = f32[3,3,4,4]{{3,2,1,0}} multiply(%p0.2, %p1.2), {_meta("jit(_train_step)/mpi4dl_optimizer/mul")}
  ROOT %add.3 = f32[3,3,4,4]{{3,2,1,0}} add(%multiply.3, %p1.2), {_meta("jit(_train_step)/mpi4dl_optimizer/add")}
}}

%fused_scan (p0.3: bf16[2,8,8,4]) -> bf16[2,8,8,4] {{
  %p0.3 = bf16[2,8,8,4]{{3,2,1,0}} parameter(0)
  ROOT %tanh.4 = bf16[2,8,8,4]{{3,2,1,0}} tanh(%p0.3), {_meta("jit(_train_step)/jvp()/shard_map/mpi4dl_cell05/Block/mixer/while/body/tanh")}
}}

%body (carry: (bf16[2,8,8,4])) -> (bf16[2,8,8,4]) {{
  %carry = (bf16[2,8,8,4]{{3,2,1,0}}) parameter(0)
  %get-tuple-element.9 = bf16[2,8,8,4]{{3,2,1,0}} get-tuple-element(%carry), index=0
  %fusion.9 = bf16[2,8,8,4]{{3,2,1,0}} fusion(%get-tuple-element.9), kind=kLoop, calls=%fused_scan, {_meta("jit(_train_step)/jvp()/shard_map/mpi4dl_cell05/Block/mixer/while/body/tanh")}
  ROOT %tuple.9 = (bf16[2,8,8,4]{{3,2,1,0}}) tuple(%fusion.9)
}}

%cond (carry.1: (bf16[2,8,8,4])) -> pred[] {{
  %carry.1 = (bf16[2,8,8,4]{{3,2,1,0}}) parameter(0)
  ROOT %constant.8 = pred[] constant(true)
}}

ENTRY %main.1 (param.0: bf16[2,8,8,4], param.1: f32[3,3,4,4], param.2: f32[3,3,4,4]) -> f32[3,3,4,4] {{
  %param.0 = bf16[2,8,8,4]{{3,2,1,0}} parameter(0), metadata={{op_name="x"}}
  %param.1 = f32[3,3,4,4]{{3,2,1,0}} parameter(1), metadata={{op_name="state.params[3][\\'params\\'][\\'op3\\'][\\'conv1\\'][\\'conv\\'][\\'kernel\\']"}}
  %param.2 = f32[3,3,4,4]{{3,2,1,0}} parameter(2), metadata={{op_name="state.opt_state[0].trace[3]"}}
  %copy.1 = bf16[2,8,8,4]{{3,2,0,1:T(8,128)(2,1)}} copy(%param.0)
  %constant.1 = bf16[] constant(-inf)
  %reduce-window.1 = bf16[2,8,8,4]{{3,2,1,0}} reduce-window(%copy.1, %constant.1), window={{size=1x3x3x1 pad=0_0x1_1x1_1x0_0}}, to_apply=%max_region, {_meta(_CELL3 + "/op0/mpi4dl_pool/reduce_window_max")}
  %fusion.1 = bf16[2,8,8,4]{{3,2,1,0}} fusion(%reduce-window.1, %param.1), kind=kOutput, calls=%fused_conv, {_meta(_CELL3 + "/op3/bn1/mpi4dl_batchnorm/add")}
  %all-reduce.1 = bf16[2,8,8,4]{{3,2,1,0}} all-reduce(%fusion.1), replica_groups={{}}, to_apply=%max_region, {_meta(_CELL3 + "/op3/bn1/mpi4dl_batchnorm/psum")}
  %tuple.1 = (bf16[2,8,8,4]{{3,2,1,0}}) tuple(%all-reduce.1)
  %while.1 = (bf16[2,8,8,4]{{3,2,1,0}}) while(%tuple.1), condition=%cond, body=%body, {_meta("jit(_train_step)/jvp()/shard_map/mpi4dl_cell05/Block/mixer/while")}
  %get-tuple-element.1 = bf16[2,8,8,4]{{3,2,1,0}} get-tuple-element(%while.1), index=0, {_meta("jit(_train_step)/jvp()/shard_map/mpi4dl_cell05/Block/mixer/while")}
  %fusion.2 = bf16[2,8,8,4]{{3,2,1,0}} fusion(%get-tuple-element.1), kind=kLoop, calls=%fused_bn, {_meta(_BACK3 + "/op4/jit(relu)/select_n")}
  %select-and-scatter.1 = bf16[2,8,8,4]{{3,2,1,0}} select-and-scatter(%copy.1, %fusion.2, %constant.1), window={{size=1x3x3x1}}, select=%max_region, scatter=%max_region
  %convolution.7 = f32[3,3,4,4]{{3,2,1,0}} convolution(%select-and-scatter.1, %fusion.2), window={{size=8x8}}, dim_labels=f01b_i01o->01bf
  %all-reduce-start.2 = f32[3,3,4,4]{{3,2,1,0}} all-reduce-start(%convolution.7), replica_groups={{}}, to_apply=%max_region, {_meta("jit(_train_step)/transpose(jvp())/shard_map/psum")}
  %all-reduce-done.2 = f32[3,3,4,4]{{3,2,1,0}} all-reduce-done(%all-reduce-start.2)
  %copy.2 = bf16[2,8,8,4]{{3,2,1,0}} copy(%param.0)
  ROOT %fusion.3 = f32[3,3,4,4]{{3,2,1,0}} fusion(%all-reduce-done.2, %param.2), kind=kLoop, calls=%fused_opt, {_meta("jit(_train_step)/mpi4dl_optimizer/add")}
}}
"""

# instruction: (start us, length us) inside a step of 100 us; the while
# spans its body's two trips; idle 92-100
TIMES = {
    "copy.1": (0, 4), "reduce-window.1": (4, 6), "fusion.1": (10, 20),
    "all-reduce.1": (30, 2), "while.1": (32, 20), "fusion.9": (34, 7),
    "fusion.2": (52, 8), "select-and-scatter.1": (60, 6), "convolution.7": (66, 9),
    "all-reduce-start.2": (75, 1), "copy.2": (76, 3), "all-reduce-done.2": (79, 5),
    "fusion.3": (84, 8),
}


def _events():
    out = []
    for k in range(STEPS + 1):
        t = k * 100 * US
        for name, (start, length) in TIMES.items():
            opcode = name.rsplit(".", 1)[0]
            out.append(Event(f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %x)",
                             t + start * US, length * US, {}))
        # the body's second trip
        out.append(Event("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %x)",
                         t + 43 * US, 7 * US, {}))
    return out


@pytest.fixture()
def context():
    events = _events()
    reduced = xtrace.Reduced(
        steps=STEPS, window_s=STEPS * 100e-6, busy_s=STEPS * 92e-6,
        chips=[{"window": (0, STEPS * 100 * US), "ops": events}],
        device_ops=[], idle_gaps=[])

    class Trainer:
        cells = [None] * 6      # the head is cell 05
        n_spatial = 0

    return {"reduced": reduced, "trainer": Trainer(), "_step_text": TEXT}


def test_every_instruction_lands_in_its_class_and_cell():
    table = step_classes.classify(TEXT)
    want = {
        "fusion.1": ("convkxk", "03", False),          # not its epilogue's batchnorm
        "fusion.2": ("batchnorm", "03", False),        # two of its three instructions
        "copy.1": ("pool", "03", False),               # its first consumer's
        "reduce-window.1": ("pool", "03", False),
        "select-and-scatter.1": ("pool", "03", False),  # by opcode; the cell inherited
        "convolution.7": ("convkxk", "03", False),      # rewritten, no stack: by opcode
        "all-reduce.1": ("batchnorm", "03", True),
        "all-reduce-start.2": ("grad_allreduce", None, True),
        "all-reduce-done.2": ("grad_allreduce", None, True),
        "while.1": ("other", "05", False),
        "fusion.9": ("other", "05", False),
        "fusion.3": ("optimizer", None, False),
        "copy.2": ("unscoped", None, False),
    }
    assert {k: table[k] for k in want} == want
    assert "convolution.1" not in table  # a fused instruction is no op of its own


def test_classes_and_unscoped_add_up_to_busy_and_the_while_counts_once(context):
    split = step_classes.split(context)
    assert sum(split.values()) == pytest.approx(0.092)  # ms a step: the busy time
    busy = xtrace.union_seconds(xtrace.clip(
        context["reduced"].chips[0]["ops"], 0, STEPS * 100 * US)) / STEPS
    assert sum(split.values()) == pytest.approx(1e3 * busy)
    by_class = {c: step_classes.ms(context, (c,)) for c in step_classes.CLASSES}
    assert by_class == {
        "convkxk": pytest.approx(0.029), "conv1x1": None,
        "batchnorm": pytest.approx(0.010), "pool": pytest.approx(0.016),
        "halo": None, "optimizer": pytest.approx(0.008), "loss": None,
        "grad_allreduce": pytest.approx(0.006),
        "other": pytest.approx(0.020),  # the while once, not 20 + 2 x 7
        "unscoped": pytest.approx(0.003),
    }
    assert step_classes.cell_ms(context) == {
        "03": pytest.approx(0.055), "05": pytest.approx(0.020)}


def test_the_metrics_read_the_split(context):
    context["peaks"] = {"bf16_flops_per_s": 1e12}
    read = {name: spec.metric_reader("layer_metrics", name) for name in (
        "unscoped_ms", "optimizer_ms", "head_loss_ms", "conv_ms", "conv1x1_ms",
        "batchnorm_ms", "pool_ms", "cell_ms_max", "grad_allreduce_ms",
        "bn_allreduce_ms", "halo_ms")}
    got = {name: reader(context) for name, reader in read.items()}
    assert got == {
        "unscoped_ms": pytest.approx(0.003), "optimizer_ms": pytest.approx(0.008),
        "head_loss_ms": pytest.approx(0.020),  # cell 05 is the last; no loss op
        "conv_ms": pytest.approx(0.029), "conv1x1_ms": None,
        "batchnorm_ms": pytest.approx(0.010), "pool_ms": pytest.approx(0.016),
        "cell_ms_max": pytest.approx(0.055),
        "grad_allreduce_ms": pytest.approx(0.006),
        "bn_allreduce_ms": pytest.approx(0.002), "halo_ms": None,
    }
    # the three collective parts make up collective_ms
    collective = spec.metric_reader("layer_metrics", "collective_ms")(context)
    assert collective == pytest.approx(0.008)


def test_innermost_event_has_the_time():
    events = [Event("a", 0, 100, {}), Event("b", 10, 20, {}), Event("c", 15, 5, {}),
              Event("d", 90, 30, {}), Event("e", 200, 10, {})]
    got = step_classes.innermost_seconds(events, 0, 205, key=lambda ev: ev.name)
    assert {k: v * 1e9 for k, v in got.items()} == {
        "a": pytest.approx(70), "b": pytest.approx(15), "c": pytest.approx(5),
        "d": pytest.approx(30), "e": pytest.approx(5)}


ALL = ("unscoped_ms", "optimizer_ms", "head_loss_ms", "conv_ms", "conv1x1_ms",
       "conv_roofline", "conv1x1_roofline", "batchnorm_ms", "pool_ms",
       "cell_ms_max", "grad_allreduce_ms", "bn_allreduce_ms", "halo_ms")


@pytest.mark.parametrize("name", ALL)
def test_a_program_without_the_scopes_reads_nothing(context, name):
    """The parent's program under this benchmark: no ``compiled_step``, or a
    compiled text that names no cell and no optimiser; and a run that was not
    traced. The reader returns None and the line leaves the metric out."""
    read = spec.metric_reader("layer_metrics", name)
    bare = TEXT.replace("mpi4dl_", "")
    assert read(dict(context, _step_text=bare)) is None
    no_accessor = {k: v for k, v in context.items() if k != "_step_text"}
    assert read(dict(no_accessor, trainer=object())) is None
    assert read(dict(context, reduced=None)) is None


def test_the_table_tool_prints_rows_columns_and_sums(context, tmp_path, capsys):
    from chipbench.tools import step_table

    step_table.dump(str(tmp_path), context)
    step_table.main(["--from", str(tmp_path)])
    out = capsys.readouterr().out
    lines = out.splitlines()
    header = next(l for l in lines if l.startswith("cell "))
    assert header.split() == ["cell", "convkxk", "batchnorm", "pool", "optimizer",
                              "grad_allreduce", "other", "unscoped", "sum"]
    row = {l.split()[0]: [float(v) for v in l.split()[1:]] for l in lines
           if l.split()[0] in ("03", "05", "-", "sum", "coll.")}
    assert row["03"] == pytest.approx([0.029, 0.010, 0.016, 0, 0, 0, 0, 0.055])
    assert row["-"] == pytest.approx([0, 0, 0, 0.008, 0.006, 0, 0.003, 0.017])
    assert row["sum"][-1] == pytest.approx(0.092)
    assert row["coll."] == pytest.approx([0, 0.002, 0, 0, 0.006, 0, 0, 0.008])
    assert "unscoped by op family: copy 0.003" in out
