"""The phases of one run, shared by ``run.py`` and ``tools/readings.py``.

``Session`` builds the program once; ``first_steps`` makes the weights and
the state from a seed and drives the timed object through its first steps
by the window's own loop, keeping what the comparison needs; ``compare``
runs the plain reference over the same steps once the program's state is
freed and returns every compared number.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import json
import time

from . import check, counting, defaults, program, spec
from .loop import StepLoop


def say(**fields):
    """An earlier line of the run's output: one JSON object."""
    print(json.dumps(fields), flush=True)


@dataclasses.dataclass
class FirstSteps:
    seed: int
    loop: StepLoop
    batches: list          # host (x, y) of the compared steps
    losses: list           # the program's loss at each compared step
    grad_norms: list       # per leaf, the first gradient as the optimiser got it
    change_norms: list     # per leaf, ||params after the steps - params at the seed||
    compile_s: float = 0.0  # a follower's seconds compiling or loading its programs
    grad_scale: float = 0.0  # a follower's largest whole-tree gradient norm over the steps


class Session:
    """One cell's program and reference. The reference module is the
    family's face: ``cells(model)`` and ``kinds(model)`` it must define;
    ``input_spec(model, traffic)``, ``loss(logits, labels)`` and
    ``train_flops_per_sample(model, traffic)`` it may (``defaults.py`` and
    ``counting.py`` hold what stands in for each)."""

    def __init__(self, cell: spec.Cell):
        from chipbench.reference import plain

        self.cell = cell
        self.batch = int(cell.traffic["batch_size"])
        self.check_steps = int(cell.traffic["check_steps"])
        t0 = time.perf_counter()
        self.trainer, self.cfg = program.build_trainer(cell.config, self.batch)
        self.reference = importlib.import_module(cell.config["reference"]["module"])
        self.ref_cells = self.reference.cells(cell.model)
        self.kinds = self.reference.kinds(cell.model)
        input_spec = getattr(self.reference, "input_spec", defaults.input_spec)
        sample_shape, self.x_dtype = input_spec(cell.model, cell.traffic)
        self.x_shape = (self.batch,) + tuple(sample_shape)
        self.loss = getattr(self.reference, "loss", defaults.loss)
        self.specs = plain.record_specs(self.ref_cells, self.x_shape, self.x_dtype)
        self.make_params = plain.params_maker(self.specs)
        say(phase="build", seconds=time.perf_counter() - t0,
            remat=self.trainer.remat, mesh=dict(self.trainer.mesh.shape),
            cells=len(self.ref_cells), spatial_cells=self.trainer.n_spatial,
            parameters=sum(
                _size(shape) for s in self.specs for shape, _ in s.values()))

    @functools.cached_property
    def flops_per_sample(self) -> float:
        """Training FLOPs of one sample: the family's own count, else 3 x
        the reference's forward conv and matmul FLOPs (read after the
        window: the count traces the whole reference)."""
        own = getattr(self.reference, "train_flops_per_sample", None)
        if own is not None:
            return float(own(self.cell.model, self.cell.traffic))
        return counting.train_flops_per_sample(
            self.ref_cells, self.x_shape[1:], self.x_dtype)

    def first_steps(self, seed: int, steps: int, wrap_step=None) -> FirstSteps:
        """Weights and state from ``seed``; the first ``steps`` steps (at
        least the compared ones) through the loop the window will use.
        ``wrap_step`` lets a test break the timed path underneath."""
        import jax

        from chipbench.reference import plain

        params = self.make_params(seed)
        state = program.initial_state(self.trainer, params)
        del params
        stream = program.input_stream(
            self.cell.config, self.cfg, self.cell.traffic, seed)
        first = FirstSteps(seed, None, [], [], None, None)

        def keep(index, state, host_batch):
            if index >= self.check_steps:
                return
            first.batches.append(host_batch)
            if index == 0:
                leaves = jax.tree.leaves(state.opt_state)
                if len(leaves) != len(jax.tree.leaves(state.params)):
                    raise RuntimeError(
                        "the optimiser's state is not one momentum trace per "
                        "parameter; the first gradient cannot be read from it"
                    )
                first.grad_norms = [float(v) for v in check.leaf_norms(leaves)]
            if index == self.check_steps - 1:
                seeded = self.make_params(seed)
                first.change_norms = [
                    float(v) for v in
                    check.leaf_norms_of_difference(state.params, seeded)
                ]

        trainer = self.trainer if wrap_step is None else wrap_step(self.trainer)
        loop = StepLoop(trainer, state, stream, on_step=keep)
        loop.run(steps=max(steps, self.check_steps))
        loop.on_step = None
        first.loop = loop
        first.losses = list(loop.losses[: self.check_steps])
        return first

    def batches_only(self, seed: int) -> FirstSteps:
        """The compared steps' batches without the program: what a reading
        of the control alone needs."""
        stream = iter(program.input_stream(
            self.cell.config, self.cfg, dict(self.cell.traffic, prefetch=False),
            seed))
        batches = [next(stream) for _ in range(self.check_steps)]
        return FirstSteps(seed, None, batches, None, None, None)

    def compare(self, first: FirstSteps, control=None):
        """Every compared number of one run, ``{name: value}``. With
        ``control`` (a mode of ``reference/plain.py``) the control's numbers
        are taken beside the program's, off the same float32 pass: the
        reference in that arithmetic stands in the program's place, as a
        follower of the same batches for the whole-step numbers and as each
        tapped cell for the cell-by-cell ones. Then the result is
        ``(program's numbers, control's numbers)``; the program's are None
        where ``first`` holds batches only."""
        import jax

        from chipbench.reference import plain

        taps = check.sample_taps(self.kinds, first.seed)
        # in the order the VJPs return them: output, the parameters'
        # cotangents, the input's cotangent (none from a cell fed integers:
        # ``zip`` then ends before ``cell_dx_err``)
        keys = ("cell_y_err", "cell_dv_err", "cell_dx_err")
        errors = {k: {} for k in keys}
        control_errors = {k: {} for k in keys}

        seconds = {"taps": 0.0}

        def on_tap(follower, index, x):
            t0 = time.perf_counter()
            try:
                compare_cell(follower, index, x)
            finally:
                seconds["taps"] += time.perf_counter() - t0

        def compare_cell(follower, index, x):
            fn, variables = self.ref_cells[index], follower.params[index]
            head = index == len(self.ref_cells) - 1
            y_shape = jax.eval_shape(
                lambda v, x_: fn(plain.Scope(v["params"]), x_), variables, x
            )
            ct = check.seeded_cotangent(y_shape, first.seed, index)
            if head:  # the follower's head program ends in the loss
                ref = check.reference_cell_vjp(fn, "f32", variables, x, ct)
            else:
                ref = (follower.forward_cell(index, x),) + tuple(
                    follower.vjp_cell(index, x, ct))
            if first.losses is not None:
                got = check.program_cell_vjp(self.trainer, index, variables, x, ct)
                for key, a, b in zip(keys, got, ref):
                    errors[key][index] = check.relative_l2(a, b)
            if control:
                got = check.reference_cell_vjp(fn, control, variables, x, ct)
                for key, a, b in zip(keys, got, ref):
                    control_errors[key][index] = check.relative_l2(a, b)

        t0 = time.perf_counter()
        followed = self._follow(first, "f32", taps, on_tap)
        say(phase="reference", seconds=time.perf_counter() - t0,
            compile_or_load_s=followed.compile_s, tapped_cells_s=seconds["taps"],
            taps=taps,
            tap_kinds=[self.kinds[i] for i in taps],
            program_losses=first.losses, reference_losses=followed.losses,
            cell_errors={k: {str(i): v for i, v in d.items()}
                         for k, d in errors.items()})
        numbers = self._numbers(first, followed, errors) if first.losses else None
        if not control:
            return numbers
        stand_in = self._follow(first, control)
        say(phase="control", mode=control, losses=stand_in.losses,
            cell_errors={k: {str(i): v for i, v in d.items()}
                         for k, d in control_errors.items()})
        return numbers, self._numbers(stand_in, followed, control_errors)

    def _follow(self, first: FirstSteps, mode, taps=(), on_tap=None) -> FirstSteps:
        """The reference in ``mode``'s arithmetic over the same batches from
        the same seed: its losses, first gradient and parameters' change."""
        from chipbench.reference import plain
        from chipbench.reference.step import Follower

        opt = self.cell.config["optimizer"]
        follower = Follower(
            self.ref_cells, self.make_params(first.seed),
            opt["learning_rate"], opt["momentum"], self.loss, mode=mode,
        )
        losses, grad_norms, grad_scale = [], None, 0.0
        for k, (x, y) in enumerate(first.batches):
            loss, grads = follower.step(
                x, y, taps=taps if k == 0 else (), on_tap=on_tap
            )
            losses.append(loss)
            norms = [float(v) for v in check.leaf_norms(grads)]
            grad_scale = max(grad_scale, check.whole_norm(norms))
            if k == 0:
                grad_norms = norms
            del grads
        change = [
            float(v) for v in check.leaf_norms_of_difference(
                follower.params, self.make_params(first.seed))
        ]
        return FirstSteps(first.seed, None, first.batches, losses, grad_norms,
                          change, compile_s=follower.prepare_s,
                          grad_scale=grad_scale)

    def _numbers(self, got: FirstSteps, ref: FirstSteps, cell_errors: dict) -> dict:
        numbers = {}
        for k, (a, b) in enumerate(zip(got.losses, ref.losses)):
            numbers[f"loss_gap_step{k + 1}"] = abs(a - b) / abs(b)
        # The whole first gradient is measured against the reference's
        # largest gradient over the compared steps: when the images of the
        # first batch carry one label, train-mode BatchNorm over the batch
        # removes what their cotangents share and the first gradient is a
        # residual, a thirtieth of its usual norm, whose own norm is no scale
        # to measure a gap against (PERF.md section 6).
        (numbers["grad_norm_gap_worst_leaf"],
         numbers["grad_norm_gap"]) = check.norm_gaps(
             got.grad_norms, ref.grad_norms, whole_floor=ref.grad_scale)
        (numbers["change_norm_gap_worst_leaf"],
         numbers["change_norm_gap"]) = check.norm_gaps(
             got.change_norms, ref.change_norms)
        # Each error is the largest over the tapped cells, but for the kinds
        # of cell that the cell's file sets ``apart`` for it (with the
        # reason): those read ``<error>.<kind>`` and have a limit of their own.
        apart = self.cell.limits.get("apart", {})
        for key, by_cell in cell_errors.items():
            for index, value in by_cell.items():
                kind = self.kinds[index]
                name = f"{key}.{kind}" if kind in apart.get(key, ()) else key
                numbers[name] = max(value, numbers.get(name, 0.0))
        return numbers


def _size(shape):
    n = 1
    for d in shape:
        n *= d
    return n
