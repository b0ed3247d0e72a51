"""Share of the matrix units' peak a token cell's projections reach: their
least FLOPs a step / the chip's peak bf16 FLOP/s / ``tok_proj_ms``. Least:
forward and the two gradients of every product under ``mpi4dl_part_proj``
once, 3 x the FLOPs of the ``dot_general``s whose innermost part scope is
that one in the program's **forward** jaxpr at the window's shapes (the
trainer's cells applied in turn to shapes, traced once after the window and
never run: :func:`forward_flops`, ``step_classes.conv_class_flops``' way of
counting, here over the program because the names are the program's). No
family is named here: a model whose projections stand under the scope is
counted, the rows being whatever its forward multiplies (both copies' under
block diffusion); the router, the experts' grouped products and the head
stand under other names. The "cell" remat's second forward is executed and
not counted, so the share cannot pass 100%. Matrix-unit-bound at 16,384 rows.

The time is the part's as ``token_parts`` books it (its rule 3): a fusion
that holds a ``dot`` is the dot's whatever else was fused into it, so where
the compiler fuses a mixer's short convolution, taps and gates into its
projections (all of LFM2's ``lfm2_shortconv``) that time is in
``tok_proj_ms`` without any work of its own here, and the share reads low by
it. None from a program without the part scopes, and from one with the
scopes whose forward holds no product under ``mpi4dl_part_proj``."""

from chipbench.harness import counting, token_parts

_KEY = "_tok_proj_least_flops"


def forward_flops(jaxpr, part: str = "proj", prefix: str = "", times: float = 1.0) -> float:
    """FLOPs of the ``dot_general``s of ``jaxpr`` whose name stack's
    innermost part is ``part``: an outer equation's stack stands in front of
    its body's (a ``jit``'s or a ``custom_vjp``'s body is traced under an
    empty stack), a ``scan``'s body counts once a trip."""
    total = 0.0
    for eqn in jaxpr.eqns:
        stack = "/".join(s for s in (prefix, str(eqn.source_info.name_stack)) if s)
        if eqn.primitive.name == "dot_general":
            if token_parts.scope_of(stack)[2] == part:
                total += times * counting._eqn_flops(eqn)
            continue
        trips = times * (eqn.params["length"] if eqn.primitive.name == "scan" else 1)
        for value in eqn.params.values():
            for inner in counting._subjaxprs(value):
                total += forward_flops(inner, part, stack, trips)
    return total


def least_flops_per_step(context) -> float:
    """3 x :func:`forward_flops` of the trainer's cells over the window's
    batch, as shapes."""
    import jax

    session = context["session"]
    cells = context["trainer"].cells

    def forward(params, h):
        for cell, p in zip(cells, params):
            h = cell.apply(p, h)
        return h

    jaxpr = jax.make_jaxpr(forward)(
        jax.eval_shape(session.make_params, 0),
        jax.ShapeDtypeStruct(tuple(session.x_shape), session.x_dtype))
    return 3.0 * forward_flops(jaxpr.jaxpr)


def read(context):
    if token_parts.split(context) is None:
        return None
    if _KEY not in context:
        context[_KEY] = least_flops_per_step(context)
    if not context[_KEY]:
        return None
    return token_parts.roofline_pct(context, "proj", context[_KEY])
