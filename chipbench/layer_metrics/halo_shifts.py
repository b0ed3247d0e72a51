"""Forward halo shifts per step (``Trainer.halo_shift_count``: each lowers
to one collective-permute, the backward at most doubles it); counted by
abstract tracing, repeats exactly."""


def read(context):
    import jax

    trainer, session = context["trainer"], context["session"]
    if not trainer.n_spatial:
        return None
    params = jax.eval_shape(session.make_params, 0)
    return float(trainer.halo_shift_count(params, session.x_shape))
