"""Programs lowered inside the measured window (``jax.monitoring``'s
``jaxpr_to_mlir_module_duration`` events); every one is a stall."""


def read(context):
    return float(context["compiles_in_window"])
