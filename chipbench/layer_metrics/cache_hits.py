"""Compiled programs found in the persistent cache during set-up
(``jax.monitoring``'s ``/jax/compilation_cache/cache_hits``)."""


def read(context):
    return float(context["setup_cache_hits"])
