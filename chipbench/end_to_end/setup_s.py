"""Process start to the start of the window: imports, build, weights,
trace, compile or cache load, the warm-up steps. The reference check runs
after the window and is not in it."""


def read(context):
    return context["setup_seconds"]
