"""Global batch x whole steps in the window / the window's length, all
chips of the cell together."""


def read(context):
    return context["window_rate"]
