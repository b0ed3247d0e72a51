"""90th percentile of the window's step times, host clock, loss read to
loss read (the sample count is on the run's ``window`` line)."""


def read(context):
    return 1e3 * context["percentile"](context["step_s"], 90)
