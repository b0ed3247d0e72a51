"""Share of a step in which no op runs on the device, mean over the cell's
chips: 1 - (union of device-op intervals per traced step) / (the measured
window's mean step time). The busy time comes from the trace and the step
time from the untraced window, because the profiler slows the traced steps
themselves (1.05x on one chip, 4.3x on four: PERF.md section 5) while the
device runs the same program either way; the traced window's own idle share
follows from ``device.busy_s`` and ``device.window_s`` on the result line."""


def read(context):
    reduced, step_s = context["reduced"], context["step_s"]
    if reduced is None or not step_s:
        return None
    busy_per_step = reduced.busy_s / reduced.steps
    return 100.0 * (1.0 - busy_per_step / (sum(step_s) / len(step_s)))
