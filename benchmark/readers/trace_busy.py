"""Reader ``trace_busy``: the share of the traced window in which no
operation ran on the device, mean over the cell's chips, in percent."""


def read(context):
    trace = context["trace"]
    if trace is None or not trace.window_s:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
