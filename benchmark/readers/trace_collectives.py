"""Reader ``trace_collectives``: device time of collective operations
per step, in milliseconds, on the device that spends most in them.

``exposed=false``: all of it, the time collectives hold the operation
line plus the compute they span between the halves of an asynchronous
pair. ``exposed=true``: only the time in which no compute operation
runs on that device. A trace without a collective (a one-chip cell)
gives no value.
"""


def read(context, program, steps, exposed):
    trace, traced = context["trace"], context["traced"]
    if trace is None or not traced or not traced.get(steps):
        return None
    shown, hidden = trace.collective_seconds(program)
    if not shown and not hidden:
        return None
    seconds = shown if exposed else shown + hidden
    return seconds / traced[steps] * 1e3
