"""Reader ``host_counters``: a ratio of two of the driver's counters.

``counters`` are what the driver measured in the untraced window on
the host's clock or read from the program's public counters (for the
``epochs`` driver: ``window_s``, ``gap_s``, ``input_wait_s``,
``train_flops_per_s``) plus ``peak_flops_per_s`` from the peak table
times the cell's chips. A counter that the driver does not supply, or
a zero denominator, gives no value.
"""


def read(context, numerator, denominator, scale=1.0):
    counters = context["counters"]
    top, bottom = counters.get(numerator), counters.get(denominator)
    if top is None or not bottom:
        return None
    return scale * top / bottom
