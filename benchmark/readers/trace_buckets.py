"""Reader ``trace_buckets``: device time of a program's operations by
source bucket, per step, in milliseconds; or that time's roofline
share.

``program`` is a part of the program's name in the trace
(``train_segment``), ``bucket`` a source bucket as
``trace_reduce.bucket_of`` names it (``nn/conv.py``; ``*`` for the
whole program), ``steps`` the driver's count of the steps traced
(``train_steps`` or ``eval_steps``). With ``roofline`` (a layer kind
of ``flops.py``: ``conv`` or ``dense``) the value is the least time
the chip could take for those layers of one step, from shapes and the
peak table, over the bucket's measured time, in percent; an earlier
line says which bound applies.
"""

from benchmark import flops


def read(context, program, bucket, steps, roofline=None):
    trace, traced = context["trace"], context["traced"]
    if trace is None or not traced or not traced.get(steps):
        return None
    seconds = trace.self_seconds(program, bucket) / traced[steps]
    if not seconds:
        return None
    if roofline is None:
        return seconds * 1e3
    config, peaks = context["config"], context["peaks"]
    if peaks is None:
        return None
    floor, bound = flops.roofline_floor_s(
        config["layers"], flops.input_shape(config), roofline,
        config["batch"] // context["chips"],
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    context["log"](
        "roofline %s (%s): floor %.3f ms a step, %s-bound; measured "
        "%.3f ms" % (roofline, bucket, floor * 1e3, bound, seconds * 1e3))
    return 100.0 * floor / seconds
