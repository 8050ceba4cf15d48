"""``kernel_time_share.train``: device time inside Mosaic custom calls
over device busy time, from the profiler trace, in percent."""


def read(ctx):
    if not ctx.trace:
        return None
    return 100.0 * ctx.trace["kernel_time_share"]
