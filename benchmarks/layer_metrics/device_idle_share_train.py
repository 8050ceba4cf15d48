"""``device_idle_share.train``: 1 - union of device-op intervals over
the traced window of two groups, mean over the chips, in percent."""


def read(ctx):
    if not ctx.trace:
        return None
    return 100.0 * ctx.trace["idle_share"]
