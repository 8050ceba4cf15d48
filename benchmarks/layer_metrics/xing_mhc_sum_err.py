"""``xing_mhc_sum_err``: how far the Sinkhorn-Knopp iterations leave
``Hres`` from doubly stochastic. A hyper-connected sub-layer adds to the
program's counter ``mhc.sum_err`` the largest ``|rowsum - 1|`` or
``|colsum - 1|`` over all of its tokens' ``Hres`` and 1 to
``mhc.sublayers``; counters are sums over layers and steps, so this is
their quotient: the mean over the run's sub-layers of each one's worst
token."""


def read(ctx):
    total = ctx.counters.get("mhc.sum_err")
    sublayers = ctx.counters.get("mhc.sublayers")
    if total is None or not sublayers:
        return None
    return total / sublayers
