"""``phi4flash_ssm_min_step_log_decay``: the most negative ``dt A`` of
one token step. A selective-scan mixer adds to the program's counter
``ssm1.log_decay_min`` the least ``dt_t[c] A[n, c]`` over its tokens,
channels and state entries and 1 to ``ssm1.scans``; counters are sums
over layers and steps, so this is their quotient: the mean over the
run's layer-steps of each layer's least. ``exp`` of it is the least
share of a state entry that one step hands on; towards -88 that ``exp``
underflows in float32 and the backward pass's ``d da = G h`` products
are of a state that is gone."""


def read(ctx):
    total = ctx.counters.get("ssm1.log_decay_min")
    scans = ctx.counters.get("ssm1.scans")
    if total is None or not scans:
        return None
    return total / scans
