"""``ssm_min_chunk_log_decay``: how much of the state a chunk
starts from is still there at its end. A state-space mixer adds to the
program's counter ``ssm.min_chunk_log_decay`` the most negative
log-decay summed over one chunk (``G_C``, over its heads and chunks) and
1 to ``ssm.layers``; counters are sums over layers and steps, so this is
their quotient: the mean over the run's layer-steps of each layer's most
negative ``G_C``. ``exp`` of it is the least share of a chunk's incoming
state that any head hands on. At the seed's weights it is far below -88
(a head whose state survives no chunk), and a reader of ``correct``
should know: such a head's carried state reaches only the first tokens
of the next chunk."""


def read(ctx):
    total = ctx.counters.get("ssm.min_chunk_log_decay")
    layers = ctx.counters.get("ssm.layers")
    if total is None or not layers:
        return None
    return total / layers
