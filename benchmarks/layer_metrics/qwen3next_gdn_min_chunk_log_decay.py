"""``qwen3next_gdn_min_chunk_log_decay``: how much of the state a chunk
starts from is still there at its end. A gated delta-rule layer with a
decay a head adds to the program's counter ``gdn.log_decay_min`` the
most negative running sum of its log-decays inside any chunk (over its
value heads and chunks) and 1 to ``gdn.scans``; counters are sums over
layers and steps, so this is their quotient: the mean over the run's
layer-steps of each layer's most negative in-chunk sum. ``exp`` of it is
the least share of a chunk's incoming state that any head hands on; far
below -88 it says that some head's carried state reaches only the first
tokens of the next chunk, and a reader of ``correct`` should know."""


def read(ctx):
    total = ctx.counters.get("gdn.log_decay_min")
    scans = ctx.counters.get("gdn.scans")
    if total is None or not scans:
        return None
    return total / scans
