"""``fwd_time_share.train``: device self time of the ops whose
``op_name`` is under ``ff.forward`` or ``ff.loss`` (and in no
``transpose(``) over device busy time in the traced groups, in percent."""
from benchmarks.harness import span_reduce


def read(ctx):
    return span_reduce.phase_share(ctx, "forward")
