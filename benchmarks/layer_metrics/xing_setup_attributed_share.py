"""``xing_setup_attributed_share``: of the time from the recorder's first
event to the end of the warm-up, the percent under the union of the leaf
spans (``setup_reduce.LEAF_PREFIXES``, ``LEAF_NAMES``).
``setup_attributed_share``'s reading, for a cell that metric's ``workloads`` list does not
hold."""
from benchmarks.harness import setup_reduce


def read(ctx):
    return setup_reduce.reduced(ctx)["setup_attributed_share"]
