"""``keye_dsa_select_time_share.train``: of
``keye_dsa_time_share.train``'s ops, those under the name scope
``dsa.select`` (the 32 compare-and-count passes of the threshold search,
whose loop body's ops count once each, the running count among equal
scores and the mask), over device busy time in the traced groups, in
percent."""
from benchmarks.harness import sparse_reduce


def read(ctx):
    return sparse_reduce.share_of_scopes(ctx, ("dsa.select",))
