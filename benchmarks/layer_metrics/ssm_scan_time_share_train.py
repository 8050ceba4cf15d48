"""``ssm_scan_time_share.train``: of ``ssm_time_share.train``'s ops,
those the layer runs under its name scope ``ssm.scan`` (the recurrence:
the running log-decays, the scan over the chunks with a chunk's ``L``,
``C B^T``, its products and the state's update in its body, whose ops
count once each and the loop's own event only for what they leave, or
the two kernels that run all of it; not the projections, the
convolution, the gate and the norm around it), over device busy time in
the traced groups, in percent."""
from benchmarks.harness import name_reduce, scope_reduce


def read(ctx):
    return name_reduce.share_of_scope(
        ctx, lambda l: scope_reduce.op_kind(l) == "OP_STATE_SPACE_MIXER",
        "ssm.scan")
