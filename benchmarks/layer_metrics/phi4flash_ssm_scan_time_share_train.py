"""``phi4flash_ssm_scan_time_share.train``: of
``phi4flash_ssm_time_share.train``'s ops, those the layer runs under its
name scope ``ssm1.scan`` (the recurrence: a chunk's ``exp(dt A)`` and
``dt B x``, the loop over the tokens and the loop over the chunks, whose
bodies' ops count once each and the loops' own events only for what
they leave, the read through C; not the projections, the convolution
and the gate around it), over device busy time in the traced groups, in
percent."""
from benchmarks.harness import diff_reduce, name_reduce


def read(ctx):
    return name_reduce.share_of_scope(ctx, diff_reduce.is_selective_scan,
                                      "ssm1.scan")
