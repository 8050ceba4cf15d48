"""``qwen3next_gdn_scan_time_share.train``: of
``qwen3next_gdn_time_share.train``'s ops, those the layer runs under its
name scope ``gdn.scan`` (the recurrence: the running log-decays, a
chunk's ``(K K^T) * L`` and ``(Q K^T) * L``, the triangular solve and
the scan over the chunks, whose body's ops count once each and the
loop's own event only for what they leave; not the projections, taps
and gates around it), over device busy time in the traced groups, in
percent."""
from benchmarks.harness import name_reduce, scope_reduce


def read(ctx):
    return name_reduce.share_of_scope(
        ctx, lambda l: scope_reduce.op_kind(l) == "OP_GATED_DELTA_RULE"
        and (getattr(l, "params", None) or {}).get("decay") == "head",
        "gdn.scan")
