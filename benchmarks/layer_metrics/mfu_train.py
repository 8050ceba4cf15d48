"""``mfu.train``: tokens/s x the benchmark's own operations per token
over (chips x the bf16 peak of the table of peaks), in percent. An
end-to-end utilization, not a kernel's roofline share."""


def read(ctx):
    if ctx.peak is None or not ctx.tokens_per_s:
        return None
    return (100.0 * ctx.tokens_per_s * ctx.train_flops_per_token
            / (ctx.chips * ctx.peak["bf16_flops_per_s"]))
