"""``step_ms.train``: median group time over the steps of a group, in
milliseconds: the steadier companion of ``train_tokens_per_s``."""
import statistics


def read(ctx):
    if not ctx.groups:
        return None
    return statistics.median(ctx.groups) / ctx.steps_per_group * 1e3
