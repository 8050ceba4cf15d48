"""``mosaic_calls_per_step``: Mosaic (Pallas) kernels in the compiled
train step, counted as ``custom_call_target="tpu_custom_call"`` (the
bare word also sits in op metadata)."""


def read(ctx):
    return ctx.step_text.count('custom_call_target="tpu_custom_call"')
