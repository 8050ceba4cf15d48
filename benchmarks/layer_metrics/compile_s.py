"""``compile_s``: host clock around ``FFModel.compile`` (search, op
measurement, floor guard, verifier, init), in seconds."""


def read(ctx):
    return ctx.compile_s
