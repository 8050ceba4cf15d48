"""``xing_mhc_clamped``: the program's counter ``mhc.clamped`` over the
run: entries of a token's ``Hres~`` that met ``mhc_h_res_clamp_min`` or
``mhc_h_res_clamp_max`` before the exponential. At the seed's weights
none should: a clamped entry passes no gradient."""


def read(ctx):
    return ctx.counters.get("mhc.clamped")
