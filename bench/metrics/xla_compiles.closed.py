"""Engine: XLA compiles (or persistent-cache loads) in the traced window,
over every jitted function (``favor_xla_compiles_total{fun}``); steady
state reads 0, closed loop."""


def read(ctx):
    c = ctx.registry["counters"].get("favor_xla_compiles_total")
    if c is None:
        return None
    return float(sum(c["series"].values()))
