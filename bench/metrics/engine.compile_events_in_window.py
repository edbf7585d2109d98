"""Engine: JAX backend compiles (persistent-cache loads included) that
happened inside the measured window. Every executable the traffic reaches
is warmed in set-up, so this should read 0; each event is a stall of the
tick loop. A count."""


def read(ctx):
    return float(ctx.stats.get("compiles", 0))
