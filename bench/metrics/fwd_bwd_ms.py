"""fwd_bwd_ms: device milliseconds per step of the ops whose source is the
model code (forward and backward). Layer: fwd+bwd."""
from bench.trace import layer_of


def read(ctx):
    return ctx.per_step_ms(lambda op: layer_of(op) == "fwd+bwd")
