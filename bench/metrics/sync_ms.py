"""sync_ms: device milliseconds per step of the non-collective ops of the
sync engine: those whose source is the sync engine (select, densify,
error-memory update, encode and decode), and those that carry no frame
of a layer but read or write an array of a compressed bucket's size (the
bucket-wide passes ``u = m + eta*g`` and ``u - sel``). Layer: sync engine."""
from bench.trace import layer_of


def read(ctx):
    return ctx.per_step_ms(lambda op: layer_of(op) == "sync engine")
