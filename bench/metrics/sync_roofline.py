"""sync_roofline: least time of the Mem-SGD update over sync_ms.

The least time moves 12 bytes for each element of the compressed
buckets at the HBM peak: read the f32 memory, read the f32 gradient,
write the f32 memory. Whatever implements the update does this much, so
a later select is read against the same yardstick; sync_ms counts the
bucket-wide passes wherever XLA fuses them. Nothing to read where the
cell compresses nothing. Layer: sync engine."""
from bench.trace import layer_of


def read(ctx):
    elements = ctx.facts.get("compressed_elements")
    ms = ctx.per_step_ms(lambda op: layer_of(op) == "sync engine")
    if not elements or not ms:
        return None
    least_ms = 1e3 * 12.0 * elements / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_ms / ms
