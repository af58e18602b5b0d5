"""memsgd_kernel_ms: device milliseconds per step of the fused Mem-SGD
kernel (``repro.kernels.fused_memsgd``: ``u = m + eta*g``, the per-row
top-k and the residual in one pass). Its Pallas call is named
``memsgd_fused``, and the compiled step names the kernel's custom call
after it (``memsgd_fused`` or ``memsgd_fused.<n>``), so the trace shows
it under that name. 0 where the step selects in XLA instead. The kernel's
frames are the sync engine's, so its time is part of ``sync_ms`` too.
Layer: sync engine."""
import re

KERNEL_OP = re.compile(r"^memsgd_fused(\.\d+)?$")


def read(ctx):
    return ctx.per_step_ms(lambda op: KERNEL_OP.match(op.name) is not None)
