"""Share of the compiled step's fused-kernel dispatch decisions that fell
back to XLA for want of VMEM (the program's obs counters
``kernels.fused.{dispatch,vmem_fallback}``)."""


def read(r):
    n = r.counters.get("kernels.fused.dispatch", 0)
    if n <= 0:
        return None
    return 100.0 * r.counters.get("kernels.fused.vmem_fallback", 0) / n
