"""Kernels: the paged ConSmax prefill kernel's share of its roofline.

Least time per chunk from ``costs.prefill_kernel`` (filled rows only) over
the traced device time of the kernel, per program run; one kernel call per
layer."""


def read(run):
    return run.kernel_roofline("jit_prefill_chunk_step", run.prefill_costs())
