"""Kernels: the paged ConSmax decode kernel's share of its roofline.

Least time per step from ``costs.decode_kernel`` (active slots' filled rows
only) over the traced device time of the kernel, per program run; one
kernel call per layer."""


def read(run):
    return run.kernel_roofline("jit_decode_step", run.decode_costs())
