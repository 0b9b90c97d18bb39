"""Kernels: the paged ConSmax decode kernel's share of its roofline.

Least time per step, summed over the step's kernel calls (the model
module's ``decode_kernel_calls``: active slots' filled rows only), over the
traced device time of the ``consmax_decode_paged_op`` ops, per program
run."""


def read(run):
    return run.kernel_roofline("jit_decode_step", "consmax_decode_paged_op",
                               run.decode_calls())
