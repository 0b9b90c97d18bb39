"""Kernels: the paged ConSmax prefill kernel's share of its roofline.

Least time per chunk, summed over the chunk's kernel calls (the model
module's ``prefill_kernel_calls``: filled rows only), over the traced
device time of the ``consmax_prefill_paged_op`` ops, per program run."""


def read(run):
    return run.kernel_roofline("jit_prefill_chunk_step",
                               "consmax_prefill_paged_op",
                               run.prefill_calls())
