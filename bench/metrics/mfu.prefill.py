"""Whole prefill step: model FLOPs of the real prompt tokens of each chunk
(the model module's ``prefill_step_flops``) over the traced device time of
the prefill program at the chip's bf16 peak."""


def read(run):
    return run.step_mfu("jit_prefill_chunk_step", run.prefill_flops())
