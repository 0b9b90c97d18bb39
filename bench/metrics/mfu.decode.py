"""Whole decode step: model FLOPs of the active slots' tokens (the model
module's ``decode_step_flops``) over the traced device time of the decode
program at the chip's bf16 peak."""


def read(run):
    return run.step_mfu("jit_decode_step", run.decode_flops())
