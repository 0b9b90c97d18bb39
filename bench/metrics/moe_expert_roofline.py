"""Kernels: the grouped expert matmuls' share of their roofline in the
decode program. The least time of the window's decode steps, from the
engine's counters of routed rows and experts hit (the model module's
``moe_expert_cost``: 6 d ff FLOPs a row, each hit expert's three matrices
read once, the rows in and out), over the traced device time of the
``gmm`` kernels (the Pallas grouped matmul under the ``moe_experts``
scope), per program run. None where the engine counts no expert rows."""


def read(run):
    c = getattr(run, "counters", None) or {}
    if not c.get("moe_rows") or not c.get("decode_steps"):
        return None
    flops, nbytes = run.model.moe_expert_cost(
        c["moe_rows"], c["moe_experts_hit"], run.sizes)
    steps = c["decode_steps"]
    return run.kernel_roofline("jit_decode_step", "gmm",
                               [[(flops / steps, nbytes / steps)]])
