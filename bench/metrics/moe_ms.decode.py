"""Expert layer: device time per run of the decode program
(``jit_decode_step``) in the model's expert layers, where every layer's MLP
is its expert layer: the ops under the ``mlp`` named scope, which holds the
``moe`` scope (routing, the grouped matmuls, the weighted combine) and the
layer's pre-norm. (``engine_trace.SCOPES`` names ``mlp`` and not ``moe``,
so that is the innermost scope a trace's op is given.) None where the
engine counts no expert rows, or no op of the trace carries the scope."""


def read(run):
    if not (getattr(run, "counters", None) or {}).get("moe_rows"):
        return None
    eng = getattr(run, "engine", None) or {}
    secs = eng.get("scopes", {}).get("jit_decode_step", {}).get("mlp")
    runs = eng.get("runs", {}).get("jit_decode_step")
    return 1000.0 * secs / runs if secs and runs else None
