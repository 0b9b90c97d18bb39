"""KV cache: device time per run of the decode program (``jit_decode_step``)
in ops under the ``kv_cache`` named scope (every write of new K/V into the
page pool). None where no op of the trace carries the scope."""


def read(run):
    eng = getattr(run, "engine", None) or {}
    secs = eng.get("scopes", {}).get("jit_decode_step", {}).get("kv_cache")
    runs = eng.get("runs", {}).get("jit_decode_step")
    return 1000.0 * secs / runs if secs and runs else None
