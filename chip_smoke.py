"""Chip smoke test: ConSmax serving at qwen2-1.5b's published width on a TPU.

    python3 chip_smoke.py                  # one chip: phases A and B
    python3 chip_smoke.py --chips 4        # a four-chip host: sharded engine
    python3 chip_smoke.py --decode-timing  # one chip: the paged decode
                                           # kernel alone, timed

Phase A compiles each of the four ConSmax serving kernels (decode, paged
decode, prefill, paged prefill) with Mosaic at qwen2-1.5b widths, with a
bf16 and an int8 KV cache, runs them on the chip and compares each with its
jnp reference (``ref.py``, evaluated at float32 matmul precision) within
``TOL``.

Phase B builds a paged ``ContinuousBatchingEngine`` through
``repro.launch.serve`` -- the serving CLI's own flags and engine builder,
both kernels on, random weights from ``--seed`` -- compiles its prefill and
decode steps (their HLO must hold Mosaic's ``tpu_custom_call``: no
interpreted kernel or jnp walk stands in), serves 8 requests with prompts
of 128-3000 tokens drawn from ``--seed`` for 32 greedy tokens each, and
checks that every request got its tokens, all inside the vocabulary, and
that each step compiled once.

``--chips 4`` runs only the sharded path: the same requests on one device,
then on a 2x2 mesh (tp=2 over the 2 KV heads, seq_shards=2) in this one
process. It checks that the mesh engine's weights and caches span all four
devices and that the greedy tokens are identical to the one-device run, and
reports the largest difference between the two runs' first decode logits.
On a TPU v5e that identity does not hold today: the tensor-parallel axis
changes the logits (see the README's "Sharded serving"), so this phase
fails there. It serves through the host-sampling path, which returns the
logits, so it is slow: about eight minutes on a four-chip v5e host.

``--decode-timing`` times only the paged decode kernel at the shapes of
the benchmark's ``qwen2-1.5b.decode-long`` cell: 24 slots, a 32-column
table of 256-row pages in random pool order, contexts spread over
2,100-7,400 rows, bf16 K/V at qwen2-1.5b widths, 28 calls (one per
layer) in one compiled program. It prints milliseconds per call,
microseconds per live page and the kernel's share of its roofline by
``bench/costs.decode_kernel``'s count and ``bench/peaks.json``.

Earlier lines are facts about the run: device, compile seconds, wall time,
tokens, peak device memory. None of them is a speed claim. The last line is
``{"ok": true, "device": {...}}``. Any failure exits non-zero before it, and
so does a run that finds no TPU or no ``src/repro`` beside this file.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen2-1.5b"
SLOTS, MAX_SEQ, PAGE, CHUNK = 8, 4096, 256, 512
REQUESTS, NEW_TOKENS, PROMPT_LENS = 8, 32, (128, 3000)
# kernel vs fp32 reference: the kernel rounds p to bf16 before p @ v and its
# output to bf16, each a relative 2^-8; allow twice that on sum|p*v| + |out|
TOL = 2.0 ** -7


def fail(msg: str):
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def say(msg: str):
    print(msg, flush=True)


# ------------------------------------------------------ phase A: kernels ----
def _kernel_cases(cfg, seed):
    """(name, kernel fn, kernel args, reference) for the four kernels, each
    with a bf16 and an int8 cache. ``reference(f)`` evaluates the jnp
    reference with ``f`` applied to V: identity for the expected output,
    ``abs`` for sum|p*v|, the scale of the tolerance."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import cache_layout as CL
    from repro.kernels.consmax_decode.kernel import (consmax_decode,
                                                     consmax_decode_paged)
    from repro.kernels.consmax_decode.ref import consmax_decode_ref
    from repro.kernels.consmax_prefill.kernel import (consmax_prefill,
                                                      consmax_prefill_paged)
    from repro.kernels.consmax_prefill.ref import consmax_prefill_ref

    H, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    npg = MAX_SEQ // PAGE
    pages = SLOTS * npg
    key = jax.random.key(seed)

    def rnd(i, shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.float32)

    def gather(pool):                   # page pool -> contiguous slot view
        return pool[table].reshape(SLOTS, MAX_SEQ, hkv, d)

    beta = jnp.linspace(0.5, 2.5, H, dtype=jnp.float32)
    gamma = jnp.full((H,), 2.0, jnp.float32)
    kw = dict(window=cfg.window, softcap=cfg.attn_softcap)
    lens = jnp.linspace(1, MAX_SEQ, SLOTS).round().astype(jnp.int32)
    table = jax.random.permutation(jax.random.fold_in(key, 9),
                                   pages).astype(jnp.int32).reshape(SLOTS,
                                                                    npg)
    index = jnp.asarray([0, MAX_SEQ - CHUNK - 3], jnp.int32)
    chunk_lens = jnp.asarray([CHUNK, CHUNK // 2 - 3], jnp.int32)
    qd = rnd(1, (SLOTS, H, d)).astype(jnp.bfloat16)
    qp = rnd(2, (2, CHUNK, H, d)).astype(jnp.bfloat16)
    cases = []
    for kv_dtype in ("bfloat16", "int8"):
        dt = CL.kv_cache_dtype(kv_dtype)

        def cache(i, shape):
            x = rnd(i, shape)
            if not CL.kv_quantized(dt):
                x = x.astype(dt)
                return x, None, x
            codes, scale = CL.quantize_kv(x, dt)
            # the reference sees exactly the values the kernel multiplies
            return codes, scale, CL.dequantize_kv(codes, scale, jnp.bfloat16)

        k, ks, kref = cache(3, (SLOTS, MAX_SEQ, hkv, d))
        v, vs, vref = cache(4, (SLOTS, MAX_SEQ, hkv, d))
        kp, kps, kpref = cache(5, (pages, PAGE, hkv, d))
        vp, vps, vpref = cache(6, (pages, PAGE, hkv, d))
        scales = dict(k_scale=ks, v_scale=vs)
        # the paged kernels read stacked pools at a layer: here one layer
        pscales = dict(k_scale=None if kps is None else kps[None],
                       v_scale=None if vps is None else vps[None])

        def dref(kc, vc):
            return lambda vv: consmax_decode_ref(
                qd, kc.swapaxes(1, 2), vv(vc).swapaxes(1, 2), lens, beta,
                gamma, **kw)

        def pref(kc, vc, rows):
            return lambda vv: consmax_prefill_ref(
                qp, kc[rows], vv(vc)[rows], index, chunk_lens, beta, gamma,
                **kw)

        two = slice(0, 2)
        cases += [
            (f"decode/{kv_dtype}", consmax_decode,
             (qd, k, v, lens, beta, gamma), scales, dref(kref, vref)),
            (f"decode_paged/{kv_dtype}", consmax_decode_paged,
             (qd, kp[None], vp[None], 0, table, lens, beta, gamma), pscales,
             dref(gather(kpref), gather(vpref))),
            (f"prefill/{kv_dtype}", consmax_prefill,
             (qp, k[two], v[two], index, chunk_lens, beta, gamma),
             dict(k_scale=None if ks is None else ks[two],
                  v_scale=None if vs is None else vs[two]),
             pref(kref, vref, two)),
            (f"prefill_paged/{kv_dtype}", consmax_prefill_paged,
             (qp, kp[None], vp[None], 0, table[two], index, chunk_lens, beta,
              gamma),
             pscales, pref(gather(kpref), gather(vpref), two)),
        ]
    return [(name, functools.partial(fn, interpret=False, **kw, **sc), args,
             ref) for name, fn, args, sc, ref in cases]


def phase_kernels(cfg, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    for name, fn, args, ref in _kernel_cases(cfg, seed):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        secs = time.perf_counter() - t0
        if "tpu_custom_call" not in compiled.as_text():
            fail(f"phase A {name}: no tpu_custom_call in the compiled HLO")
        got = np.asarray(compiled(*args), np.float32)
        with jax.default_matmul_precision("float32"):
            want = np.asarray(ref(lambda x: x), np.float32)
            mag = np.asarray(ref(jnp.abs), np.float32)
        if got.shape != want.shape or not np.isfinite(got).all():
            fail(f"phase A {name}: shape {got.shape} vs {want.shape} or "
                 "non-finite output")
        err = np.abs(got - want)
        ratio = float((err / (TOL * (mag + np.abs(want)) + 1e-30)).max())
        say(f"phase A {name}: compile {secs:.2f} s, max |err| "
            f"{float(err.max()):.3e}, max |err|/tol {ratio:.3f}")
        if ratio > 1.0:
            fail(f"phase A {name}: kernel differs from its jnp reference "
                 f"by {ratio:.3f}x the tolerance")


# ------------------------------------------------ the decode kernel alone ----
DT_SLOTS, DT_COLUMNS, DT_CONTEXTS, DT_CALLS = 24, 32, (2100, 7400), 28


def decode_timing(cfg, dev, seed):
    """The paged decode kernel alone at decode-long's shapes: one line of
    JSON with its time per call, per live page and its roofline share."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, str(ROOT))
    from bench import costs
    from repro.kernels.consmax_decode.kernel import consmax_decode_paged

    H, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    pages = DT_SLOTS * DT_COLUMNS
    rng = np.random.default_rng(seed)
    lens = np.linspace(*DT_CONTEXTS, DT_SLOTS).round().astype(np.int32)
    rng.shuffle(lens)
    table = rng.permutation(pages).astype(np.int32).reshape(DT_SLOTS,
                                                            DT_COLUMNS)
    live = -(-lens // PAGE)
    for i, n in enumerate(live):
        table[i, n:] = -1                    # unmapped past the fill
    key = jax.random.key(seed)

    def rnd(i, shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.float32).astype(jnp.bfloat16)

    args = (rnd(1, (DT_SLOTS, H, d)), rnd(2, (1, pages, PAGE, hkv, d)),
            rnd(3, (1, pages, PAGE, hkv, d)), jnp.asarray(0, jnp.int32),
            jnp.asarray(table),
            jnp.asarray(lens), jnp.linspace(0.5, 2.5, H, dtype=jnp.float32),
            jnp.full((H,), 2.0, jnp.float32))

    def calls(q, *rest):
        # each call's q depends on the last output, so none is hoisted
        def body(_, acc):
            qi = (q.astype(jnp.float32) + acc * 1e-30).astype(q.dtype)
            return acc + consmax_decode_paged(
                qi, *rest, scale=1.0, interpret=False).astype(jnp.float32)
        return jax.lax.fori_loop(0, DT_CALLS, body,
                                 jnp.zeros(q.shape, jnp.float32))

    compiled = jax.jit(calls).lower(*args).compile()
    compiled(*args).block_until_ready()
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        compiled(*args).block_until_ready()
        times.append(time.perf_counter() - t0)
    per_call = float(np.median(times)) / DT_CALLS
    shape = types.SimpleNamespace(h=H, hkv=hkv, dk=d)
    flops, nbytes = costs.decode_kernel(lens.tolist(), shape)
    least, bound = costs.least_time(flops, nbytes,
                                    costs.peaks(dev.device_kind))
    say(json.dumps({
        "decode_timing": {"slots": DT_SLOTS, "columns": DT_COLUMNS,
                          "live_pages": int(live.sum()), "calls": DT_CALLS},
        "ms_per_call": per_call * 1e3,
        "us_per_live_page": per_call / int(live.sum()) * 1e6,
        "roofline_pct": 100.0 * least / per_call, "bound": bound}))


# ------------------------------------------------------- phase B: engine ----
def engine_flags(seed, *extra):
    return ["--arch", ARCH, "--published", "--engine", "continuous",
            "--paged", "--page-size", str(PAGE), "--max-slots", str(SLOTS),
            "--max-seq", str(MAX_SEQ), "--prefill-chunk", str(CHUNK),
            "--decode-kernel", "--prefill-kernel", "--seed", str(seed),
            *extra]


def make_prompts(vocab, seed):
    import numpy as np

    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, size=REQUESTS)
    return [rng.integers(0, vocab, size=int(n)).tolist() for n in lens]


def serve(flags, seed, label, *, mesh_devices=0, first_logits=False):
    """Build the engine the serving CLI builds for ``flags``, compile its
    steps, serve the smoke requests; returns (tokens per request, first
    decode step's (active mask, logits) when ``first_logits``)."""
    import jax
    import numpy as np

    from repro.analysis.trace_guard import TraceGuard
    from repro.launch import serve as serve_cli

    eng = serve_cli.build_engine(serve_cli.parse_args(flags))
    if mesh_devices:
        spans = {len(leaf.sharding.device_set) for leaf in
                 jax.tree.leaves((eng.params, eng.caches))}
        if eng.plan is None or eng.plan.mesh.devices.size != mesh_devices \
                or spans != {mesh_devices}:
            fail(f"{label}: mesh does not span {mesh_devices} devices "
                 f"(leaves span {sorted(spans)})")
    for name in ("prefill", "decode"):
        t0 = time.perf_counter()
        hlo = eng.compile_step(name).as_text()
        say(f"{label}: {name} step compile {time.perf_counter() - t0:.1f} s")
        if "tpu_custom_call" not in hlo:
            fail(f"{label}: the compiled {name} step holds no "
                 "tpu_custom_call -- a kernel did not compile with Mosaic")
    guard = TraceGuard.for_engine(eng)
    seen = []
    if first_logits:
        decode = eng._decode

        def record(params, caches, inputs, sampling):
            out = decode(params, caches, inputs, sampling)
            if not seen:
                seen.append((np.asarray(inputs["active"]),
                             np.asarray(out[0], np.float32)))
            return out

        eng._decode = record
    prompts = make_prompts(eng.cfg.vocab_size, seed)
    uids = [eng.submit(p, NEW_TOKENS) for p in prompts]
    t0 = time.perf_counter()
    results = eng.run()
    wall = time.perf_counter() - t0
    tokens = [results.get(u, []) for u in uids]
    n = sum(len(t) for t in tokens)
    say(f"{label}: {len(prompts)} requests, {sum(map(len, prompts))} prompt "
        f"tokens, {n} generated tokens in {wall:.2f} s wall (first "
        f"dispatches included)")
    counts = guard.counts()
    say(f"{label}: compilations per step {counts}")
    if any(len(t) != NEW_TOKENS for t in tokens):
        fail(f"{label}: requests returned {[len(t) for t in tokens]} tokens, "
             f"expected {NEW_TOKENS} each")
    if not all(0 <= x < eng.cfg.vocab_size for t in tokens for x in t):
        fail(f"{label}: a token lies outside the vocabulary")
    if counts["prefill_step"] != 1 or counts["decode_step"] != 1:
        fail(f"{label}: expected one compile per step, got {counts}")
    guard.assert_ok()
    if mesh_devices and {len(leaf.sharding.device_set) for leaf in
                         jax.tree.leaves(eng.caches)} != {mesh_devices}:
        fail(f"{label}: caches left the {mesh_devices}-device mesh")
    del eng
    gc.collect()
    return tokens, (seen[0] if seen else None)


def peak_memory(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the sharded engine on a 2x2 mesh, "
                         "compared with one device")
    ap.add_argument("--decode-timing", action="store_true",
                    help="time only the paged decode kernel at the "
                         "decode-long cell's shapes")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, kernel inputs and requests")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no src/repro beside {Path(__file__).name}: run it from a "
             "checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    from repro.configs.registry import get_config
    from repro.launch.compile_cache import enable_compile_cache

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (platform {dev.platform!r}); this test "
             "runs on the chip only")
    if len(devs) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, found "
             f"{len(devs)}")
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    say(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH)
    say(f"model: {ARCH} published width (d_model {cfg.d_model}, "
        f"{cfg.n_layers} layers, {cfg.n_heads} heads, {cfg.n_kv_heads} KV "
        f"heads, head_dim {cfg.head_dim_}, vocab {cfg.vocab_size}), random "
        f"weights from seed {args.seed}")

    t_all = time.perf_counter()
    if args.decode_timing:
        decode_timing(cfg, dev, args.seed)
    elif args.chips == 1:
        phase_kernels(cfg, args.seed)
        serve(engine_flags(args.seed), args.seed, "phase B")
    else:
        one, lg1 = serve(engine_flags(args.seed, "--host-sampling"),
                         args.seed, "one device", first_logits=True)
        four, lg4 = serve(engine_flags(args.seed, "--host-sampling",
                                       "--mesh", "2x2"),
                          args.seed, "mesh 2x2", mesh_devices=4,
                          first_logits=True)
        (act1, l1), (act4, l4) = lg1, lg4
        if not (act1 == act4).all():
            fail("the two runs' first decode steps ran different slots")
        diff = float(abs(l1[act1] - l4[act4]).max())
        same = sum(a == b for a, b in zip(one, four))
        say(f"mesh 2x2 vs one device: first decode step max |logit diff| "
            f"{diff:.6g}; {same}/{len(one)} requests with identical tokens")
        if one != four:
            fail("greedy tokens on the 2x2 mesh differ from one device")
    say(f"total wall {time.perf_counter() - t_all:.1f} s; device 0 peak "
        f"bytes in use {peak_memory(dev)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}),
        flush=True)


if __name__ == "__main__":
    main()
