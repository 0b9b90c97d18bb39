"""The reference reads the params pytree the way the program does.

At smoke size on the CPU, the program's full forward (``lm_apply`` with no
cache, float32 compute) and the dense model module's reference agree on
every logit, on weights that the module made, for both configurations'
layouts (qkv bias and rope theta included)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import spec
import tiny

DENSE = tiny.model("dense")


def small(name):
    cfg = json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())
    cfg = DENSE.tiny(cfg)
    cfg["program"]["overrides"]["compute_dtype"] = "float32"
    return cfg


@pytest.mark.parametrize("name", ["qwen2-1.5b", "granite-3-2b"])
def test_reference_matches_lm_apply(name):
    import serve_loop
    from repro.models import transformer as T
    cfg = small(name)
    params = DENSE.make_params(cfg, 123)
    mcfg = serve_loop.model_config(cfg, DENSE.PUBLISHED)
    shapes = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), T.lm_abstract(mcfg))
    assert shapes == want
    s = DENSE.sizes(cfg)
    t = 512
    toks = np.random.default_rng(0).integers(0, s.vocab, size=t)
    prog, _, _ = T.lm_apply(params, mcfg, tokens=jnp.asarray(toks)[None],
                            merged=True)
    h = DENSE.hidden(params, s, jnp.asarray(toks))
    ref = jnp.einsum("td,vd->tv", h,
                     params["embed"]["table"].astype(jnp.float32),
                     precision=reference.HI)
    prog = np.asarray(prog[0], np.float32)
    ref = np.asarray(ref)
    assert np.abs(prog - ref).max() < 1e-3 * max(1.0, np.abs(ref).max())


def test_gaps_read_served_tokens():
    cfg = small("qwen2-1.5b")
    params = DENSE.make_params(cfg, 7)
    s = DENSE.sizes(cfg)
    prompt = list(range(40))
    # greedy continuation under the reference itself: every gap is 0
    served = []
    for _ in range(5):
        seq = np.asarray(prompt + served)
        h = DENSE.hidden(params, s, jnp.asarray(
            np.pad(seq, (0, 512 - len(seq)))))
        lg = h[len(seq) - 1] @ params["embed"]["table"].astype(
            jnp.float32).T
        served.append(int(jnp.argmax(lg)))
    gap, top = DENSE.logit_gaps(params, s, prompt, served)
    assert np.all(gap < 1e-5) and list(top) == served
    wrong = [(x + 1) % s.vocab for x in served]
    gap, _ = DENSE.logit_gaps(params, s, prompt, wrong)
    assert np.all(gap > 0)
