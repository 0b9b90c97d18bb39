"""The reference reads the params pytree the way the program does.

At smoke size on the CPU, the program's full forward (``lm_apply`` with no
cache, float32 compute) and the reference agree on every logit, on weights
that ``weights.py`` made, for both configurations' layouts (qkv bias and
rope theta included)."""
import copy
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import spec
import weights


def tiny(name):
    cfg = json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())
    cfg = copy.deepcopy(cfg)
    cfg.update(hidden_size=64, intermediate_size=96, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, vocab_size=300)
    cfg["program"]["overrides"].update(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=96,
        vocab_size=300, head_dim=0, compute_dtype="float32")
    return cfg


@pytest.mark.parametrize("name", ["qwen2-1.5b", "granite-3-2b"])
def test_reference_matches_lm_apply(name):
    import serve_loop
    from repro.models import transformer as T
    cfg = tiny(name)
    params = weights.make_params(cfg, 123)
    mcfg = serve_loop.model_config(cfg)
    shapes = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), T.lm_abstract(mcfg))
    assert shapes == want
    t = 512
    toks = np.random.default_rng(0).integers(0, 300, size=t)
    prog, _, _ = T.lm_apply(params, mcfg, tokens=jnp.asarray(toks)[None],
                            merged=True)
    s = reference.Sizes(**weights.sizes(cfg))
    h = reference.hidden(params, s, jnp.asarray(toks))
    ref = jnp.einsum("td,vd->tv", h,
                     params["embed"]["table"].astype(jnp.float32),
                     precision=reference.HI)
    prog = np.asarray(prog[0], np.float32)
    ref = np.asarray(ref)
    assert np.abs(prog - ref).max() < 1e-3 * max(1.0, np.abs(ref).max())


def test_gaps_read_served_tokens():
    cfg = tiny("qwen2-1.5b")
    params = weights.make_params(cfg, 7)
    s = reference.Sizes(**weights.sizes(cfg))
    prompt = list(range(40))
    # greedy continuation under the reference itself: every gap is 0
    served = []
    for _ in range(5):
        seq = np.asarray(prompt + served)
        h = reference.hidden(params, s, jnp.asarray(
            np.pad(seq, (0, 512 - len(seq)))))
        lg = h[len(seq) - 1] @ params["embed"]["table"].astype(
            jnp.float32).T
        served.append(int(jnp.argmax(lg)))
    gap, top = reference.logit_gaps(params, s, prompt, served)
    assert np.all(gap < 1e-5) and list(top) == served
    wrong = [(x + 1) % 300 for x in served]
    gap, _ = reference.logit_gaps(params, s, prompt, wrong)
    assert np.all(gap > 0)
