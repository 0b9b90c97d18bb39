"""A configuration brings its model as a module of its own.

* The dense module gives, at smoke size on the CPU, the weights and the
  reference's gaps that the harness gave before the model moved into
  ``bench/models/dense.py``: the digests below were recorded then, from
  the same seed, prompt and served tokens.
* A test-only module (``windowed/``: sliding-window and full layers in
  turn, ``head_dim`` not ``hidden_size / num_attention_heads``), laid out
  in a root of its own with its configuration, cell and a metric that
  reads an engine counter, runs through ``run.run_cell`` and passes the
  check, with no file of the harness changed.
"""
import hashlib
import json
import shutil

import jax
import numpy as np
import pytest

import spec
import tiny

HERE = spec.BENCH / "tests" / "windowed"

DIGESTS = {
    "qwen2-1.5b.decode-long": {
        "weights": "2263968d9131e9b56cc3a1196f6aef1ed80cdfa7fd55a343e03c64aa"
                   "8e039c34",
        "fp32": "08f0c753b7345308aa4be04ff25952e6562fc1d36760813ba78e1f6c5b"
                "0d3564",
        "fp8": "1107ef6dd082ea75593bcc148d25f52f6e33dd884848f405fbe54c33a906"
               "4e4a"},
    "granite-3-2b.chat-bursty": {
        "weights": "dbf613cd673841c7af7f2cdb4fd01a91018e039f1a3a6a18894831074"
                   "de6b838",
        "fp32": "d5be23b6ad81ba928104e25db413427439ad454acab5d77a24fd169088"
                "dc79dd",
        "fp8": "6e0a438c52a050110d58d706dcc339a45f1acb93e37cbf3ef2744893ee4e"
               "cf99"},
}


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_dense_weights_and_reference_are_as_recorded(name):
    c = tiny.cell(name, (0.1, 0.01))
    params = c.model.make_params(c.config, 123)
    assert digest(params) == DIGESTS[name]["weights"]
    rng = np.random.default_rng(0)
    prompt, served = rng.integers(0, 512, 40), rng.integers(0, 512, 24)
    s = c.model.sizes(c.config)
    for prec in ("fp32", "fp8"):
        gap, top = c.model.logit_gaps(params, s, prompt, served, prec)
        got = hashlib.sha256(np.asarray(gap, np.float32).tobytes()
                             + np.asarray(top, np.int32).tobytes())
        assert got.hexdigest() == DIGESTS[name][prec], prec


def windowed_root(tmp_path):
    """A checkout's layout holding only the test's configuration, model,
    cell and metric, and a mix and a metric copied from the benchmark."""
    bench = tmp_path / "bench"
    shutil.copytree(HERE, bench)
    (bench / "traffic").mkdir()
    shutil.copy(spec.BENCH / "traffic" / "decode-long.json",
                bench / "traffic")
    shutil.copy(spec.BENCH / "metrics" / "decode_kernel_roofline.py",
                bench / "metrics")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "windowed",
                     "file": "bench/configs/windowed.json"}],
        "workloads": [{"name": "windowed.decode-long", "config": "windowed",
                       "traffic": "decode-long", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "output_tok_s", "unit": "tokens/s"}],
        "per_layer": [{"name": "prefill_chunk_share", "unit": "%",
                       "workloads": ["windowed.decode-long"]},
                      {"name": "decode_kernel_roofline", "unit": "%"}]}))
    return tmp_path


def test_a_new_model_runs_without_a_harness_edit(tmp_path):
    import run as bench_run
    import serve_loop
    from repro.models import transformer as T
    c = spec.load_cell("windowed.decode-long", root=windowed_root(tmp_path))
    assert c.model.__file__ == str(tmp_path / "bench/models/windowed.py")
    s = c.model.sizes(c.config)
    assert s.dk * s.h != s.d and set(s.pattern) == {"local", "global"}
    # the module's layout is the program's
    params = c.model.make_params(c.config, 5)
    mcfg = serve_loop.model_config(c.config, c.model.PUBLISHED)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), T.lm_abstract(mcfg))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), params) == want
    # a window layer's kernel call counts only its window
    local, full = c.model.decode_kernel_calls([100, 10], s)[:2]
    assert local[0] == full[0] * (s.window + 10) / 110
    r = bench_run.run_cell(c, 2**31 + 99, 3.0, True, require_tpu=False)
    assert r["correct"] is True, r["check"]
    # the metric file in the new root read the engine's counters; the
    # kernel's roofline has no device trace to read on the CPU
    assert 0 < r["metrics"]["prefill_chunk_share"]["value"] <= 100
    assert "decode_kernel_roofline" not in r["metrics"]
