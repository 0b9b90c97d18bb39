"""The comparison that decides ``correct``.

Once the window has closed and the engine's state is freed, a sample of the
requests the engine finished, drawn from the seed and always holding the
one with the most served tokens, is run through the model module's
reference (teacher forced on each prompt and its served tokens): ``gaps``
is its ``logit_gaps`` with the run's weights and sizes bound,
``gaps(prompt, served, prec) -> (gap, argmax)``. A greedy token served where
the reference's best logit lies far above it is wrong; rounding only lets
a served token sit a little below the best. The numbers compared:

* ``max_logit_gap``: the widest gap, over every sampled served token,
  between the reference's best logit and its logit of the served token;
* ``mean_logit_gap``: the mean of those gaps: rounding makes a few served
  tokens sit a little below the best, a lower precision makes more of them
  sit further below, so the mean separates the two more steadily than the
  widest gap alone;
* ``length_mismatches``: finished requests whose served length differs from
  their budget (no EOS: every request serves exactly its budget);
* ``out_of_vocab``: served tokens outside the vocabulary.
"""
from __future__ import annotations

import numpy as np

import loadgen

SAMPLE_TOKENS = 512      # sample at least this many served tokens ...
SAMPLE_MAX = 4           # ... from at most this many requests
SEQ_BUDGET = 16_384      # ... and at most this many prompt + served tokens


def sample(finished: dict, req_of: dict, seed: int) -> list[int]:
    """uids to compare: the longest served, then others in a seeded order,
    until SAMPLE_TOKENS are covered."""
    if not finished:
        return []
    uids = sorted(finished)
    longest = max(uids, key=lambda u: (len(finished[u]), -u))
    rng = np.random.default_rng(loadgen.seed_seq(seed, 3))
    order = [u for u in rng.permutation(uids).tolist() if u != longest]
    chosen = [longest]
    served = len(finished[longest])
    seq = served + req_of[longest].prompt_len
    for u in order:
        if served >= SAMPLE_TOKENS or len(chosen) >= SAMPLE_MAX:
            break
        n = len(finished[u]) + req_of[u].prompt_len
        if seq + n > SEQ_BUDGET:
            continue
        chosen.append(u)
        served += len(finished[u])
        seq += n
    return chosen


def compare(gaps, finished: dict, req_of: dict, seed: int, vocab: int,
            limits: dict) -> tuple[bool, dict, dict]:
    """(correct, numbers with limits, facts about the sample)."""
    mism = sum(1 for u, toks in finished.items()
               if len(toks) != req_of[u].output_len)
    oov = sum(1 for toks in finished.values()
              for t in toks if not 0 <= t < vocab)
    chosen = sample(finished, req_of, seed)
    got = []
    for u in chosen:
        req = req_of[u]
        prompt = loadgen.prompt_tokens(seed, req.index, req.prompt_len,
                                       vocab)
        served = np.clip(np.asarray(finished[u]), 0, vocab - 1)
        got.append(gaps(prompt, served)[0])
    got = np.concatenate(got) if got else np.zeros(1)
    numbers = {
        "max_logit_gap": {"value": float(got.max()),
                          "limit": limits["max_logit_gap"]},
        "mean_logit_gap": {"value": float(got.mean()),
                           "limit": limits["mean_logit_gap"]},
        "length_mismatches": {"value": mism, "limit": 0},
        "out_of_vocab": {"value": oov, "limit": 0},
    }
    ok = bool(chosen) and all(v["value"] <= v["limit"]
                              for v in numbers.values())
    facts = {"finished": len(finished), "sampled_requests": len(chosen),
             "sampled_tokens": int(got.size),
             "share_below_best": float(np.mean(got > 0))}
    return ok, numbers, facts


def control_tokens(gaps, finished: dict, req_of: dict, seed: int,
                   vocab: int) -> dict:
    """The control in the program's place: at every position the check
    reads, the float8 reference's first choice (teacher-forced on the same
    prompt and served tokens), as the tokens ``compare`` then judges."""
    out = dict(finished)
    for u in sample(finished, req_of, seed):
        req = req_of[u]
        prompt = loadgen.prompt_tokens(seed, req.index, req.prompt_len,
                                       vocab)
        served = np.clip(np.asarray(finished[u]), 0, vocab - 1)
        _, low_top = gaps(prompt, served, prec="fp8")
        out[u] = [int(t) for t in low_top]
    return out
