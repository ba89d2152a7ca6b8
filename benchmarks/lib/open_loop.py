"""The general open-loop schedule generator: a traffic file of kind
``open_loop_blocks`` and a seed in, a list of timed requests out. No JAX.

Every block of ``block`` requests holds exactly the stated multiset of
prompt and output lengths and spans exactly ``block / rate`` seconds, so
the offered tokens per second are the same for every seed to within one
block. The seed shuffles the lengths inside a block, draws the gaps
before they are rescaled, and draws the token ids. Nothing else."""

import math

import numpy as np


def _multiset(weights):
    """``{"64": 8, "128": 6}`` -> [64]*8 + [128]*6."""
    out = []
    for length, count in sorted(weights.items(), key=lambda kv: int(kv[0])):
        out.extend([int(length)] * int(count))
    return out


def schedule(traffic, seed, horizon_s):
    """``[(due_s, prompt_len, n_new), ...]`` from t = 0 to ``horizon_s``,
    sorted by due time. Whole blocks are generated and the tail past the
    horizon is cut."""
    block, rate = int(traffic["block"]), float(traffic["rate"])
    prompts = _multiset(traffic["prompt_lengths"])
    outputs = _multiset(traffic["output_lengths"])
    if len(prompts) != block or len(outputs) != block:
        raise ValueError("the length weights must add up to block=%d "
                         "(prompts %d, outputs %d)"
                         % (block, len(prompts), len(outputs)))
    rng = np.random.default_rng(seed)
    span = block / rate
    requests = []
    for b in range(int(math.ceil(horizon_s / span))):
        p = rng.permutation(prompts)
        o = rng.permutation(outputs)
        gaps = rng.exponential(1.0, block)
        due = b * span + np.cumsum(gaps) * (span / gaps.sum())
        requests.extend((float(t), int(pl), int(nl))
                        for t, pl, nl in zip(due, p, o))
    return [r for r in requests if r[0] <= horizon_s + 1e-9]


def token_ids(requests, seed, vocab):
    """One int64 prompt per request, drawn from the seed (a stream of its
    own, so the lengths do not shift it)."""
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(0, vocab, size=r[1], dtype=np.int64)
            for r in requests]


def offered_tokens_per_s(requests, horizon_s):
    """(prompt, output) tokens offered per second of the horizon."""
    return (sum(r[1] for r in requests) / horizon_s,
            sum(r[2] for r in requests) / horizon_s)
