"""The closed-loop request sequence: a traffic file of kind
``closed_loop`` and a seed in, the global sequence of requests out, which
the clients draw from in order. No JAX.

Every block of ``block`` requests holds exactly the stated multiset of
prompt and output lengths, as ``open_loop.schedule`` has it, so the work
offered per request is the same for every seed to within one block. The
seed shuffles the lengths inside a block and draws the token ids.
Nothing else: there are no arrival times, a client sends its next
request when its reply arrives."""

import math

import numpy as np

from benchmarks.lib.open_loop import _multiset


def sequence(traffic, seed, n):
    """``[(prompt_len, n_new), ...]``: at least ``n`` requests, in whole
    blocks."""
    block = int(traffic["block"])
    prompts = _multiset(traffic["prompt_lengths"])
    outputs = _multiset(traffic["output_lengths"])
    if len(prompts) != block or len(outputs) != block:
        raise ValueError("the length weights must add up to block=%d "
                         "(prompts %d, outputs %d)"
                         % (block, len(prompts), len(outputs)))
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(int(math.ceil(n / block))):
        out.extend((int(p), int(o)) for p, o in
                   zip(rng.permutation(prompts), rng.permutation(outputs)))
    return out


def sequence_length(traffic, seconds):
    """How many requests to build before the ramp: what the stated
    ``max_req_s`` (well above what the system sustains) would complete
    in the ramp and the window, and a block for every client beyond."""
    return int(math.ceil((traffic["ramp_s"] + seconds)
                         * traffic["max_req_s"])) \
        + int(traffic["clients"]) * int(traffic["block"])
