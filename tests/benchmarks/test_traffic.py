"""The open-loop generator offers the same load whatever the seed."""

import json
import os
import sys
from collections import Counter

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import open_loop  # noqa: E402

SEEDS = [0, 1, 7, 42, 1234, 99991, 2 ** 31 - 1, 2 ** 31 + 11,
         3000000019, 4000000007]
HORIZON = 53.0


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "chat_steady.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", SEEDS)
def test_every_block_holds_the_stated_lengths_and_spans_block_over_rate(
        traffic, seed):
    block, rate = traffic["block"], traffic["rate"]
    span = block / rate
    # a whole number of blocks, so that none is cut
    requests = open_loop.schedule(traffic, seed, 10 * span)
    assert len(requests) == 10 * block
    want_p = Counter({int(k): v for k, v in
                      traffic["prompt_lengths"].items()})
    want_o = Counter({int(k): v for k, v in
                      traffic["output_lengths"].items()})
    for b in range(10):
        chunk = requests[b * block:(b + 1) * block]
        assert Counter(r[1] for r in chunk) == want_p
        assert Counter(r[2] for r in chunk) == want_o
        # the block's last arrival lands exactly on the block's end
        assert chunk[-1][0] == pytest.approx((b + 1) * span, abs=1e-9)
        assert chunk[0][0] > b * span
    dues = [r[0] for r in requests]
    assert dues == sorted(dues)


def test_offered_load_differs_between_seeds_by_less_than_one_block(traffic):
    block = traffic["block"]
    one_block_out = sum(int(k) * v
                        for k, v in traffic["output_lengths"].items())
    one_block_in = sum(int(k) * v
                       for k, v in traffic["prompt_lengths"].items())
    offered = [open_loop.offered_tokens_per_s(
        open_loop.schedule(traffic, seed, HORIZON), HORIZON)
        for seed in SEEDS]
    ins = [o[0] for o in offered]
    outs = [o[1] for o in offered]
    assert max(ins) - min(ins) < one_block_in / HORIZON
    assert max(outs) - min(outs) < one_block_out / HORIZON
    counts = [len(open_loop.schedule(traffic, s, HORIZON)) for s in SEEDS]
    assert max(counts) - min(counts) < block


def test_the_seed_changes_which_request_is_which(traffic):
    a = open_loop.schedule(traffic, 1, HORIZON)
    b = open_loop.schedule(traffic, 2, HORIZON)
    assert a != b
    assert open_loop.schedule(traffic, 1, HORIZON) == a
    ids_a = open_loop.token_ids(a[:3], 1, 50257)
    ids_b = open_loop.token_ids(a[:3], 2, 50257)
    assert [len(x) for x in ids_a] == [r[1] for r in a[:3]]
    assert any((x != y).any() for x, y in zip(ids_a, ids_b))
    assert all(0 <= x.min() and x.max() < 50257 for x in ids_a)


def test_weights_must_add_up_to_the_block(traffic):
    bad = dict(traffic, block=traffic["block"] + 1)
    with pytest.raises(ValueError, match="add up to block"):
        open_loop.schedule(bad, 0, 10.0)
