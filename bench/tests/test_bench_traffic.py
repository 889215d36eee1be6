"""The traffic generator: the same seed gives the same schedule, and
every seed gets the same work (pool, lengths, gaps) in another order."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

MIXES = Path(__file__).parents[1] / "traffic"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def schedule(reqs, vocab=1000):
    return [(r.prompt_len, r.max_new, r.due, r.tokens(vocab).tobytes())
            for r in reqs]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_same_seed_same_open_loop_schedule(seed):
    a = traffic.open_loop(mix("chat"), seed, 20.0)
    b = traffic.open_loop(mix("chat"), seed, 20.0)
    assert schedule(a) == schedule(b)


def test_seeds_change_the_order_not_the_work():
    m = mix("chat")
    a = traffic.open_loop(m, 1, 30.0)
    b = traffic.open_loop(m, 2, 30.0)
    assert schedule(a) != schedule(b)
    assert sorted(r.prompt_len for r in a) == \
        sorted(r.prompt_len for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    gaps = lambda rs: sorted(np.diff([r.due for r in rs]).round(9))  # noqa
    assert len(a) == len(b)
    # the same multiset of gaps, one of which is the last (dropped) one
    assert len(set(gaps(a)) ^ set(gaps(b))) <= 2


@pytest.mark.parametrize("name", ["chat", "longprompt"])
def test_the_pool_is_the_same_for_every_seed(name):
    m = mix(name)
    pool = traffic.prompt_pool(m["prompt"])
    assert len(pool) == m["prompt"].get("pool",
                                        len(m["prompt"].get("lengths", [])))
    if m["loop"] == "open":
        lens = {r.prompt_len for s in (3, 4, 5)
                for r in traffic.open_loop(m, s, 51.0)}
    else:
        lens = set()
        for s in (3, 4):
            it = traffic.closed_loop(m, s)
            lens |= {next(it).prompt_len for _ in range(50)}
    assert lens == set(pool)


def test_chat_pool_is_the_stated_lognormal_quantiles():
    # median 108, sigma 1.0, quantiles 1/16, 3/16 ... 15/16
    assert traffic.prompt_pool(mix("chat")["prompt"]) == \
        [23, 44, 66, 92, 126, 176, 262, 501]


def test_chat_lengths_have_the_sharegpt_means():
    m = mix("chat")
    assert np.mean(traffic.prompt_pool(m["prompt"])) == \
        pytest.approx(161.31, abs=0.5)
    outs = traffic.output_lengths(m["output"], traffic.MEAN_QUANTILES)
    assert np.mean(outs) == pytest.approx(337.99, abs=0.5)
    assert traffic.max_request_tokens(m) <= m["max_len"]


def test_longprompt_pool_is_the_longbench_truncation():
    m = mix("longprompt")
    assert traffic.prompt_pool(m["prompt"]) == [3500]
    assert traffic.output_lengths(m["output"], 5) == [64] * 5
    assert traffic.max_request_tokens(m) <= m["max_len"]


LOGSPACE = {"loop": "closed", "max_len": 4096,
            "prompt": {"kind": "logspace_pool", "min": 1024, "max": 3840,
                       "pool": 6},
            "output": {"kind": "uniform", "min": 16, "max": 64}}


def test_logspace_pool_is_log_spaced():
    assert traffic.prompt_pool(LOGSPACE["prompt"]) == \
        [1024, 1334, 1737, 2263, 2948, 3840]


def test_closed_loop_order_is_the_same_for_every_seed():
    m = mix("longprompt")
    a, b = traffic.closed_loop(m, 1), traffic.closed_loop(m, 2)
    ra = [next(a) for _ in range(100)]
    rb = [next(b) for _ in range(100)]
    assert [(r.prompt_len, r.max_new) for r in ra] == \
        [(r.prompt_len, r.max_new) for r in rb]
    assert ra[0].tokens(1000).tobytes() != rb[0].tokens(1000).tobytes()


def test_closed_loop_blocks_hold_every_length_once():
    m = LOGSPACE
    it = traffic.closed_loop(m, 9)
    reqs = [next(it) for _ in range(6 * 49)]
    pool = traffic.prompt_pool(m["prompt"])
    for k in range(0, len(reqs), 6):
        assert sorted(r.prompt_len for r in reqs[k:k + 6]) == pool
    assert sorted(r.max_new for r in reqs[:49]) == list(range(16, 65))


def test_prompts_share_no_prefix_block():
    reqs = traffic.open_loop(mix("chat"), 5, 30.0)
    heads = {r.tokens(122753)[:16].tobytes() for r in reqs}
    assert len(heads) == len(reqs)
