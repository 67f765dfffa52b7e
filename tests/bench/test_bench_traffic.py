"""Traffic generators: the same seed gives the same inputs, and every
seed the same set of sizes and arrivals in another order."""
import numpy as np
import pytest

import harness

ROLLOUT = harness.load_cell("rollout.qwen2.5-0.5b")
# An open-loop cell as a chat cell would set it (no such cell is declared
# until its rate is found on the chip).
CHAT = {"name": "chat.test", "traffic": "chat",
        "clients": {"rate_per_s": 2.0},
        "engine": {"max_batch": 64, "max_seq_len": 2560}}
BIG = 2 ** 33 + 12345        # past 32 signed bits, as the checks draw


def _closed(seed):
    mix = harness.load_mix(ROLLOUT["traffic"])
    gen = harness.load_module("traffic", mix["generator"])
    stream = gen.plan(mix, ROLLOUT, seed, 151936, 45)
    return stream, [stream.group(g) for g in range(stream.n)]


def _open(seed, seconds=45):
    mix = harness.load_mix(CHAT["traffic"])
    gen = harness.load_module("traffic", mix["generator"])
    return gen.plan(mix, CHAT, seed, 151936, seconds)


def test_closed_groups_deterministic_per_seed():
    _, a = _closed(BIG)
    _, b = _closed(BIG)
    _, c = _closed(BIG + 1)
    flat = lambda gs: [(s.prompt.tobytes(), s.max_new) for g in gs  # noqa
                       for s in g]
    assert flat(a) == flat(b)
    assert flat(a) != flat(c)


def test_closed_groups_same_sizes_for_every_seed():
    # the same groups (a prompt length with its budgets) for every seed,
    # sent in another order
    sets, orders = [], []
    for seed in (1, BIG):
        _, groups = _closed(seed)
        shape = [(len(g[0].prompt), tuple(s.max_new for s in g))
                 for g in groups]
        sets.append(sorted(shape))
        orders.append(shape)
    assert sets[0] == sets[1]
    assert orders[0] != orders[1]
    stream, groups = _closed(3)
    k = harness.load_mix("rollout")["completions_per_prompt"]
    assert all(len(g) == k for g in groups)
    assert all(len({s.prompt.tobytes() for s in g}) == 1 for g in groups)
    # the stream never runs dry and repeats the sizes with fresh ids
    again = stream.group(stream.n)
    assert len(again[0].prompt) == len(groups[0][0].prompt)
    assert not np.array_equal(again[0].prompt, groups[0][0].prompt)


def test_lengths_within_the_mix_and_the_engine():
    mix = harness.load_mix("rollout")
    _, groups = _closed(5)
    for g in groups:
        p = len(g[0].prompt)
        assert mix["prompt_tokens"]["min"] <= p <= mix["prompt_tokens"]["max"]
        for s in g:
            assert p + s.max_new <= ROLLOUT["engine"]["max_seq_len"]
            assert 3 <= s.prompt.min() and s.prompt.max() < 151936


def test_open_poisson_deterministic_and_fixed_work():
    a, b, c = _open(BIG), _open(BIG), _open(7)
    key = lambda xs: [(s.due, s.prompt.tobytes(), s.max_new)  # noqa: E731
                      for s in xs]
    assert key(a) == key(b) and key(a) != key(c)
    n = round(CHAT["clients"]["rate_per_s"] * 45)
    assert len(a) == len(c) == n
    assert sorted(len(s.prompt) for s in a) == sorted(len(s.prompt)
                                                      for s in c)
    assert sorted(s.max_new for s in a) == sorted(s.max_new for s in c)
    dues = [s.due for s in a]
    assert dues == sorted(dues) and dues[0] == 0.0 and dues[-1] < 45
    gaps = np.diff(dues + [45.0])
    assert sorted(gaps) == pytest.approx(sorted(np.diff(
        [s.due for s in c] + [45.0])))
    for s in a:
        assert s.max_new + len(s.prompt) <= CHAT["engine"]["max_seq_len"]
