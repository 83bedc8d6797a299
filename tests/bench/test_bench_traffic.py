"""The traffic generator: seeded, the same set of sizes and gaps for every
seed in an order the seed draws, in bounds."""
import pytest

from bench import generator
from bench.harness import ROOT, read_json

CHAT = read_json(ROOT / "bench/traffic/chat-poisson.json")
OPEN_CELL = {"slots": 24, "rate_rps": 4.0}


def _key(t):
    return [(j.prompt, j.output_tokens, round(j.due_s, 9)) for j in t.jobs]


def test_same_seed_same_traffic_and_seeds_differ():
    a = generator.make_traffic(CHAT, OPEN_CELL, 2**33 + 5, 50, 1000)
    b = generator.make_traffic(CHAT, OPEN_CELL, 2**33 + 5, 50, 1000)
    c = generator.make_traffic(CHAT, OPEN_CELL, 2**33 + 6, 50, 1000)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


def test_every_seed_puts_the_same_schedule_on_the_system():
    a = generator.make_traffic(CHAT, OPEN_CELL, 1, 50, 1000)
    b = generator.make_traffic(CHAT, OPEN_CELL, 2**40 + 2, 50, 1000)

    def sizes(t):
        return [(len(j.prompt), j.output_tokens) for j in t.jobs]

    # the same set of prompt and output lengths, in another order
    assert sorted(len(j.prompt) for j in a.jobs) == \
        sorted(len(j.prompt) for j in b.jobs)
    assert sorted(j.output_tokens for j in a.jobs) == \
        sorted(j.output_tokens for j in b.jobs)
    assert sizes(a) != sizes(b)
    # the same set of gaps, in another order: the schedules differ, and
    # each seed's last arrival is the same set's sum less its first gap
    gaps = generator.exponential_gaps(OPEN_CELL["rate_rps"], len(a.jobs))
    for t in (a, b):
        due = [j.due_s for j in t.jobs]
        assert due[0] == 0.0
        assert any(due[-1] == pytest.approx(gaps.sum() - g) for g in gaps)
    assert [j.due_s for j in a.jobs] != [j.due_s for j in b.jobs]


def test_a_mix_names_an_arrival_process_the_generator_knows():
    assert CHAT["arrival"] in generator.ARRIVALS
    with pytest.raises(ValueError, match="arrival"):
        generator.make_traffic({**CHAT, "arrival": "bursty"}, OPEN_CELL, 1,
                               50, 1000)


def test_open_loop_spans_the_window_within_the_clamps():
    t = generator.make_traffic(CHAT, OPEN_CELL, 7, 50, 1000)
    assert len(t.jobs) == 200
    dues = [j.due_s for j in t.jobs]
    assert dues == sorted(dues) and 45.0 < dues[-1] < 50.0
    for j in t.jobs:
        assert 8 <= len(j.prompt) <= 500 and 8 <= j.output_tokens <= 500
        assert all(0 <= tok < 1000 for tok in j.prompt)
    median = sorted(j.output_tokens for j in t.jobs)[100]
    assert 55 <= median <= 75  # log-normal median 64


def test_quantiles_follow_the_lognormal():
    q = generator.lognormal_quantiles(
        {"median": 64, "sigma": 0.8, "min": 1, "max": 10**6}, 1001)
    assert q[500] == 64 and list(q) == sorted(q)
    gaps = generator.exponential_gaps(4.0, 10000)
    assert abs(gaps.mean() - 0.25) < 0.01


@pytest.mark.parametrize("seed", [11, 2**35 + 3])
def test_order_is_stratified_so_long_requests_spread_over_the_window(seed):
    t = generator.make_traffic(CHAT, OPEN_CELL, seed, 50, 1000)
    sizes = sorted(len(j.prompt) for j in t.jobs)
    top = sizes[-len(sizes) // generator.STRATA]  # the longest tenth
    for b in range(0, len(t.jobs), generator.STRATA):
        block = t.jobs[b:b + generator.STRATA]
        assert sum(len(j.prompt) >= top for j in block) <= 2
