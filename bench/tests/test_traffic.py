"""The traffic generator: one schedule per mix (its structure seed), the
same multiset of sizes for every structure seed, token contents from the
run seed, 128-aligned prefill chunks, and opt-30b's transcripts within
2,048 positions."""
import json
import os

import pytest

from bench.harness import client, traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TEMPLATE = client.template(50272)
BIG_SEED = 2 ** 31 + 12345


def mix(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        m = json.load(f)
    traffic.check_mix(m)
    return m


@pytest.mark.parametrize("name", ["returning_long", "returning_2k",
                                  "fresh_2k"])
def test_seeded_and_reproducible(name):
    m = mix(name)
    a = traffic.make_schedule(m, 30.0, TEMPLATE)
    b = traffic.make_schedule(m, 30.0, TEMPLATE)
    c = traffic.make_schedule(dict(m, structure_seed=m["structure_seed"]
                                   + 1), 30.0, TEMPLATE)
    assert a == b
    assert a != c
    assert len(a.turns) == round(mix(name)["rate_per_s"] * 30.0)
    # the same multiset of sizes, in another order
    assert sorted(t.answer_tokens for t in a.turns) == \
        sorted(t.answer_tokens for t in c.turns)
    assert [t.answer_tokens for t in a.turns] != \
        [t.answer_tokens for t in c.turns]
    assert sorted(x.history for x in a.sessions) == \
        sorted(x.history for x in c.sessions)


@pytest.mark.parametrize("name", ["returning_long", "returning_2k",
                                  "fresh_2k"])
@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_chunks_aligned(name, seed):
    m = dict(mix(name), structure_seed=seed)
    s = traffic.make_schedule(m, 30.0, TEMPLATE)
    align, step = m["align"], m["answer_align"]
    for c, h in s.prefill_shapes():
        assert h % align == 0
        assert c % step == 0 and 0 < c <= align
    for t in s.turns:
        assert (t.answer_tokens - 1) % step == 0
        stored_after = t.prompt_end + t.answer_tokens - 1
        if t.session >= 0:
            assert t.history % align == 0
            assert stored_after % align == 0
    for sess in s.sessions:
        assert sess.history % align == 0
        assert sess.first_user_tokens > 0


def run_seconds():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        return json.load(f)["run_seconds"]


@pytest.mark.parametrize("seed", [0, 3, BIG_SEED])
def test_opt_transcripts_fit_2048_positions(seed):
    m = dict(mix("returning_2k"), structure_seed=seed)
    s = traffic.make_schedule(m, run_seconds(), TEMPLATE)
    for t in s.turns:
        # positions 0 .. prompt_end + answer - 1 are written
        assert t.prompt_end + t.answer_tokens <= 2048
    lo, hi = m["history_tokens"]
    assert all(lo <= x.history <= hi for x in s.sessions)


def test_sessions_follow_their_history():
    s = traffic.make_schedule(mix("returning_long"), 40.0, TEMPLATE)
    hist = {i: x.history for i, x in enumerate(s.sessions)}
    for t in s.turns:
        assert t.history == hist[t.session]
        assert t.prompt_end == t.history + TEMPLATE.next + t.user_tokens
        hist[t.session] = t.prompt_end + t.answer_tokens - 1


def test_run_seed_draws_the_contents():
    s = traffic.make_schedule(mix("returning_long"), 30.0, TEMPLATE)
    a = client.Client("engine_pump", s, 50272, BIG_SEED).openings()
    b = client.Client("engine_pump", s, 50272, BIG_SEED).openings()
    c = client.Client("engine_pump", s, 50272, BIG_SEED + 1).openings()
    assert [x.history.tolist() for x in a] == [x.history.tolist() for x in b]
    assert [len(x.history) for x in a] == [len(x.history) for x in c]
    assert [x.history.tolist() for x in a] != [x.history.tolist() for x in c]
