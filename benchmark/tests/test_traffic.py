"""The traffic generator and the work counts on plain data: what a seed
changes and what it leaves, which requests the rate counts, and that the
least work of a statement does not follow the number of calls the program
made of it."""

import itertools
import json
import pathlib
import types

import pytest

from benchmark.lib import harness, traffic, useful
from benchmark.work import dense as work

BENCH = pathlib.Path(__file__).resolve().parents[1]
MIX = json.loads((BENCH / "traffic" / "bon_sweep.json").read_text())
MODEL = json.loads((BENCH / "configs" / "smollm2-1.7b.json").read_text())["model"]


def first(seed, count, draw="window"):
    return list(itertools.islice(traffic.bodies(MIX, seed, draw), count))


def own(sent):
    return [r for r in sent if traffic.paper_shaped(MIX, r)]


def test_a_seed_orders_the_scenarios_and_draws_every_request_seed():
    a, b = first(7, 22), first(2 ** 31 + 9, 22)
    assert a == first(7, 22)
    # Every seed sends the same set of scenarios, a round at a time.
    for sent in (own(a), own(b)):
        for start in (0, 5, 10, 15):
            assert sorted(r["scenario"] for r in sent[start:start + 5]) == [
                f"aamas:{k}" for k in range(1, 6)]
    orders = {tuple(r["scenario"] for r in own(first(seed, 6))) for seed in range(12)}
    assert len(orders) > 4
    seeds = [r["seed"] for r in a + b]
    assert len(set(seeds)) == len(seeds)
    assert all(r["params"]["n"] == 32 for r in a)


def test_the_paper_s_parameters_letter_for_letter():
    assert MIX["request"] == {"method": "best_of_n",
                              "params": {"n": 32, "max_tokens": 50}}
    assert MIX["loop"] == {"kind": "closed", "clients": 4}
    assert MIX["scenarios"]["ids"] == [1, 2, 3, 4, 5]


def test_greedy_requests_have_their_places_and_are_not_the_mix_s_own():
    sent = first(3, 40)
    greedy = [i for i, r in enumerate(sent) if not traffic.paper_shaped(MIX, r)]
    assert greedy == [2, 18, 34]
    assert sent[2]["params"] == {"n": 32, "max_tokens": 16, "temperature": 0.0}
    # One more request on the scenario that set-up found it answered on: by
    # default the first of the seed's order, which takes no turn of it.
    order = traffic.greedy_scenarios(MIX, 3)
    assert sorted(order) == [f"aamas:{k}" for k in range(1, 6)]
    assert [r["scenario"] for r in own(sent)[:5]] == order
    assert {sent[i]["scenario"] for i in greedy} == {order[0]}
    moved = list(itertools.islice(
        traffic.bodies(MIX, 3, greedy_scenario=order[3]), 40))
    assert {moved[i]["scenario"] for i in greedy} == {order[3]}
    assert own(moved) == own(sent)


def test_set_up_sends_the_window_s_kinds_under_other_seeds():
    warm = traffic.warm_bodies(MIX, 11)
    ahead = first(11, 24)
    kinds = {(r["scenario"], json.dumps(r["params"], sort_keys=True)) for r in ahead}
    assert {(r["scenario"], json.dumps(r["params"], sort_keys=True))
            for r in warm} == kinds
    assert len(warm) == len(kinds) == 6
    assert not {r["seed"] for r in warm} & {r["seed"] for r in ahead}


def _sent(payload, sent, done, ok=True):
    one = traffic.Sent(0, payload, sent, sent)
    one.done, one.status = done, 200 if ok else 500
    one.body = {"statement": "a b", "utilities": {"x": 1}, "welfare": {"y": 1}}
    return one


def test_the_rate_and_the_median_leave_the_greedy_requests_out():
    cell = types.SimpleNamespace(traffic=MIX)
    own, greedy = first(1, 3)[0], first(1, 3)[2]
    sent = [_sent(own, 0.0, 10.0), _sent(own, 0.0, 12.0), _sent(greedy, 1.0, 3.0),
            _sent(own, 10.0, 20.0)]
    out = harness.end_to_end(cell, sent)
    assert out["statements_per_s"] == pytest.approx(3 / 20.0)
    assert out["time_to_statement_p50_s"] == pytest.approx(10.0)
    # A failed request is no statement and counts as the slowest.
    sent[0].status = 500
    out = harness.end_to_end(cell, sent)
    assert out["statements_per_s"] == pytest.approx(2 / 20.0)
    assert out["time_to_statement_p50_s"] == pytest.approx(12.0)


def _generate_call(start, end, seeds, tokens=50, prompt="p" * 100):
    request = lambda seed: types.SimpleNamespace(  # noqa: E731
        chat=False, user_prompt=prompt, system_prompt=None, seed=seed)
    return {"kind": "generate", "start": start, "end": end,
            "requests": [request(s) for s in seeds],
            "results": [types.SimpleNamespace(token_ids=(5,) * tokens)
                        for _ in seeds]}


def test_a_statement_s_work_does_not_follow_the_number_of_calls():
    base = 123456789
    one = [_generate_call(0.0, 4.0, range(base, base + 32))]
    four = [_generate_call(k, k + 1.0, range(base + 8 * k, base + 8 * k + 8))
            for k in range(4)]
    a = useful.tally(work, MODEL, one, 0.0, 4.0)["generate"]
    b = useful.tally(work, MODEL, four, 0.0, 4.0)["generate"]
    for key in ("tokens", "flops", "bytes"):
        assert a[key] == pytest.approx(b[key])
    assert (a["launches"], b["launches"]) == (1.0, 4.0)
    # By hand: the prompt once, 50 steps that read every weight once and
    # every cached position of the 32 rows once.
    p = 101  # 100 bytes and the first token
    kv = work.kv_bytes_per_token(MODEL)
    steps = sum(work.weight_bytes(MODEL) + (p + 32 * (s - 1)) * kv
                for s in range(1, 51))
    assert a["bytes"] == pytest.approx(work.weight_bytes(MODEL) + steps)
    # Another statement on the same prompt is another statement's work, and
    # half of the stretch holds half of it.
    two = one + [_generate_call(0.0, 4.0, range(5 * base, 5 * base + 32))]
    assert useful.tally(work, MODEL, two, 0.0, 4.0)["generate"]["bytes"] == \
        pytest.approx(2 * a["bytes"])
    assert useful.tally(work, MODEL, one, 0.0, 2.0)["generate"]["bytes"] == \
        pytest.approx(a["bytes"] / 2)
