"""The terms of the dense work file, each alone, against its whole and
numbers worked by hand; and the tally by term over plain records."""

import json
import pathlib
import types

import pytest

from benchmark.lib import useful
from benchmark.work import dense as work

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"
MODELS = {name: json.loads((CONFIGS / f"{name}.json").read_text())["model"]
          for name in ("smollm2-1.7b", "mistral-7b-v0.3-h16")}


@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_three_terms_sum_to_span_flops(name):
    m = MODELS[name]
    for start, count, with_head in ((0, 2000, 1), (2000, 50, 50),
                                    (700, 100, 100), (9, 1, 1), (0, 3, 0)):
        terms = sum(work.span_flops(m, start, count, with_head, term=term)
                    for term in work.TERMS)
        assert terms == pytest.approx(
            work.span_flops(m, start, count, with_head), rel=1e-12)
        # each alone, by hand
        context = count * start + count * (count + 1) // 2
        assert work.span_flops(m, start, count, with_head, term="attention") == \
            4 * context * m["n_heads"] * m["head_dim"] * m["n_layers"]
        assert work.span_flops(m, start, count, with_head, term="head") == \
            2 * with_head * m["vocab_size"] * m["d_model"]
        assert work.span_flops(m, start, count, with_head, term="matrix") == \
            2 * count * m["n_layers"] * work.layer_matmul_params(m)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_cache_term_and_the_weights_sum_to_step_bytes(name):
    m = MODELS[name]
    for cached, rows in ((0, 1), (1, 8), (3000, 32)):
        cache = work.step_bytes(m, cached, rows, term="attention")
        assert cache == cached * work.kv_bytes_per_token(m)
        assert cache + work.weight_bytes(m) == work.step_bytes(m, cached, rows)
        assert sum(work.step_bytes(m, cached, rows, term=term)
                   for term in work.TERMS) == work.step_bytes(m, cached, rows)
    assert work.head_bytes(m) == m["vocab_size"] * m["d_model"] * 2
    assert work.weight_bytes(m, term="head") == work.head_bytes(m)
    assert work.weight_bytes(m, term="attention") == 0
    assert sum(work.weight_bytes(m, term=term) for term in work.TERMS) == \
        work.weight_bytes(m)


def test_smollm2_head_by_hand():
    m = MODELS["smollm2-1.7b"]
    assert work.span_flops(m, 0, 0, 1, term="head") == 2 * 49152 * 2048
    assert work.head_bytes(m) == 201_326_592  # the tied table, 201 MB


def _records():
    request = lambda seed: types.SimpleNamespace(  # noqa: E731
        chat=False, user_prompt="p" * 100, system_prompt=None, seed=seed)
    generate = {"kind": "generate", "start": 0.0, "end": 4.0,
                "requests": [request(s) for s in range(1000, 1008)],
                "results": [types.SimpleNamespace(token_ids=(5,) * 20)
                            for _ in range(8)]}
    agents = tuple(types.SimpleNamespace(context="c" * n, system_prompt=None,
                                         chat=False, role="assistant")
                   for n in (60, 90))
    matrix = {"kind": "score_matrix", "start": 1.0, "end": 3.0,
              "requests": [types.SimpleNamespace(
                  agents=agents, candidates=("x" * 30, "y" * 40))]}
    embed = {"kind": "embed", "start": 2.0, "end": 6.0,
             "requests": ["e" * 50, "f" * 70]}
    return [generate, matrix, embed]


@pytest.mark.parametrize("name", sorted(MODELS))
def test_tally_by_term_sums_to_the_tally(name):
    m = MODELS[name]
    calls = _records()
    whole = useful.tally(work, m, calls, 0.0, 4.0)
    terms = {term: useful.tally(work, m, calls, 0.0, 4.0, term=term)
             for term in work.TERMS}
    for kind, entry in whole.items():
        for key in ("flops", "bytes"):
            assert sum(terms[t][kind][key] for t in work.TERMS) == \
                pytest.approx(entry[key], rel=1e-12)
        # What is no work is the same under every term.
        for key in ("launches", "tokens"):
            assert {terms[t][kind][key] for t in work.TERMS} == {entry[key]}
    # By hand, the generation call: 8 rows of 20 tokens after a prompt of
    # 101 (100 bytes and the first token).  The head: the prompt's last
    # position and every sampled one; its table at the prefill and at each
    # of the 20 steps.  The cache: what 20 steps read.
    head = terms["head"]["generate"]
    assert head["flops"] == work.span_flops(m, 0, 0, 1 + 8 * 20, term="head")
    assert head["bytes"] == pytest.approx(21 * work.head_bytes(m))
    cache = sum((101 + 8 * (step - 1)) * work.kv_bytes_per_token(m)
                for step in range(1, 21))
    assert terms["attention"]["generate"]["bytes"] == pytest.approx(cache)
    assert terms["attention"]["generate"]["flops"] == pytest.approx(
        work.span_flops(m, 0, 101, term="attention")
        + 8 * work.span_flops(m, 101, 20, term="attention"))
    # Scoring reads no bytes in the tally, and embeds have no head.
    assert terms["head"]["embed"]["flops"] == 0.0
    assert terms["attention"]["score_matrix"]["bytes"] == 0.0
    assert terms["head"]["score_matrix"]["flops"] == \
        work.span_flops(m, 0, 0, 2 * (30 + 40), term="head")
    # Half of the embed call lies in the stretch.
    assert terms["matrix"]["embed"]["flops"] == pytest.approx(
        0.5 * work.span_flops(m, 0, 51 + 71, term="matrix"))
