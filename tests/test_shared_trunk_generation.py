"""Shared-trunk generation: decode B rows from ONE prefilled prompt.

best_of_n's N drafts and every habermas phase decode many rows from an
identical prompt (reference best_of_n.py:101-142, habermas_machine.py:
530-583).  The shared path prefills the prompt once and broadcast-attends
it per step (forward_trunk_tail with n_slots=B, n_roles=1) — per-step KV
reads drop from B·(ctx+t) to ctx+B·t.  It must be a pure optimization:
same tokens as the classic per-row-trunk path for the same seeds.
"""

import numpy as np
import pytest

from consensus_tpu.backends.base import GenerationRequest
from consensus_tpu.backends.tpu import TPUBackend


def make_backend(**kw):
    kw.setdefault("model", "tiny-gemma2")
    kw.setdefault("max_context", 128)
    kw.setdefault("base_seed", 0)
    kw.setdefault("dtype", "float32")
    return TPUBackend(**kw)


@pytest.fixture(scope="module")
def shared():
    return make_backend(shared_trunk_generation=True)


@pytest.fixture(scope="module")
def classic():
    return make_backend(shared_trunk_generation=False)


def requests_same_prompt(n, max_tokens=10, temperature=0.0):
    return [
        GenerationRequest(
            user_prompt="One common draft prompt.",
            max_tokens=max_tokens,
            seed=50 + i,
            temperature=temperature,
        )
        for i in range(n)
    ]


def test_shared_matches_classic_greedy(shared, classic):
    """Greedy rows are logit-determined: the shared trunk must reproduce the
    classic path's tokens exactly (identical math, different layout)."""
    requests = requests_same_prompt(6, temperature=0.0)
    ours = shared.generate(requests)
    ref = classic.generate(requests)
    assert [r.token_ids for r in ours] == [r.token_ids for r in ref]


def test_shared_matches_classic_sampled(shared, classic):
    """Sampled rows use the same per-request key streams in both paths."""
    requests = requests_same_prompt(8, temperature=0.9)
    ours = shared.generate(requests)
    ref = classic.generate(requests)
    assert [r.token_ids for r in ours] == [r.token_ids for r in ref]


def test_rows_are_distinct_despite_shared_trunk(shared):
    requests = requests_same_prompt(8, temperature=1.0)
    results = shared.generate(requests)
    assert len({r.token_ids for r in results}) > 1


def test_mixed_batch_routes_both_paths(shared, classic):
    """4 identical prompts ride the shared path, 2 odd ones the classic
    path; result order must be preserved."""
    requests = requests_same_prompt(4, temperature=0.8) + [
        GenerationRequest(
            user_prompt=f"different {i}", max_tokens=8, seed=i, temperature=0.8
        )
        for i in range(2)
    ]
    ours = shared.generate(requests)
    ref = classic.generate(requests)
    assert [r.token_ids for r in ours] == [r.token_ids for r in ref]


def test_shared_respects_stop_and_eos_semantics(shared):
    requests = [
        GenerationRequest(
            user_prompt="One common draft prompt.",
            max_tokens=10,
            seed=i,
            temperature=0.7,
            stop=("e",),
        )
        for i in range(4)
    ]
    for result in shared.generate(requests):
        assert "e" not in result.text
        assert result.finish_reason == "stop" or len(result.token_ids) <= 10


def test_shared_trunk_with_bias_tables(shared, classic):
    requests = [
        GenerationRequest(
            user_prompt="One common draft prompt.",
            max_tokens=8,
            seed=i,
            temperature=0.9,
            bias_against_tokens=("e", "t"),
            bias_value=-100.0,
        )
        for i in range(5)
    ]
    ours = shared.generate(requests)
    ref = classic.generate(requests)
    assert [r.token_ids for r in ours] == [r.token_ids for r in ref]


class TestRoutingThreshold:
    """Small identical-prompt groups inside a larger batch route CLASSIC
    (combined chunks amortize the per-step weight read); big groups and
    whole-batch groups still take the shared path (round-4 routing fix —
    the habermas revision phase is 30 distinct 4-row groups)."""

    def _routes(self, backend, requests, monkeypatch):
        import consensus_tpu.backends.tpu as tpu_mod

        calls = {"shared": 0, "classic": 0}
        orig_shared = tpu_mod.TPUBackend._generate_shared
        orig_classic = tpu_mod.TPUBackend._generate_classic

        def spy_shared(self, reqs, ids):
            calls["shared"] += 1
            return orig_shared(self, reqs, ids)

        def spy_classic(self, reqs, ids):
            calls["classic"] += 1
            return orig_classic(self, reqs, ids)

        monkeypatch.setattr(tpu_mod.TPUBackend, "_generate_shared", spy_shared)
        monkeypatch.setattr(tpu_mod.TPUBackend, "_generate_classic", spy_classic)
        results = backend.generate(requests)
        assert all(r.ok for r in results)
        return calls

    def test_small_groups_in_big_batch_go_classic(self, shared, monkeypatch):
        requests = [
            GenerationRequest(
                user_prompt=f"Revision prompt {g}", max_tokens=8, seed=g * 10 + i
            )
            for g in range(5)
            for i in range(4)  # 5 distinct 4-row groups
        ]
        calls = self._routes(shared, requests, monkeypatch)
        assert calls["shared"] == 0 and calls["classic"] >= 1

    def test_whole_batch_group_stays_shared(self, shared, monkeypatch):
        requests = [
            GenerationRequest(user_prompt="One prompt", max_tokens=8, seed=i)
            for i in range(4)
        ]
        calls = self._routes(shared, requests, monkeypatch)
        assert calls["shared"] == 1 and calls["classic"] == 0

    def test_large_group_in_mixed_batch_stays_shared(self, shared, monkeypatch):
        from consensus_tpu.backends.tpu import _SHARED_TRUNK_SOLO_ROWS

        requests = [
            GenerationRequest(user_prompt="Big group", max_tokens=8, seed=i)
            for i in range(_SHARED_TRUNK_SOLO_ROWS)
        ] + [
            GenerationRequest(user_prompt=f"Stray {i}", max_tokens=8, seed=99 + i)
            for i in range(2)
        ]
        calls = self._routes(shared, requests, monkeypatch)
        assert calls["shared"] == 1 and calls["classic"] >= 1


@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_engine_hands_the_whole_group_to_one_program(
    shared, monkeypatch, temperature
):
    """The 32 rows of one call go through the engine as one cohort: one
    ``generate_shared`` launch of 32 rows, whose rows are, row for row, what
    the same seeds give in four direct calls of 8 (the engine's old cohorts;
    per-row keys make a row independent of who shares its batch)."""
    from consensus_tpu.backends.batching import BatchingBackend

    requests = requests_same_prompt(32, temperature=temperature)
    direct = [
        result
        for i in range(0, 32, 8)
        for result in shared.generate(requests[i : i + 8])
    ]
    launches = []
    record = shared.instruments.record_launch
    monkeypatch.setattr(
        shared.instruments, "record_launch",
        lambda kind, shape: launches.append((kind, shape[0]))
        or record(kind, shape),
    )
    engined = BatchingBackend(shared, engine=True)
    try:
        served = engined.generate(requests)
        stats = engined.engine.stats()
    finally:
        engined.close()
    assert [r.token_ids for r in served] == [r.token_ids for r in direct]
    assert [r.text for r in served] == [r.text for r in direct]
    assert launches == [("generate_shared", 32)]
    assert engined.batch_counts["generate"] == 1
    assert stats["decode_windows"] == 1 and stats["kv_pages_reserved"] == 0
