"""TPUBackend protocol tests on a tiny random-weight model (CPU devices).

Random weights make statements noise, but every protocol property —
shapes, determinism, logprob validity, batching, EOS/stop handling —
is exactly what production runs rely on.
"""

import numpy as np
import pytest

from consensus_tpu.backends.base import (
    GenerationRequest,
    NextTokenRequest,
    ScoreRequest,
)
from consensus_tpu.backends.tpu import TPUBackend
from consensus_tpu.models.tokenizer import ByteTokenizer

ISSUE = "Should the town build a new playground?"


@pytest.fixture(scope="module")
def backend():
    return TPUBackend(model="tiny-gemma2", max_context=256, base_seed=0)


class TestGenerate:
    def test_batch_generation(self, backend):
        requests = [
            GenerationRequest(user_prompt=f"Prompt {i}", max_tokens=8, seed=i)
            for i in range(3)
        ]
        results = backend.generate(requests)
        assert len(results) == 3
        for result in results:
            assert result.finish_reason in ("stop", "length")
            assert len(result.token_ids) <= 8

    def test_deterministic_for_same_batch(self, backend):
        requests = [GenerationRequest(user_prompt="Same prompt", max_tokens=6, seed=1)]
        r1 = backend.generate(requests)[0]
        r2 = backend.generate(requests)[0]
        assert r1.text == r2.text

    def test_stop_string_truncates(self, backend):
        request = GenerationRequest(user_prompt="Hi", max_tokens=6, seed=0)
        full = backend.generate([request])[0]
        if len(full.text) > 1:
            stop_char = full.text[1]
            stopped = backend.generate(
                [GenerationRequest(user_prompt="Hi", max_tokens=6, seed=0,
                                   stop=(stop_char,))]
            )[0]
            assert stop_char not in stopped.text

    def test_same_request_independent_of_batch(self, backend):
        """Regression (VERDICT r1 #7): a request's output must not depend on
        which other requests share its device batch — per-row PRNG keys."""
        probe = GenerationRequest(
            user_prompt="Independent request", max_tokens=6, seed=7,
            temperature=0.9,
        )
        alone = backend.generate([probe])[0]
        other = GenerationRequest(
            user_prompt="A different companion", max_tokens=6, seed=11,
            temperature=0.9,
        )
        batched = backend.generate([other, probe])[1]
        assert alone.text == batched.text
        assert alone.token_ids == batched.token_ids

    def test_unseeded_duplicate_requests_stay_diverse(self, backend):
        """Unseeded identical prompts in one batch (best_of_n drafts,
        habermas candidates) must each get a distinct sampling stream."""
        requests = [
            GenerationRequest(
                user_prompt="Draft a statement", max_tokens=8, seed=None,
                temperature=1.0,
            )
            for _ in range(3)
        ]
        results = backend.generate(requests)
        token_sets = {r.token_ids for r in results}
        assert len(token_sets) > 1

    def test_greedy_at_zero_temperature(self, backend):
        requests = [
            GenerationRequest(user_prompt="Greedy", max_tokens=5, temperature=0.0,
                              seed=s)
            for s in (1, 2)
        ]
        results = backend.generate(requests)
        assert results[0].text == results[1].text  # greedy ignores seed


class TestScore:
    def test_continuation_logprobs_only(self, backend):
        result = backend.score(
            [ScoreRequest(context="The town meeting", continuation=" agreed today")]
        )[0]
        assert result.ok
        assert all(lp <= 0.0 for lp in result.logprobs)
        # Tokens decode back to the continuation text.
        assert "".join(result.tokens).strip().startswith("agreed")

    def test_batch_matches_single(self, backend):
        requests = [
            ScoreRequest(context="Alpha beta", continuation=" gamma"),
            ScoreRequest(context="One two", continuation=" three four"),
        ]
        batched = backend.score(requests)
        singles = [backend.score([r])[0] for r in requests]
        for b, s in zip(batched, singles):
            np.testing.assert_allclose(b.logprobs, s.logprobs, atol=1e-3)

    def test_mean_and_total(self, backend):
        result = backend.score(
            [ScoreRequest(context="ctx", continuation=" something longer here")]
        )[0]
        assert result.mean() == pytest.approx(np.mean(result.logprobs))
        assert result.total() == pytest.approx(np.sum(result.logprobs))


class TestNextToken:
    def test_topk_distinct_sorted(self, backend):
        candidates = backend.next_token_logprobs(
            [NextTokenRequest(user_prompt="Next", k=5, mode="topk")]
        )[0]
        assert len(candidates) == 5
        ids = [c.token_id for c in candidates]
        assert len(set(ids)) == 5
        lps = [c.logprob for c in candidates]
        assert lps == sorted(lps, reverse=True)

    def test_sample_mode_seed_dependence(self, backend):
        a = backend.next_token_logprobs(
            [NextTokenRequest(user_prompt="Next", k=4, mode="sample", seed=1)]
        )[0]
        b = backend.next_token_logprobs(
            [NextTokenRequest(user_prompt="Next", k=4, mode="sample", seed=1)]
        )[0]
        c = backend.next_token_logprobs(
            [NextTokenRequest(user_prompt="Next", k=4, mode="sample", seed=2)]
        )[0]
        assert [x.token_id for x in a] == [x.token_id for x in b]
        assert any(
            x.token_id != y.token_id for x, y in zip(a, c)
        ) or len(a) != len(c)

    def test_sample_independent_of_batch(self, backend):
        """Device-side Gumbel-top-k uses per-row keys: candidates for a
        request match whether it runs alone or batched."""
        probe = NextTokenRequest(user_prompt="Probe", k=4, mode="sample", seed=5)
        alone = backend.next_token_logprobs([probe])[0]
        other = NextTokenRequest(
            user_prompt="Companion prompt", k=4, mode="sample", seed=9
        )
        batched = backend.next_token_logprobs([other, probe])[1]
        assert [c.token_id for c in alone] == [c.token_id for c in batched]

    def test_larger_k_is_prefix_superset(self, backend):
        """Gumbel-top-k without replacement: asking for more candidates keeps
        the smaller request's set (same row key, same scores)."""
        small = backend.next_token_logprobs(
            [NextTokenRequest(user_prompt="Prefix", k=3, mode="sample", seed=4)]
        )[0]
        large = backend.next_token_logprobs(
            [NextTokenRequest(user_prompt="Prefix", k=6, mode="sample", seed=4)]
        )[0]
        assert {c.token_id for c in small} <= {c.token_id for c in large}

    def test_bias_suppresses_tokens(self, backend):
        top = backend.next_token_logprobs(
            [NextTokenRequest(user_prompt="Bias test", k=3, mode="topk")]
        )[0]
        banned = top[0].token
        if banned.strip():
            rebiased = backend.next_token_logprobs(
                [
                    NextTokenRequest(
                        user_prompt="Bias test", k=3, mode="topk",
                        bias_against_tokens=(banned,),
                    )
                ]
            )[0]
            assert all(banned not in c.token for c in rebiased)


class TestEmbed:
    def test_unit_norm_and_shape(self, backend):
        vectors = backend.embed(["hello world", "completely different text"])
        assert vectors.shape[0] == 2
        np.testing.assert_allclose(
            np.linalg.norm(vectors, axis=1), [1.0, 1.0], atol=1e-5
        )

    def test_identical_texts_identical_vectors(self, backend):
        vectors = backend.embed(["same text", "same text"])
        np.testing.assert_allclose(vectors[0], vectors[1], atol=1e-6)


class TestDecoderIntegration:
    def test_best_of_n_runs_on_tpu_backend(self, backend):
        from consensus_tpu.methods import get_method_generator

        gen = get_method_generator(
            "best_of_n", backend, {"n": 2, "max_tokens": 6, "seed": 3}
        )
        statement = gen.generate_statement(
            ISSUE, {"A": "Yes, kids need it.", "B": "Too expensive."}
        )
        assert isinstance(statement, str)

    def test_experiment_with_tpu_backend(self, backend, tmp_path):
        from consensus_tpu.experiment import Experiment

        config = {
            "experiment_name": "tpu_smoke",
            "seed": 1,
            "num_seeds": 1,
            "scenario": {
                "issue": ISSUE,
                "agent_opinions": {"A": "Build it.", "B": "Save the money."},
            },
            "methods_to_run": ["zero_shot"],
            "zero_shot": {"max_tokens": 6},
            "output_dir": str(tmp_path),
        }
        frame = Experiment(config, backend=backend).run()
        assert len(frame) == 1
        assert frame["error_message"].iloc[0] == ""


class TestGenerateChunking:
    """HBM-aware decode-batch chunking (backends/tpu.py:_generate_rows_allowed)."""

    def make(self, **kw):
        from consensus_tpu.backends.tpu import TPUBackend

        return TPUBackend(model="tiny-gemma2", dtype="float32", max_context=128, **kw)

    def test_rows_allowed_rounds_down_to_pow2(self, monkeypatch):
        import consensus_tpu.backends.tpu as tpu_mod

        backend = self.make()
        unit = (
            2 * backend.config.n_layers * backend.config.n_kv_heads
            * backend.config.head_dim * 4  # float32
        )
        budget_free = (
            tpu_mod._HBM_BYTES - backend._params_bytes
            - tpu_mod._ACTIVATION_RESERVE_BYTES
        )
        # Choose width/max_new so exactly 5 rows fit -> pow2 floor is 4.
        per_row_cols = budget_free // (5 * unit)
        width = int(per_row_cols) - 2 * 16
        assert backend._generate_rows_allowed(width, 16) == 4

    def test_rows_allowed_floor_is_one(self, monkeypatch):
        import consensus_tpu.backends.tpu as tpu_mod

        backend = self.make()
        monkeypatch.setattr(tpu_mod, "_HBM_BYTES", backend._params_bytes + 1)
        assert backend._generate_rows_allowed(4096, 512) == 1

    def test_live_sessions_shrink_the_allowance(self):
        backend = self.make()
        base = backend._generate_rows_allowed(1024, 128)
        backend._session_budget.acquire(backend._session_budget.cap // 2)
        try:
            assert backend._generate_rows_allowed(1024, 128) <= base
        finally:
            backend._session_budget.release(backend._session_budget.cap // 2)

    def test_segmented_allowance_models_the_block_peak(self):
        """The segmented row allowance (backends/tpu.py:
        _segmented_rows_allowed) tracks the block-list HBM peak — the
        single-buffered frozen blocks (no concat transient: segments
        append to a list), the double-buffered live tail, and one seg_len
        of compaction-gather transient; int8 KV halves the column cost
        (plus a scale-plane margin) — while beating the monolithic
        allowance (whose full-budget tail is double-buffered by the
        carry copy)."""
        backend = self.make()  # kv_quant defaults ON
        exact = self.make(kv_quant=False)
        max_new, seg = 768, 128
        cols = (max_new - seg) + 2 * seg + seg  # frozen + dbuf tail + gather
        assert exact._segmented_rows_allowed(0, max_new, seg) == (
            exact._generate_rows_allowed(cols - 2 * seg, seg)
        )
        quant_cols = (cols + 1) // 2 + seg // 4
        assert backend._segmented_rows_allowed(0, max_new, seg) == (
            backend._generate_rows_allowed(quant_cols - 2 * seg, seg)
        )
        # int8 KV must raise capacity, and both must beat monolithic.
        assert backend._segmented_rows_allowed(0, max_new, seg) > (
            exact._segmented_rows_allowed(0, max_new, seg)
        )
        assert exact._segmented_rows_allowed(0, max_new, seg) >= (
            exact._generate_rows_allowed(0, max_new)
        )
        # Classic layout (wide per-row prompt trunk): under kv_quant the
        # trunk is int8 at decode time, but the prefill→quantize transient
        # (1.5x bf16 trunk) is the binding peak at production widths.
        width = 1024
        quant_cols = (cols + 1) // 2 + seg // 4
        expected = max(
            width + width // 2 + 2 * seg,
            (width + 1) // 2 + width // 16 + quant_cols,
        )
        assert backend._segmented_rows_allowed(width, max_new, seg) == (
            backend._generate_rows_allowed(expected - 2 * seg, seg)
        )
        assert backend._segmented_rows_allowed(width, max_new, seg) >= (
            exact._segmented_rows_allowed(width, max_new, seg)
        )

    def test_oversized_batch_chunks_and_results_match(self, monkeypatch):
        from consensus_tpu.backends.base import GenerationRequest
        from consensus_tpu.backends.tpu import TPUBackend

        backend = self.make()
        requests = [
            GenerationRequest(
                user_prompt=f"Issue number {i}.", max_tokens=4, seed=100 + i
            )
            for i in range(6)
        ]
        whole = backend.generate(requests)
        # Force single-row chunks: per-request results must be identical
        # (per-row PRNG keys make rows batch-composition independent).
        monkeypatch.setattr(
            TPUBackend, "_generate_rows_allowed", lambda self, w, m: 1
        )
        chunked = backend.generate(requests)
        assert [r.text for r in whole] == [r.text for r in chunked]
        assert backend.call_counts["generate"] == 12  # 6 + 6, not double-counted


class TestATextIsTokenisedOnce:
    """Every tokenisation of the serving path asks ``TPUBackend.token_ids``:
    a text is encoded once however many rows, matrices and callers ask for
    its ids, and what they get is what the tokenizer gives."""

    AGENTS = ["Buses matter most to agent %d, who has more to say. " % a * (a + 1)
              for a in range(5)]
    CANDIDATES = ["candidate statement number %d %s" % (c, "and more " * (c % 5))
                  for c in range(32)]

    @pytest.fixture()
    def fresh(self, backend):
        """A backend of its own memo over the module's weights, with a
        tokenizer that notes what it is asked to encode."""
        from paged_capture import CountingTokenizer

        fresh = TPUBackend(config=backend.config, params=backend.params,
                           max_context=1024, base_seed=0)
        fresh.tokenizer = CountingTokenizer(fresh.tokenizer)
        return fresh

    @staticmethod
    def _tokenize_counts():
        from consensus_tpu.obs.metrics import get_registry

        family = get_registry().snapshot()["families"].get(
            "backend_tokenize_texts_total", {"series": []})
        counts = {"encoded": 0, "reused": 0}
        counts.update({s["labels"]["outcome"]: s["value"]
                       for s in family["series"] if s["labels"]["backend"] == "tpu"})
        return counts

    def _matrix(self):
        from consensus_tpu.backends.score_matrix import (
            AgentContext,
            ScoreMatrixRequest,
        )

        return ScoreMatrixRequest(
            agents=tuple(AgentContext(context=a, system_prompt="Judge.")
                         for a in self.AGENTS),
            candidates=tuple(self.CANDIDATES))

    def test_a_call_of_32_rows_over_one_prompt_encodes_one_text(
            self, fresh, monkeypatch):
        plain = ByteTokenizer()
        launched = []

        def no_device(requests, prompt_ids):
            launched.append(list(prompt_ids))
            return [None] * len(requests)

        monkeypatch.setattr(fresh, "_generate_shared", no_device)
        requests = [GenerationRequest(user_prompt=ISSUE, system_prompt="Be brief.",
                                      max_tokens=4, seed=i) for i in range(32)]
        before = self._tokenize_counts()
        fresh.generate(requests)
        rendered = plain.chat_prompt(ISSUE, "Be brief.")
        assert fresh.tokenizer.texts == [rendered]
        assert launched == [plain.encode(rendered, add_bos=True)]
        after = self._tokenize_counts()
        assert after["encoded"] - before["encoded"] == 1
        assert after["reused"] - before["reused"] == 31

    def test_a_matrix_encodes_each_text_once_and_a_second_matrix_none(
            self, fresh, monkeypatch):
        from paged_capture import captured_matrix

        plain = ByteTokenizer()
        request = self._matrix()
        seen = captured_matrix(fresh, request, monkeypatch)
        prefixes = [fresh._score_prefix(a.to_score_request(""))
                    for a in request.agents]
        assert sorted(fresh.tokenizer.texts) == sorted(prefixes + self.CANDIDATES)
        # Row for row what the plain tokenizer gives: a row re-feeds its
        # context from the last page boundary on, then its candidate less
        # the last token (candidate-major, an agent a row).
        page = fresh._SCORE_PAGE_SIZE
        tokens = np.concatenate([c["tokens"] for c in seen["chunks"]])
        rows = [(p, c) for c in self.CANDIDATES for p in prefixes]
        for r in (0, 1, 63, 64, 159):
            ids = plain.encode(rows[r][0], add_bos=True)
            stream = (ids + plain.encode(rows[r][1]))[(len(ids) - 1) // page * page:-1]
            assert tokens[r, : len(stream)].tolist() == stream
        del fresh.tokenizer.texts[:]
        captured_matrix(fresh, request, monkeypatch)
        fresh.embed(self.CANDIDATES[:4])  # with the BOS, from the ids without
        assert fresh.tokenizer.texts == []

    def test_the_ids_are_the_tokenizers_and_the_callers_own(self, fresh):
        plain = ByteTokenizer()
        text = "[USER]What now?[/USER]\n[ASSISTANT] café <eos> and on"
        for add_bos in (False, True, False):
            ids = fresh.token_ids(text, add_bos=add_bos)
            assert ids == plain.encode(text, add_bos=add_bos)
            ids.append(-1)  # a caller's list is its own
        assert fresh.tokenizer.texts == [text]
        tally = {}
        fresh.token_ids(text, tally=tally)
        fresh.token_ids(text + "!", tally=tally)
        assert tally == {"encoded": 1}

    def test_the_memo_stays_within_its_bound(self, fresh, monkeypatch):
        from consensus_tpu.backends import tpu

        monkeypatch.setattr(tpu, "_TOKEN_MEMO_CHARS", 100)
        texts = ["text %02d " % i * 3 for i in range(12)]  # 24 characters each
        for text in texts:
            fresh.token_ids(text)
            held = list(fresh._token_memo)
            assert sum(map(len, held)) == fresh._token_memo_chars <= 100
        assert held == texts[-4:]  # the least recently asked for went first
        fresh.token_ids(texts[-4])  # asked again: the last to go now
        fresh.token_ids(texts[0])   # gone: encoded again, and the oldest goes
        assert list(fresh._token_memo) == [texts[-2], texts[-1], texts[-4], texts[0]]
        assert fresh.tokenizer.texts == texts + [texts[0]]
        fresh.token_ids("x" * 101)  # longer than the bound: never held
        assert "x" * 101 not in fresh._token_memo
        assert fresh.token_ids("x" * 101) == ByteTokenizer().encode("x" * 101)

    def test_threads_get_the_same_ids(self, fresh, monkeypatch):
        """More threads than cores over a memo too small for their texts, so
        that hits, evictions and double encodings interleave: every answer
        is the tokenizer's and the books balance."""
        import sys
        import threading

        from consensus_tpu.backends import tpu

        monkeypatch.setattr(tpu, "_TOKEN_MEMO_CHARS", 400)
        plain = ByteTokenizer()
        texts = ["thread text %02d " % i * 4 for i in range(12)]  # 64 each
        want = {t: plain.encode(t, add_bos=True) for t in texts}
        wrong, threads = [], []

        def ask(seed):
            order = np.random.default_rng(seed).integers(0, len(texts), 300)
            for i in order:
                if fresh.token_ids(texts[i], add_bos=True) != want[texts[i]]:
                    wrong.append(texts[i])

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=ask, args=(s,)) for s in range(16)]
            for thread in threads:
                thread.start()
            for thread in threads:
                thread.join(timeout=60.0)
        finally:
            sys.setswitchinterval(interval)
        assert not any(thread.is_alive() for thread in threads)
        assert not wrong
        held = list(fresh._token_memo)
        assert sum(map(len, held)) == fresh._token_memo_chars <= 400
        assert len(set(held)) == len(held) == 6
