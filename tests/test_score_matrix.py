"""Agent-parallel utility-matrix scoring (PR 10).

The contract pinned here:

* the fallback seam is BYTE-identical to the per-call code it replaces —
  same ScoreRequest rows, same reduction expressions, same float64
  values, same pinned (numpy first-max) argmax;
* consumers gate on ``matrix_scoring`` (default ON) and produce
  byte-identical statements/metrics with the seam on or off, across
  seeds, on the fake backend (best-of-N, beam search, the evaluator);
* merged score dispatches dedup identical rows (engine and legacy
  flush) and count removals in ``engine_score_dedup_total``;
* the fused TPU path agrees with the fallback to float tolerance with
  the same argmax, on BOTH tiny model families and every stat;
* a 64-agent matrix streams in chunks under a shrunken HBM session
  budget without falling back, bit-identical to the unchunked run;
* dp=4 and dp=1 produce identical utilities (8 virtual CPU devices
  from conftest.py).
"""

import re

import numpy as np
import pytest

from consensus_tpu.backends.base import PartialBatchError, ScoreRequest
from consensus_tpu.backends.batching import BatchingBackend
from consensus_tpu.backends.fake import FakeBackend
from consensus_tpu.backends.score_matrix import (
    AgentContext,
    ScoreMatrixRequest,
    dedup_score_requests,
    expand_deduped,
    expand_partial_error,
    fallback_score_matrix_many,
    score_matrix_many,
    welfare_argmax,
)
from consensus_tpu.obs.metrics import Registry

ISSUE = "Should the city build more parks or more parking?"
OPINIONS = {
    "alice": "Parks improve health and community.",
    "bob": "Parking shortages strangle local business.",
    "carol": "Both matter; phase the spending.",
}


def _family_total(registry, name):
    family = (registry.snapshot().get("families") or {}).get(name) or {}
    return sum(s.get("value", 0) for s in family.get("series", []))


# ---------------------------------------------------------------------------
# The seam itself
# ---------------------------------------------------------------------------


class TestSeam:
    def _request(self, stat="mean"):
        return ScoreMatrixRequest(
            agents=(
                AgentContext(context="ctx a", chat=False),
                AgentContext(context="ctx b", chat=False),
            ),
            candidates=("one", "two", "three"),
            stat=stat,
        )

    def test_cell_requests_candidate_major(self):
        rows = self._request().cell_requests()
        assert [(r.context, r.continuation) for r in rows] == [
            ("ctx a", "one"), ("ctx b", "one"),
            ("ctx a", "two"), ("ctx b", "two"),
            ("ctx a", "three"), ("ctx b", "three"),
        ]

    def test_bad_stat_and_rule_rejected(self):
        with pytest.raises(ValueError):
            self._request(stat="median")
        with pytest.raises(ValueError):
            ScoreMatrixRequest(
                agents=(AgentContext(context="c"),),
                candidates=("x",),
                welfare_rule="plutocratic",
            )

    def test_fallback_matches_percall_expressions(self):
        """Every stat reduces exactly as the consumer it serves did."""
        backend = FakeBackend()
        request = self._request()
        results = backend.score(request.cell_requests())
        for stat, expect in (
            ("mean", [r.mean(default=-10.0) for r in results]),
            ("sum", [float(sum(r.logprobs)) for r in results]),
            ("last", [float(r.logprobs[-1]) for r in results]),
        ):
            matrix = fallback_score_matrix_many(
                backend, [self._request(stat=stat)]
            )[0]
            assert matrix.utilities.ravel().tolist() == expect
        moments = fallback_score_matrix_many(
            backend, [self._request(stat="moments")]
        )[0]
        for cell_lp, cell_p, r in zip(
            moments.utilities.ravel(), moments.aux.ravel(), results
        ):
            lps = np.asarray(r.logprobs, dtype=np.float64)
            assert cell_lp == float(lps.mean())
            assert cell_p == float(np.exp(lps).mean())

    def test_welfare_argmax_pins_first_max(self):
        utilities = np.asarray([[1.0, 5.0], [2.0, 1.0], [1.0, 2.0]])
        welfare, best = welfare_argmax(utilities, "egalitarian")
        assert welfare.tolist() == [1.0, 1.0, 1.0]
        assert best == 0  # first max, numpy semantics

    def test_empty_matrix(self):
        request = ScoreMatrixRequest(agents=(), candidates=())
        result = fallback_score_matrix_many(FakeBackend(), [request])[0]
        assert result.utilities.shape == (0, 0)
        assert result.best == 0

    def test_dedup_mapping_roundtrip(self):
        a = ScoreRequest(context="x", continuation="1", chat=False)
        b = ScoreRequest(context="y", continuation="2", chat=False)
        unique, mapping = dedup_score_requests([a, b, a, a, b])
        assert len(unique) == 2
        assert expand_deduped(["A", "B"], mapping) == ["A", "B", "A", "A", "B"]

    def test_expand_partial_error(self):
        a = ScoreRequest(context="x", continuation="1", chat=False)
        b = ScoreRequest(context="y", continuation="2", chat=False)
        _, mapping = dedup_score_requests([a, b, a])
        error = PartialBatchError("boom", ["ra", None], {1: "bad row"})
        expanded = expand_partial_error(error, mapping)
        assert expanded.results == ["ra", None, "ra"]
        assert expanded.row_errors == {1: "bad row"}

    def test_obs_families_recorded(self):
        registry = Registry()
        from consensus_tpu.backends.score_matrix import record_matrix

        result = fallback_score_matrix_many(FakeBackend(), [self._request()])[0]
        record_matrix(result, 2, registry)
        assert _family_total(registry, "score_matrix_cells_total") == 6
        assert _family_total(registry, "score_matrix_d2h_bytes_total") > 0
        fam = (registry.snapshot().get("families") or {}).get(
            "score_agents_per_call"
        )
        assert fam is not None


# ---------------------------------------------------------------------------
# Consumer byte-identity (fake backend), matrix on vs off
# ---------------------------------------------------------------------------


class TestConsumerIdentity:
    @pytest.mark.parametrize("method", ["best_of_n", "beam_search"])
    @pytest.mark.parametrize("seed", [0, 7, 123])
    def test_statements_identical(self, method, seed):
        from consensus_tpu.methods import get_method_generator

        texts = {}
        for matrix_on in (True, False):
            generator = get_method_generator(
                method,
                FakeBackend(),
                {"n": 4, "max_tokens": 12, "seed": seed, "beam_width": 3,
                 "matrix_scoring": matrix_on},
            )
            texts[matrix_on] = generator.generate_statement(ISSUE, OPINIONS)
        assert texts[True] == texts[False]

    def test_evaluator_metrics_identical(self):
        from consensus_tpu.evaluation import StatementEvaluator

        statements = ["Fund both.", "Parks first.", "Fund both."]
        rows = {}
        for matrix_on in (True, False):
            rows[matrix_on] = StatementEvaluator(
                FakeBackend(), matrix_scoring=matrix_on
            ).evaluate_statements_batched(statements, ISSUE, OPINIONS)
        for on, off in zip(rows[True], rows[False]):
            assert set(on) == set(off)
            for key in on:
                assert on[key] == off[key], key

    def test_best_of_n_utilities_float32_cast_stable(self):
        """best-of-N historically built an f32 matrix; the float64
        fallback utilities must cast to the identical f32 values."""
        from consensus_tpu.methods.best_of_n import BestOfNGenerator

        backend = FakeBackend()
        candidates = ["Fund both now.", "Parks first."]
        on = BestOfNGenerator(
            backend, {"matrix_scoring": True}
        ).score_candidates(ISSUE, OPINIONS, candidates)
        off = BestOfNGenerator(
            backend, {"matrix_scoring": False}
        ).score_candidates(ISSUE, OPINIONS, candidates)
        assert on.dtype == off.dtype == np.float32
        assert np.array_equal(on, off)


# ---------------------------------------------------------------------------
# Dispatch seams: engine + legacy flush, dedup accounting
# ---------------------------------------------------------------------------


class TestDispatch:
    def _request(self):
        return ScoreMatrixRequest(
            agents=(
                AgentContext(context="ctx a", chat=False),
                AgentContext(context="ctx b", chat=False),
            ),
            candidates=("one", "two"),
        )

    @pytest.mark.parametrize("engine", [True, False])
    def test_batching_score_matrix_matches_direct(self, engine):
        direct = fallback_score_matrix_many(FakeBackend(), [self._request()])[0]
        batching = BatchingBackend(
            FakeBackend(), registry=Registry(), engine=engine
        )
        try:
            with batching.session():
                via = score_matrix_many(batching, [self._request()])[0]
        finally:
            batching.close()
        assert np.array_equal(via.utilities, direct.utilities)
        assert via.best == direct.best

    @pytest.mark.parametrize("engine", [True, False])
    def test_score_dedup_counter(self, engine):
        registry = Registry()
        batching = BatchingBackend(
            FakeBackend(), registry=registry, engine=engine
        )
        try:
            duplicate = ScoreRequest(
                context="same ctx", continuation="same cont", chat=False
            )
            with batching.session():
                results = batching.score(
                    [duplicate, duplicate,
                     ScoreRequest(context="other", continuation="x",
                                  chat=False)]
                )
            assert results[0].logprobs == results[1].logprobs
        finally:
            batching.close()
        assert _family_total(registry, "engine_score_dedup_total") >= 1


# ---------------------------------------------------------------------------
# Fused device path (tiny real models)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_backends():
    from consensus_tpu.backends.tpu import TPUBackend

    return {
        model: TPUBackend(model=model, dtype="float32", max_context=256)
        for model in ("tiny-gemma2", "tiny-llama3")
    }


def _tiny_request(n_agents=3, n_candidates=3, stat="mean"):
    return ScoreMatrixRequest(
        agents=tuple(
            AgentContext(
                context=f"Opinion holder {i} wants more of option {i}.",
                system_prompt="You are a panelist.",
                chat=True,
            )
            for i in range(n_agents)
        ),
        candidates=tuple(
            f"Candidate statement {j} about the issue." for j in range(n_candidates)
        ),
        stat=stat,
    )


class TestFusedParity:
    @pytest.mark.parametrize("model", ["tiny-gemma2", "tiny-llama3"])
    @pytest.mark.parametrize("stat", ["mean", "sum", "last", "moments"])
    def test_fused_matches_fallback(self, tiny_backends, model, stat):
        backend = tiny_backends[model]
        request = _tiny_request(stat=stat)
        fused = backend.score_matrix([request])[0]
        assert fused.path == "fused"
        fallback = fallback_score_matrix_many(backend, [request])[0]
        np.testing.assert_allclose(
            np.asarray(fused.utilities, np.float64),
            fallback.utilities,
            atol=5e-5, rtol=5e-5,
        )
        assert fused.best == fallback.best
        np.testing.assert_allclose(
            np.asarray(fused.welfare, np.float64),
            np.asarray(fallback.welfare, np.float64),
            atol=5e-5, rtol=5e-5,
        )
        if stat == "moments":
            np.testing.assert_allclose(
                np.asarray(fused.aux, np.float64), fallback.aux,
                atol=5e-5, rtol=5e-5,
            )

    def test_d2h_is_reductions_only(self, tiny_backends):
        """The fused path ships (C, A) + (C,) floats — never the per-token
        logprob vectors the fallback reports."""
        backend = tiny_backends["tiny-gemma2"]
        request = _tiny_request()
        fused = backend.score_matrix([request])[0]
        fallback = fallback_score_matrix_many(backend, [request])[0]
        n_cells = len(request.agents) * len(request.candidates)
        assert fused.d2h_bytes == n_cells * 4 + len(request.candidates) * 4
        assert fallback.d2h_bytes > 10 * fused.d2h_bytes

    def test_overlong_rows_fall_back(self, tiny_backends):
        """Rows needing the per-call scorer's truncation semantics route
        the whole request through it."""
        backend = tiny_backends["tiny-gemma2"]
        request = ScoreMatrixRequest(
            agents=(
                AgentContext(context="word " * 400, chat=False),
            ),
            candidates=("short tail.",),
        )
        before = backend.matrix_stats["fallbacks"]
        result = backend.score_matrix([request])[0]
        assert result.path == "fallback"
        assert backend.matrix_stats["fallbacks"] == before + 1

    def test_64_agents_chunk_under_budget(self, tiny_backends):
        """The acceptance case: a 64-agent matrix streams through a
        shrunken HBM session budget in >1 chunk, no fallback, and the
        chunked utilities are bit-identical to the unchunked run."""
        backend = tiny_backends["tiny-gemma2"]
        request = ScoreMatrixRequest(
            agents=tuple(
                AgentContext(
                    context=f"Panel member {i} holds position variant {i}.",
                    chat=True,
                )
                for i in range(64)
            ),
            candidates=("Fund parks first.", "Parking is essential."),
        )
        full = backend.score_matrix([request])[0]
        assert full.path == "fused"

        config = backend.config
        page_bytes = (
            config.n_layers * 16 * config.n_kv_heads * config.head_dim * 4 * 2
        )
        # Recompute the fused layout's shared-page total so the shrunken
        # budget leaves room for the shared pages plus only ~8 rows of
        # private tail pages — forcing the 128-row batch to chunk.
        shared_pages = 0
        for agent in request.agents:
            ids = backend.tokenizer.encode(
                backend._score_prefix(agent.to_score_request("")),
                add_bos=True,
            )
            shared_pages += ((len(ids) - 1) // 16 * 16) // 16
        cap = backend._session_budget.cap
        backend._session_budget.cap = page_bytes * (shared_pages + 8 * 8 + 1)
        chunks_before = backend.matrix_stats["chunks"]
        fallbacks_before = backend.matrix_stats["fallbacks"]
        try:
            chunked = backend.score_matrix([request])[0]
        finally:
            backend._session_budget.cap = cap
        assert chunked.path == "fused"
        assert backend.matrix_stats["fallbacks"] == fallbacks_before
        assert backend.matrix_stats["chunks"] - chunks_before > 1
        assert np.array_equal(
            np.asarray(chunked.utilities), np.asarray(full.utilities)
        )

    def test_dp4_matches_dp1(self, tiny_backends):
        """Sharding the row batch over the dp mesh must not change the
        utilities (8 virtual CPU devices from conftest)."""
        from consensus_tpu.backends.tpu import TPUBackend

        base = tiny_backends["tiny-gemma2"]
        wide = TPUBackend(
            model="tiny-gemma2", dtype="float32", max_context=256, dp=4,
            params=base.params, config=base.config,
        )
        request = _tiny_request(n_agents=8, n_candidates=4)
        r1 = base.score_matrix([request])[0]
        r4 = wide.score_matrix([request])[0]
        assert r1.path == r4.path == "fused"
        assert np.array_equal(
            np.asarray(r1.utilities), np.asarray(r4.utilities)
        )
        assert r1.best == r4.best

    def test_token_accounting(self, tiny_backends):
        backend = tiny_backends["tiny-gemma2"]
        request = _tiny_request()
        before = backend.token_counts["scored"]
        backend.score_matrix([request])
        scored = backend.token_counts["scored"] - before
        cont_tokens = sum(
            len(backend.tokenizer.encode(c)) for c in request.candidates
        )
        assert scored == len(request.agents) * cont_tokens


# ---------------------------------------------------------------------------
# The score chunk's head: every position against streamed vocabulary tiles
# (PR 28)
# ---------------------------------------------------------------------------

_PAGE = 16
_ROWS, _WIDTH = 8, 32
#: Stream lengths by row (a row's query block is its stream but the last
#: token: 7 to 31 real columns of 32, the rest padding) and where each row's
#: scored columns start.  Row 5 scores nothing.
_STREAMS = (32, 20, 9, 27, 16, 12, 8, 31)
_SCORE_FROM = (4, 10, 0, 25, 3, None, 6, 1)


def _chunk_inputs(vocab):
    """A chunk of rows that hold their whole stream in pages of their own."""
    rng = np.random.default_rng(28)
    blocks = _WIDTH // _PAGE
    sink = _ROWS * blocks
    tokens = np.zeros((_ROWS, _WIDTH), np.int32)
    targets = np.zeros((_ROWS, _WIDTH), np.int32)
    score_mask = np.zeros((_ROWS, _WIDTH), bool)
    chunk_valid = np.zeros((_ROWS, _WIDTH), bool)
    streams = np.zeros((_ROWS, _WIDTH), np.int32)
    stream_valid = np.zeros((_ROWS, _WIDTH), bool)
    write_pages = np.full((_ROWS, _WIDTH), sink, np.int32)
    cols = np.arange(_WIDTH)
    for r, (n, start) in enumerate(zip(_STREAMS, _SCORE_FROM)):
        ids = rng.integers(12, vocab, size=n)
        streams[r, :n], stream_valid[r, :n] = ids, True
        tokens[r, : n - 1], targets[r, : n - 1] = ids[:-1], ids[1:]
        chunk_valid[r, : n - 1] = True
        write_pages[r, : n - 1] = r * blocks + cols[: n - 1] // _PAGE
        if start is not None:
            # Past the real columns too: the program has to drop those.
            score_mask[r, start:] = True
    tables = np.arange(_ROWS * blocks, dtype=np.int32).reshape(_ROWS, blocks)
    lengths = np.asarray(_STREAMS, np.int32) - 1
    offsets = np.tile(cols % _PAGE, (_ROWS, 1)).astype(np.int32)
    return dict(
        tokens=tokens, targets=targets, score_mask=score_mask,
        chunk_valid=chunk_valid, tables=tables, lengths=lengths,
        write_pages=write_pages, write_offsets=offsets,
        streams=streams, stream_valid=stream_valid, num_pages=sink,
    )


def _score_chunk_program(monkeypatch, tile):
    """``paged_score_chunk`` under a ``jit`` of its own, with the tile's
    width fixed where the program would take it from the shapes.  A new
    function object a call: ``jit`` keeps its traces by function, and a
    trace made under one tile would serve the next."""
    import jax

    from consensus_tpu.models import stepper

    if tile is not None:
        monkeypatch.setattr(stepper, "score_vocab_tile", lambda positions: tile)

    def program(*args, **kwargs):
        return stepper.paged_score_chunk.__wrapped__(*args, **kwargs)

    return jax.jit(program, static_argnums=(1,))


def _run_score_chunk(program, params, config, chunk):
    import jax.numpy as jnp

    from consensus_tpu.models.stepper import make_page_state

    state = make_page_state(config, chunk["num_pages"], _PAGE, ssm_rows=8)
    stats, _ = program(
        params, config, jnp.asarray(chunk["tokens"]), jnp.asarray(chunk["targets"]),
        jnp.asarray(chunk["score_mask"]), jnp.asarray(chunk["chunk_valid"]),
        state, jnp.asarray(chunk["tables"]), jnp.asarray(chunk["lengths"]),
        jnp.asarray(chunk["write_pages"]), jnp.asarray(chunk["write_offsets"]),
        ssm_rows=jnp.zeros((_ROWS,), jnp.int32) if config.has_ssm else None,
    )
    return [np.asarray(s) for s in stats]


def _expected_reductions(params, config, chunk):
    """The four reductions from ``token_logprobs``: full float32 logits."""
    import jax.numpy as jnp

    from consensus_tpu.models.transformer import token_logprobs

    full = np.asarray(token_logprobs(
        params, config, jnp.asarray(chunk["streams"]),
        jnp.asarray(chunk["stream_valid"]),
    ))
    lp = np.zeros((_ROWS, _WIDTH), np.float64)
    lp[:, :-1] = full[:, 1:]  # column p scores stream token p + 1
    mask = chunk["score_mask"] & chunk["chunk_valid"]
    last = np.zeros((_ROWS,))
    for r in range(_ROWS):
        scored = np.flatnonzero(mask[r])
        if scored.size:
            last[r] = lp[r, scored[-1]]
    return (
        np.where(mask, lp, 0.0).sum(axis=1), last,
        np.where(mask, np.exp(lp), 0.0).sum(axis=1), mask.sum(axis=1),
    )


@pytest.fixture(scope="module")
def head_models():
    """(config, float32 weights) by name: a final softcap and a tied head,
    a plain untied head, and the hybrid block's ``lm_head_multiplier``."""
    import jax
    import jax.numpy as jnp

    from consensus_tpu.models.config import get_model_config
    from consensus_tpu.models.transformer import init_params

    models = {}
    for name in ("tiny-gemma2", "tiny-llama3", "tiny-falcon-h1"):
        config = get_model_config(name)
        models[name] = (
            config, init_params(config, jax.random.PRNGKey(28), jnp.float32)
        )
    return models


class TestStreamedHead:
    #: A tile that divides the vocabulary (268 = 4 x 67, 512 = 4 x 128), one
    #: that leaves a ragged last tile, one wider than the vocabulary, and the
    #: program's own rule.
    @pytest.mark.parametrize("tile", ["divides", 100, 1000, None])
    @pytest.mark.parametrize(
        "model", ["tiny-gemma2", "tiny-llama3", "tiny-falcon-h1"]
    )
    def test_reductions_match_full_logits(self, head_models, monkeypatch, model, tile):
        config, params = head_models[model]
        if tile == "divides":
            tile = config.vocab_size // 4
        chunk = _chunk_inputs(config.vocab_size)
        program = _score_chunk_program(monkeypatch, tile)
        sum_lp, last_lp, sum_exp, counts = _run_score_chunk(
            program, params, config, chunk
        )
        want_sum, want_last, want_exp, want_counts = _expected_reductions(
            params, config, chunk
        )
        assert counts.tolist() == want_counts.tolist()
        # float32 sums of at most 31 log-probabilities of order 5.
        np.testing.assert_allclose(sum_lp, want_sum, atol=2e-4, rtol=0)
        np.testing.assert_allclose(last_lp, want_last, atol=2e-5, rtol=0)
        np.testing.assert_allclose(sum_exp, want_exp, atol=1e-6, rtol=1e-4)
        # The row that scores nothing: what the old scan's carry gave.
        assert counts[5] == 0 and last_lp[5] == 0.0 and sum_lp[5] == 0.0
        # Columns past a row's stream are masked however the caller's
        # ``score_mask`` reads there: row 3 scores its one last real column.
        assert counts[3] == 1 and sum_lp[3] == last_lp[3]

    @pytest.mark.parametrize("tile", [100, None])
    def test_an_int8_head_goes_through_the_tile(self, head_models, monkeypatch, tile):
        from consensus_tpu.models.quant import QTensor, quantize_params

        config, params = head_models["tiny-llama3"]
        quantized = quantize_params(params)
        assert isinstance(quantized["lm_head"], QTensor)
        chunk = _chunk_inputs(config.vocab_size)
        got = _run_score_chunk(
            _score_chunk_program(monkeypatch, tile), quantized, config, chunk
        )
        want = _expected_reductions(quantized, config, chunk)
        assert got[3].tolist() == want[3].tolist()
        np.testing.assert_allclose(got[0], want[0], atol=2e-4, rtol=0)
        np.testing.assert_allclose(got[1], want[1], atol=2e-5, rtol=0)
        # And it is the quantised head that was scored, not the plain one.
        plain = _expected_reductions(params, config, chunk)
        assert np.abs(want[0] - plain[0]).max() > 1e-3

    def test_lowered_text_streams_tiles_over_all_positions(
        self, head_models, monkeypatch
    ):
        """No (rows, columns, vocabulary) array and no product of a single
        column with the head: one product a tile, every position deep."""
        import jax
        import jax.numpy as jnp

        from consensus_tpu.models.stepper import make_page_state

        config, params = head_models["tiny-gemma2"]
        vocab, tile = config.vocab_size, 100
        shapes = jax.eval_shape(lambda: params)
        state = jax.eval_shape(lambda: make_page_state(config, 16, _PAGE))
        ints = jnp.zeros((_ROWS, _WIDTH), jnp.int32)
        bools = jnp.ones((_ROWS, _WIDTH), bool)
        text = _score_chunk_program(monkeypatch, tile).lower(
            shapes, config, ints, ints, bools, bools, state,
            jnp.zeros((_ROWS, 2), jnp.int32), jnp.full((_ROWS,), _WIDTH, jnp.int32),
            ints, ints,
        ).as_text()
        assert f"tensor<{_ROWS}x{_WIDTH}x{tile}xf32>" in text
        assert f"x{vocab}xf32>" not in text  # (B, S, V) and (B, V) alike
        products = re.findall(r"stablehlo\.dot_general.*-> tensor<([0-9x]+)xf32>", text)
        assert f"{_ROWS}x{_WIDTH}x{tile}" in products
        assert f"{_ROWS}x{tile}" not in products and f"{_ROWS}x{vocab}" not in products

    @pytest.mark.parametrize(
        "rows, width, columns",
        [(8, 16, 4096), (32, 256, 4096), (64, 256, 4096), (64, 512, 2048),
         (64, 1024, 1024), (64, 2048, 1024)],
    )
    def test_tile_width_comes_from_the_shapes(self, rows, width, columns):
        from consensus_tpu.models.stepper import score_vocab_tile

        assert score_vocab_tile(rows * width) == columns


# ---------------------------------------------------------------------------
# Loadgen many-agent expansion (satellite 6)
# ---------------------------------------------------------------------------


class TestLoadgenAgents:
    def test_expansion_deterministic_and_sized(self):
        from consensus_tpu.serve.loadgen import scenario_requests

        payloads = scenario_requests(3, agents=64)
        assert all(len(p["agent_opinions"]) == 64 for p in payloads)
        again = scenario_requests(3, agents=64)
        assert [p["agent_opinions"] for p in payloads] == [
            p["agent_opinions"] for p in again
        ]
        # Variant copies are textually distinct from their base opinion.
        opinions = payloads[0]["agent_opinions"]
        names = list(opinions)
        assert any("_v" in n for n in names)
        base = {n: o for n, o in opinions.items() if "_v" not in n}
        for name, text in opinions.items():
            if "_v" in name:
                assert text not in base.values()

    def test_truncation_below_base_count(self):
        from consensus_tpu.serve.loadgen import scenario_requests

        payloads = scenario_requests(1, agents=2)
        assert len(payloads[0]["agent_opinions"]) == 2
