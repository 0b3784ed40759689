"""Falcon-H1's block (a Mamba-2 mixer beside grouped-query attention, muP
multipliers) through every program the serving path reaches, held to the
plain reference of ``tests/reference_falcon_h1.py`` on the tiny preset, in
float32 on the CPU.

Tolerances, and why each:

``LOGIT_TOL`` 2e-4    logits are of unit order (the weights are drawn so);
                      float32 sums over at most 128 terms in another order
                      differ by about 1e-6, and the chunked scan's products of
                      decays by a few 1e-6 more.  Holding the recurrent state
                      or the recurrence in bfloat16 moves them by 3e-3 or
                      more (``test_a_bfloat16_state_fails``).
``LOGPROB_TOL`` 2e-4  the same, on mean log-probabilities of a continuation.
``GAP_TOL`` 2e-4      a greedily decoded token's reference logit may lie this
                      far below the reference's best: an argmax may change on
                      rounding, a wrong state moves logits by 0.1 and more.
"""

import dataclasses
import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_falcon_h1 as ref
from consensus_tpu.models import transformer as tf
from consensus_tpu.models.config import (
    RecurrentStateUnsupported,
    get_model_config,
)
from consensus_tpu.models.generate import (
    _prefill_classic,
    generate_tokens,
    generate_tokens_segmented,
    generate_tokens_shared_trunk,
    generate_tokens_shared_trunk_segmented,
)
from consensus_tpu.models.transformer import init_params

LOGIT_TOL = 2e-4
LOGPROB_TOL = 2e-4
GAP_TOL = 2e-4

CONFIG = get_model_config("tiny-falcon-h1")

#: The reference under ``jit`` (one program a length): eagerly its scan over
#: positions is traced anew at every call.
REF_FORWARD = jax.jit(ref.forward, static_argnums=(0, 3))
REF_LOGPROBS = jax.jit(ref.token_logprobs, static_argnums=(0, 3))


@pytest.fixture(scope="module")
def params():
    return init_params(CONFIG, jax.random.PRNGKey(11), jnp.float32)


def _tokens(seed, n, low=12, high=268):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), low, high))


def _left_pad(rows, width):
    tokens = np.zeros((len(rows), width), np.int32)
    valid = np.zeros((len(rows), width), bool)
    for i, ids in enumerate(rows):
        tokens[i, width - len(ids):] = ids
        valid[i, width - len(ids):] = True
    return jnp.asarray(tokens), jnp.asarray(valid)


# -- the preset and its weights -----------------------------------------------------


def test_the_preset_has_every_multiplier_off_one_and_draws_unit_logits(params):
    c = CONFIG
    scalars = (c.embedding_multiplier, c.attention_in_multiplier,
               c.attention_out_multiplier, c.key_multiplier, c.ssm_in_multiplier,
               c.ssm_out_multiplier, c.lm_head_multiplier)
    for m in scalars + c.ssm_slice_multipliers + c.mlp_multipliers:
        assert m is not None and m != 1.0
    logits = REF_FORWARD(c, params, jnp.asarray(_tokens(0, 40)))
    # Not flat: with fan-in draws under a head multiplier of 0.0078 every
    # log-probability would be -log(V) and no precision could be told apart.
    assert 0.5 < float(jnp.std(logits)) < 2.0
    layers = params["layers"]
    for name in ("ssm_a_log", "ssm_dt_bias", "ssm_d"):
        assert layers[name].dtype == jnp.float32
    a = np.exp(np.asarray(layers["ssm_a_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0
    step = np.log1p(np.exp(np.asarray(layers["ssm_dt_bias"])))
    assert step.min() >= 0.001 * 0.999 and step.max() <= 0.1 * 1.001
    assert np.all(np.asarray(layers["ssm_d"]) == 1.0)


def test_the_mixers_small_leaves_stay_float32_in_a_bfloat16_tree():
    tree = init_params(CONFIG, jax.random.PRNGKey(3), jnp.bfloat16)
    kinds = {name: leaf.dtype for name, leaf in tree["layers"].items()}
    assert {n for n, d in kinds.items() if d == jnp.float32} == {
        "ssm_a_log", "ssm_dt_bias", "ssm_d"}
    assert kinds["ssm_in"] == kinds["ssm_conv_w"] == jnp.bfloat16


# -- the full forward -----------------------------------------------------------------


@pytest.mark.parametrize("length", [1, 7, 8, 21, 40])
def test_the_full_forward_gives_the_references_logits(params, length):
    ids = _tokens(length, length)
    tokens = jnp.asarray(ids)[None]
    positions = jnp.arange(length)[None]
    logits, _ = tf.forward(params, CONFIG, tokens, positions,
                           jnp.ones((1, length), bool))
    want = REF_FORWARD(CONFIG, params, jnp.asarray(ids))
    np.testing.assert_allclose(logits[0], want, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("pad", ["left", "right"])
def test_padded_positions_change_nothing(params, pad):
    """Scoring pads on the right, the embedder and generation on the left:
    the real positions' logits are those of the row alone."""
    ids, width = _tokens(5, 13), 24
    tokens = np.zeros((1, width), np.int32)
    valid = np.zeros((1, width), bool)
    at = slice(width - 13, width) if pad == "left" else slice(0, 13)
    tokens[0, at], valid[0, at] = ids, True
    positions = jnp.maximum(jnp.cumsum(valid.astype(np.int32), axis=1) - 1, 0)
    logits, _ = tf.forward(params, CONFIG, jnp.asarray(tokens), positions,
                           jnp.asarray(valid))
    want = REF_FORWARD(CONFIG, params, jnp.asarray(ids))
    np.testing.assert_allclose(logits[0, at], want, atol=LOGIT_TOL, rtol=0)


def test_streamed_scoring_gives_the_references_logprobs(params):
    ids, width = _tokens(6, 19), 24
    tokens = np.zeros((2, width), np.int32)
    valid = np.zeros((2, width), bool)
    tokens[0, :19], valid[0, :19] = ids, True
    tokens[1, :11], valid[1, :11] = ids[:11], True
    got = tf.token_logprobs_streamed(params, CONFIG, jnp.asarray(tokens),
                                     jnp.asarray(valid), vocab_chunk=128)
    want = REF_LOGPROBS(CONFIG, params, jnp.asarray(ids))
    np.testing.assert_allclose(got[0, :19], want, atol=LOGPROB_TOL, rtol=0)
    np.testing.assert_allclose(got[1, :11], want[:11], atol=LOGPROB_TOL, rtol=0)


def test_a_bfloat16_state_fails(params):
    """The control of the tolerances: the reference with its recurrent state
    held in bfloat16 is out by more than ten times ``LOGIT_TOL``."""
    ids = jnp.asarray(_tokens(7, 40))
    exact = REF_FORWARD(CONFIG, params, ids)
    low = REF_FORWARD(CONFIG, params, ids, jnp.bfloat16)
    assert float(jnp.max(jnp.abs(low - exact))) > 10 * LOGIT_TOL


# -- the scan: chunked against sequential ----------------------------------------------


def _scan_inputs(seed, batch, span):
    c = CONFIG
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (batch, span, c.ssm_heads, c.ssm_head_dim))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, span, c.ssm_heads)) - 2)
    a = -jnp.exp(jax.random.uniform(keys[2], (c.ssm_heads,), minval=0.0, maxval=2.7))
    bm = jax.random.normal(keys[3], (batch, span, c.ssm_groups, c.ssm_state))
    cm = jax.random.normal(keys[4], (batch, span, c.ssm_groups, c.ssm_state))
    h0 = jax.random.normal(
        keys[5], (batch, c.ssm_heads, c.ssm_head_dim, c.ssm_state))
    return x, dt, a, bm, cm, h0


@pytest.mark.parametrize("span", [2, 5, 8, 13, 16, 21])
@pytest.mark.parametrize("pads", ["none", "left", "right"])
def test_the_chunked_scan_is_the_sequential_one(span, pads):
    """Spans that are no multiple of the chunk (8), a state carried in, and
    padded positions (step size 0) that must leave the state alone."""
    c = CONFIG
    x, dt, a, bm, cm, h0 = _scan_inputs(span, 2, span)
    keep = np.ones((2, span), bool)
    if pads == "left":
        keep[0, : span // 3 + 1] = False
    elif pads == "right":
        keep[1, span - span // 2:] = False
    dt = jnp.where(jnp.asarray(keep)[:, :, None], dt, 0.0)
    y, h = tf._ssm_scan_chunked(c, x, dt, a, bm, cm, h0)
    reps = c.ssm_heads // c.ssm_groups
    want, state = [], h0
    for t in range(span):
        y_t, state = tf._ssm_scan_step(
            x[:, t], dt[:, t], a, jnp.repeat(bm[:, t], reps, axis=1),
            jnp.repeat(cm[:, t], reps, axis=1), state)
        want.append(y_t)
    want = jnp.stack(want, axis=1)
    real = jnp.asarray(keep)[:, :, None, None]
    np.testing.assert_allclose(jnp.where(real, y, 0), jnp.where(real, want, 0),
                               atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(h, state, atol=1e-4, rtol=1e-5)


def test_a_row_without_a_valid_position_keeps_its_state(params):
    lp = jax.tree.map(lambda leaf: leaf[0], params["layers"])
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 6, CONFIG.d_model))
    window = jax.random.normal(
        jax.random.PRNGKey(2), (2, CONFIG.ssm_conv - 1, CONFIG.ssm_conv_dim))
    h = jax.random.normal(jax.random.PRNGKey(3), (
        2, CONFIG.ssm_heads, CONFIG.ssm_head_dim, CONFIG.ssm_state))
    valid = jnp.asarray([[False] * 6, [True] * 4 + [False] * 2])
    _, (new_window, new_h) = tf.ssm_mixer(CONFIG, lp, u, (window, h), valid)
    np.testing.assert_array_equal(new_window[0], window[0])
    np.testing.assert_array_equal(new_h[0], h[0])
    assert float(jnp.max(jnp.abs(new_h[1] - h[1]))) > 1e-3


# -- prefill, then decode through the cache ----------------------------------------------


def _greedy_gaps(params, prompt, generated):
    """How far each generated token's reference logit lies below the
    reference's best among sampleable ids, the prefix being what was
    generated before it."""
    stream = np.concatenate([prompt, generated])
    logits = np.asarray(REF_FORWARD(CONFIG, params, jnp.asarray(stream)))
    gaps = []
    for j, token in enumerate(generated):
        row = logits[len(prompt) - 1 + j]
        gaps.append(float(row.max() - row[token]))
    return gaps


PROMPTS = [_tokens(21, 17), _tokens(22, 9), _tokens(23, 26)]


@pytest.mark.parametrize("program", ["monolithic", "segmented"])
def test_classic_generation_decodes_what_the_reference_puts_first(params, program):
    """Left-padded prompts of unequal length: prefill, then every decode
    step through the cache and the carried state, against the reference's
    full forward over the prefix."""
    tokens, valid = _left_pad(PROMPTS, 32)
    keys = jnp.zeros((3, 2), jnp.uint32)
    common = dict(temperature=jnp.zeros((3,)), eos_ids=jnp.asarray([-1], jnp.int32))
    if program == "monolithic":
        out = generate_tokens(params, CONFIG, tokens, valid, keys,
                              max_new_tokens=16, **common)
    else:
        out = generate_tokens_segmented(
            params, CONFIG, tokens, valid, keys, max_new_tokens=16, seg_len=8,
            kv_quant=False, **common)
    generated = np.asarray(out.tokens)
    assert generated.shape == (3, 16)
    for prompt, row in zip(PROMPTS, generated):
        assert max(_greedy_gaps(params, prompt, row)) < GAP_TOL


@pytest.mark.parametrize("program", ["monolithic", "segmented"])
def test_shared_trunk_generation_is_the_classic_path_row_for_row(params, program):
    """One trunk's state forked to B rows: sampled with per-row keys, each
    row is what the classic path decodes from the same prompt and key."""
    rows = 4
    prompt = PROMPTS[0]
    keys = jax.random.split(jax.random.PRNGKey(5), rows)
    tokens1, valid1 = _left_pad([prompt], 32)
    tokens, valid = _left_pad([prompt] * rows, 32)
    common = dict(temperature=jnp.full((rows,), 0.8),
                  eos_ids=jnp.asarray([-1], jnp.int32))
    classic = generate_tokens(params, CONFIG, tokens, valid, keys,
                              max_new_tokens=16, **common)
    if program == "monolithic":
        shared = generate_tokens_shared_trunk(
            params, CONFIG, tokens1, valid1, rows, keys, max_new_tokens=16,
            **common)
    else:
        shared = generate_tokens_shared_trunk_segmented(
            params, CONFIG, tokens1, valid1, rows, keys, max_new_tokens=16,
            seg_len=8, kv_quant=False, **common)
    np.testing.assert_array_equal(np.asarray(shared.tokens),
                                  np.asarray(classic.tokens))
    assert len({tuple(r) for r in np.asarray(shared.tokens).tolist()}) == rows


def test_shared_trunk_greedy_decodes_what_the_reference_puts_first(params):
    tokens1, valid1 = _left_pad([PROMPTS[2]], 32)
    out = generate_tokens_shared_trunk(
        params, CONFIG, tokens1, valid1, 2, jnp.zeros((2, 2), jnp.uint32),
        max_new_tokens=16, temperature=jnp.zeros((2,)),
        eos_ids=jnp.asarray([-1], jnp.int32))
    for row in np.asarray(out.tokens):
        assert max(_greedy_gaps(params, PROMPTS[2], row)) < GAP_TOL


def test_the_prefill_leaves_the_state_the_decode_starts_from(params):
    """The cache's recurrent state after a left-padded prefill is the state
    after the row's own tokens: one more position through the cache gives
    the reference's logits for the longer sequence."""
    tokens, valid = _left_pad(PROMPTS, 32)
    _, trunk, last_pos = _prefill_classic(params, CONFIG, tokens, valid)
    assert trunk.ssm.h.shape == (CONFIG.n_layers, 3, CONFIG.ssm_heads,
                                 CONFIG.ssm_head_dim, CONFIG.ssm_state)
    assert trunk.ssm.h.dtype == jnp.float32
    nxt = jnp.asarray([33, 44, 55], jnp.int32)
    tail = jnp.zeros((CONFIG.n_layers, 3, 1, CONFIG.n_kv_heads, CONFIG.head_dim))
    hidden, *_ = tf.forward_trunk_tail(
        params, CONFIG, nxt, last_pos + 1, trunk, tail, tail,
        (last_pos + 1)[:, None], jnp.asarray(0), 1, 3, ssm=trunk.ssm)
    logits = tf.project_logits(params, CONFIG, hidden)
    for row, prompt in enumerate(PROMPTS):
        want = REF_FORWARD(CONFIG, params, jnp.asarray(list(prompt) + [int(nxt[row])]))
        np.testing.assert_allclose(logits[row], want[-1], atol=LOGIT_TOL, rtol=0)


# -- the shared-context scorer -------------------------------------------------------------


def test_shared_context_scoring_forks_the_contexts_state(params):
    ctx, conts = _tokens(31, 23), [_tokens(32, 9), _tokens(33, 5), _tokens(34, 12)]
    ctx_tokens = np.zeros((1, 32), np.int32)
    ctx_valid = np.zeros((1, 32), bool)
    ctx_tokens[0, :23], ctx_valid[0, :23] = ctx, True
    cont_tokens = np.zeros((4, 16), np.int32)
    cont_valid = np.zeros((4, 16), bool)
    for i, cont in enumerate(conts):
        cont_tokens[i, :len(cont)], cont_valid[i, :len(cont)] = cont, True
    got = tf.shared_context_token_logprobs(
        params, CONFIG, jnp.asarray(ctx_tokens), jnp.asarray(ctx_valid),
        jnp.asarray(cont_tokens), jnp.asarray(cont_valid), vocab_chunk=128)
    for i, cont in enumerate(conts):
        want = REF_LOGPROBS(
            CONFIG, params, jnp.asarray(np.concatenate([ctx, cont])))[23:]
        np.testing.assert_allclose(got[i, :len(cont)], want, atol=LOGPROB_TOL, rtol=0)


# -- the backend: paged prefill in chunks, score rows from forked snapshots ------------------


@pytest.fixture(scope="module")
def backend(params):
    from consensus_tpu.backends.tpu import TPUBackend

    return TPUBackend(config=CONFIG, params=params, dtype="float32",
                      max_context=1024, max_batch_rows=8)


def _matrix_request(contexts, candidates):
    from consensus_tpu.backends.score_matrix import AgentContext, ScoreMatrixRequest

    return ScoreMatrixRequest(
        agents=tuple(AgentContext(context=text, chat=False) for text in contexts),
        candidates=tuple(candidates), stat="mean")


#: Contexts of 297, 37 and 9 byte-tokens (with the BOS): none a multiple of
#: the 16-token page; the first is prefilled in two 256-token chunks with the
#: others idle in the second; the last is shorter than a page (its snapshot
#: is the zero state).
CONTEXTS = ["the river rose. " * 18 + "and then", "short context of some words and more",
            "tiny ctx"]
CANDIDATES = ["we should build the bridge", "a longer statement, with a clause"]


@pytest.fixture(scope="module")
def reference_matrix(backend, params):
    lengths = [len(backend.tokenizer.encode(c, add_bos=True)) for c in CONTEXTS]
    assert lengths == [297, 37, 9]
    want = np.zeros((len(CANDIDATES), len(CONTEXTS)))
    for a, context in enumerate(CONTEXTS):
        prefix = backend.tokenizer.encode(context, add_bos=True)
        for c, candidate in enumerate(CANDIDATES):
            cont = backend.tokenizer.encode(candidate)
            lp = REF_LOGPROBS(CONFIG, params, jnp.asarray(prefix + cont))
            want[c, a] = float(jnp.mean(lp[len(prefix):]))
    return want


def test_the_fused_score_matrix_gives_the_references_utilities(
        backend, reference_matrix):
    before = backend.matrix_stats["fallbacks"]
    result = backend.score_matrix([_matrix_request(CONTEXTS, CANDIDATES)])[0]
    assert backend.matrix_stats["fallbacks"] == before and result.path == "fused"
    np.testing.assert_allclose(
        result.utilities, reference_matrix, atol=LOGPROB_TOL, rtol=0)


def test_a_state_zeroed_at_the_fork_fails_the_same_comparison(
        backend, reference_matrix, monkeypatch):
    """The planted fault: every score row starts from a zero state where it
    should start from its context's snapshot."""
    from consensus_tpu.models import stepper

    real = stepper.fork_ssm
    monkeypatch.setattr(
        stepper, "fork_ssm",
        lambda state, rows: jax.tree.map(jnp.zeros_like, real(state, rows)))
    jax.clear_caches()
    try:
        result = backend.score_matrix([_matrix_request(CONTEXTS, CANDIDATES)])[0]
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    gap = np.abs(np.asarray(result.utilities) - reference_matrix)
    assert gap[:, :2].min() > 10 * LOGPROB_TOL  # contexts with pages behind them
    # The context shorter than a page has no snapshot to lose.
    assert gap[:, 2].max() < LOGPROB_TOL


def test_the_backend_generates_and_embeds_and_counts_its_forks(backend):
    from consensus_tpu.backends.base import GenerationRequest
    from consensus_tpu.obs.metrics import get_registry

    def forked(kind):
        family = get_registry().snapshot()["families"].get(
            "backend_state_fork_rows_total", {"series": []})
        return sum(s["value"] for s in family["series"]
                   if s["labels"]["kind"] == kind)

    before = forked("generate")
    requests = [GenerationRequest(user_prompt="what should we do?", max_tokens=8,
                                  temperature=0.7, seed=100 + i) for i in range(8)]
    results = backend.generate(requests)
    assert len(results) == 8 and len({r.text for r in results}) > 1
    assert forked("generate") - before == 8
    vectors = backend.embed(["one text", "another, longer text"])
    assert vectors.shape == (2, CONFIG.d_model)
    np.testing.assert_allclose(np.linalg.norm(vectors, axis=1), 1.0, atol=1e-5)


# -- budgets --------------------------------------------------------------------------------


def test_a_rows_recurrent_bytes(backend):
    c = CONFIG
    per_layer = 4 * c.ssm_heads * c.ssm_head_dim * c.ssm_state \
        + 4 * (c.ssm_conv - 1) * c.ssm_conv_dim
    assert c.ssm_state_bytes(4) == c.n_layers * per_layer == 11264
    assert backend._recurrent_row_bytes() == 11264
    assert get_model_config("tiny-llama3").ssm_state_bytes(2) == 0
    # The published widths: 32 x 128 x 256 float32 and a 3 x 5120 bfloat16
    # window a layer, 4.03 MiB.
    full = dataclasses.replace(
        c, n_layers=4, ssm_heads=32, ssm_head_dim=128, ssm_state=256,
        ssm_inner=4096, ssm_groups=2)
    assert full.ssm_state_bytes(2) == 4 * (4 * 32 * 128 * 256 + 2 * 3 * 5120)
    assert round(full.ssm_state_bytes(2) / 4 / 2**20, 2) == 4.03


def test_generation_rows_count_the_state_twice_beside_the_tail(backend, monkeypatch):
    from consensus_tpu.backends import tpu

    c = CONFIG
    unit = 2 * c.n_layers * c.n_kv_heads * c.head_dim * 4
    budget = tpu._HBM_BYTES - backend._params_bytes - tpu._ACTIVATION_RESERVE_BYTES

    def ladder(allowed):
        bucket = 1
        while bucket * 2 <= allowed:
            bucket *= 2
        if bucket >= 2 and bucket + bucket // 2 <= allowed:
            bucket += bucket // 2
        return bucket

    for width, new in ((0, 64), (512, 128)):
        per_row = (width + 2 * new) * unit + 2 * 11264
        assert backend._generate_rows_allowed(width, new) == ladder(budget // per_row)
    # A dense configuration's rows are what they were: the tail alone.
    monkeypatch.setattr(backend, "config", get_model_config("tiny-llama3"))
    assert backend._recurrent_row_bytes() == 0
    assert backend._generate_rows_allowed(512, 128) == ladder(
        budget // ((512 + 256) * unit))


def test_a_score_chunks_temporaries_at_the_published_widths(backend, monkeypatch):
    """What keeps a 64-row score chunk of the published model off a 16 GB
    chip: 7.8 GB of a layer's temporaries, 0.5 GB of the head's and 2.3 GB of
    pool beside 8.79 GB of weights; 32 rows take half of a layer's and of the
    pool, 0.26 GB for the head (the same 4,096 columns a tile), and fit."""
    from consensus_tpu.backends import tpu

    full = dataclasses.replace(
        CONFIG, d_model=5120, n_layers=4, n_heads=20, n_kv_heads=4, head_dim=128,
        ffn_hidden=21504, ssm_heads=32, ssm_head_dim=128, ssm_state=256,
        ssm_inner=4096, ssm_chunk=128, vocab_size=261120)
    monkeypatch.setattr(backend, "config", full)
    monkeypatch.setattr(
        backend, "params", {"embed": jnp.zeros((1, 1), jnp.bfloat16)})

    def head(rows, tile):  # one float32 tile, its rows, the targets' rows
        return rows * 256 * tile * 4 + (rows * 256 + tile) * 5120 * 2

    wide = backend._score_chunk_transient_bytes(64, 256, 1600)
    half = backend._score_chunk_transient_bytes(32, 256, 1600)
    assert round((wide - head(64, 4096)) / 1e9, 1) == 7.8
    assert (half - head(32, 4096)) * 2 == wide - head(64, 4096)
    assert round(head(64, 4096) / 1e9, 2) == 0.48
    assert round(head(32, 4096) / 1e9, 2) == 0.26
    left = tpu._HBM_BYTES - 8_788_709_632
    row = backend._recurrent_row_bytes()
    assert row == 4 * (4 * 32 * 128 * 256 + 2 * 3 * 5120)
    assert wide + (8 + 2 * 64) * row > left
    assert half + (8 + 2 * 32) * row < left


def test_the_engine_reserves_a_rows_state_in_pages(backend):
    from consensus_tpu.backends.engine import DecodeEngine
    from consensus_tpu.backends.fake import FakeBackend

    page_bytes = backend._kv_page_bytes(16)
    assert backend.recurrent_state_pages(16) == -(-11264 // page_bytes) > 0
    assert backend.kv_cache_identity()[-1] == ("state", "recurrent")

    class Row:
        prompt_tokens, max_tokens = 100, 20

    engine = DecodeEngine(backend, slots=2, num_pages=64, auto_start=False)
    dense = DecodeEngine(FakeBackend(), slots=2, num_pages=64, auto_start=False)
    try:
        assert dense._state_pages == 0 and not dense.recurrent
        assert dense._pages_needed([Row(), Row()]) == 7 + 2 * 2
        assert engine._pages_needed([Row(), Row()]) == \
            7 + 2 * 2 + 2 * backend.recurrent_state_pages(16)
    finally:
        engine.close()
        dense.close()


def test_the_prefix_cache_declines_runs_without_state():
    from consensus_tpu.ops.kv_pages import PagePool, PrefixCache

    pool, seen = PagePool(8, 4), []
    cache = PrefixCache(pool, 4, identity=("m",), needs_state=True,
                        on_declined=seen.append)
    pages = pool.alloc(2)
    assert cache.insert(list(range(8)), pages) is False
    assert cache.lookup(list(range(8))) == ([], 0)
    assert cache.stats()["declined_runs"] == 2 and seen == ["insert", "lookup"]
    assert cache.stats()["entries"] == 0 and cache.stats()["misses"] == 0
    plain = PrefixCache(pool, 4, identity=("m",))
    assert plain.insert(list(range(8)), pages) is True
    assert plain.lookup(list(range(8)))[1] == 8
    assert plain.stats()["declined_runs"] == 0


# -- what refuses, by name ---------------------------------------------------------------------


def test_token_search_and_the_stream_path_refuse_by_name(backend):
    from consensus_tpu.backends.base import GenerationRequest
    from consensus_tpu.backends.engine import DecodeEngine

    with pytest.raises(RecurrentStateUnsupported, match="token-search session"):
        backend.open_fused_token_search(None)
    with pytest.raises(RecurrentStateUnsupported, match="generate_stream"):
        backend.generate_stream([GenerationRequest(user_prompt="p", max_tokens=4)])
    with pytest.raises(RecurrentStateUnsupported, match="decode_steps"):
        DecodeEngine(backend, slots=2, num_pages=64, auto_start=False,
                     decode_steps=4)
    # Not retried by the scheduler, and no fallback session takes it over.
    from consensus_tpu.backends.session import FusedSessionUnavailable
    from consensus_tpu.serve.scheduler import TRANSIENT_EXCEPTIONS

    assert not issubclass(RecurrentStateUnsupported, TRANSIENT_EXCEPTIONS)
    assert not issubclass(RecurrentStateUnsupported, FusedSessionUnavailable)


@pytest.mark.parametrize("program", [
    "search_prefill", "search_step", "suffix_propose", "rollout_scored",
    "rollout_scored_many", "rollout_verify_many", "paged_decode_step",
    "paged_decode_steps", "paged_verify_steps", "paged_gather_step"])
def test_a_program_that_cannot_carry_the_state_says_so_when_traced(program):
    import inspect

    from consensus_tpu.models import stepper

    fn = getattr(stepper, program)
    args = {name: CONFIG if name == "config" else None
            for name, p in inspect.signature(fn).parameters.items()
            if p.default is inspect.Parameter.empty or name == "config"}
    with pytest.raises(RecurrentStateUnsupported, match=program):
        fn(**args)


# -- dense configurations: nothing moved ---------------------------------------------------------

#: sha256 of ``lowered.as_text()`` of the two programs at the sizes of
#: ``tests/test_trace.py`` ``_lower_program``, and of ``init_params``' leaves
#: (path and float32 bytes) under PRNGKey(7).  The four weight pins are of
#: PR 26, the commit before the hybrid block came.  The four program pins are
#: of PR 31, which changed every program with a cache by design: the layer
#: loop carries the pools, the cache and the tail and a layer writes its part
#: in place (before it, the two ``generate_tokens_shared_trunk`` pins were of
#: PR 26 and the two ``paged_score_chunk`` pins of PR 28's streamed head).  A
#: dense program still has nothing of the hybrid block: the ``ssm``
#: assertions below.
PARENT = {
    "tiny-gemma2/generate_tokens_shared_trunk":
        "2dedda7649ac8ab14cef8a7754a528747bbd7dd0ae9ecc6ce0ffae86ccf2fb45",
    "tiny-gemma2/paged_score_chunk":
        "c599befca3063d85159c91afaf327db96f1edd64144c22a4c994188e9f985342",
    "tiny-gemma2/init/float32":
        "31d40293605e5407fe31a6a79ee7e4b983c1b492ddae03cf78db6cf00b0b96d6",
    "tiny-gemma2/init/bfloat16":
        "f4df2bac294da12861c4ccda42b003721b67df7e6381f200c92a6262db29ff25",
    "tiny-llama3/generate_tokens_shared_trunk":
        "2b529415ac0ad18d2a77e82dcebfdfa0c49b5e5b7dc7ce2ec6d4c44ad85b89c5",
    "tiny-llama3/paged_score_chunk":
        "8c0d22e16f9d9e2d057cb8a3ba95a2a3fc068cb547772ad527505174d94d6947",
    "tiny-llama3/init/float32":
        "a22fc02ac159dd2f1fa24f75a89472b9ceee55ac6f47545fe05a0d62e11ca07b",
    "tiny-llama3/init/bfloat16":
        "8792eada026ebe0af12f5b05b82427c69323213a7654b6cfc96dd9b8971849e3",
}


def _lower(program, config):
    from consensus_tpu.models.stepper import make_page_state, paged_score_chunk

    params = jax.eval_shape(
        lambda: init_params(config, jax.random.PRNGKey(0), jnp.float32))
    rows, width = 8, 32
    tokens = jnp.zeros((rows, width), jnp.int32)
    valid = jnp.ones((rows, width), bool)
    if program == "generate_tokens_shared_trunk":
        return generate_tokens_shared_trunk.lower(
            params, config, tokens[:1], valid[:1], rows,
            jnp.zeros((rows, 2), jnp.uint32), max_new_tokens=16,
            temperature=jnp.ones((rows,)),
            eos_ids=jnp.asarray([-1], jnp.int32), pad_id=0,
            init_done=jnp.zeros((rows,), bool))
    state = jax.eval_shape(lambda: make_page_state(config, 32, 16))
    return paged_score_chunk.lower(
        params, config, tokens, tokens, valid, valid, state,
        jnp.zeros((rows, 4), jnp.int32), jnp.full((rows,), width, jnp.int32),
        tokens, tokens)


@pytest.mark.parametrize("model", ["tiny-gemma2", "tiny-llama3"])
@pytest.mark.parametrize("program", ["generate_tokens_shared_trunk",
                                     "paged_score_chunk"])
def test_a_dense_programs_lowered_text_is_the_parents(model, program):
    text = _lower(program, get_model_config(model)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT[f"{model}/{program}"]
    assert "ssm" not in text and "state_fork" not in text


@pytest.mark.parametrize("model", ["tiny-gemma2", "tiny-llama3"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_dense_configurations_weights_are_the_parents_bit_for_bit(model, dtype):
    tree = init_params(get_model_config(model), jax.random.PRNGKey(7),
                       jnp.dtype(dtype))
    digest = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        digest.update(jax.tree_util.keystr(path).encode())
        digest.update(np.asarray(leaf.astype(jnp.float32)).tobytes())
    assert digest.hexdigest() == PARENT[f"{model}/init/{dtype}"]


def test_the_hybrid_programs_name_the_mixers_scopes():
    text = _lower("generate_tokens_shared_trunk", CONFIG).as_text(debug_info=True)
    for scope in ("ssm_in", "ssm_conv", "ssm_scan", "ssm_out", "state_fork"):
        assert re.search(rf'[/"]{scope}[/"]', text), scope
    state = json.dumps(sorted(tf.SSMState._fields))
    assert state == '["conv", "h"]'


# -- through the service: POST /v1/consensus ---------------------------------------------------


@pytest.fixture(scope="module")
def server(params):
    from consensus_tpu.backends import clear_backend_cache
    from consensus_tpu.serve import create_server

    instance = create_server(
        backend="tpu", port=0, default_timeout_s=600.0,
        backend_options={"config": CONFIG, "params": params, "dtype": "float32",
                         "max_context": 1024, "pin_generation_budget": True})
    instance.start()
    try:
        yield instance
    finally:
        instance.stop(drain=True)
        clear_backend_cache()


def _post(server, body):
    import urllib.error
    import urllib.request

    request = urllib.request.Request(
        server.base_url + "/v1/consensus", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(request, timeout=600) as response:
            return response.status, json.loads(response.read())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read())


_OPINIONS = {"ann": "We need more buses and fewer cars in the centre.",
             "bo": "Cars are how people with children get around.",
             "cy": "Spend the money on cycle lanes instead."}


def test_best_of_n_answers_through_the_engine(server):
    status, body = _post(server, {
        "method": "best_of_n", "issue": "How should the city change transport?",
        "agent_opinions": _OPINIONS, "seed": 5,
        "params": {"n": 4, "max_tokens": 12}})
    assert status == 200, body
    assert body["statement"].strip() and not body.get("degraded")
    assert set(body["utilities"]) == set(_OPINIONS) and body["welfare"]
    engine = server.scheduler.batching.engine
    assert engine.recurrent and engine._state_pages > 0


def test_metrics_serves_how_many_texts_were_encoded_and_how_many_reused(server):
    """``backend_tokenize_texts_total`` over a second request of the same
    issue and opinions under another seed: its prompts and contexts were
    encoded for the first, so of the texts it asks for most are reused."""
    import re
    import urllib.request

    def counts():
        with urllib.request.urlopen(server.base_url + "/metrics") as response:
            text = response.read().decode()
        found = dict.fromkeys(("encoded", "reused"), 0.0)
        for outcome, value in re.findall(
                r'^backend_tokenize_texts_total\{[^}]*outcome="(\w+)"[^}]*\} (\S+)$',
                text, re.MULTILINE):
            found[outcome] += float(value)
        return found

    before = counts()
    assert before["encoded"] > 0 and before["reused"] > 0
    status, body = _post(server, {
        "method": "best_of_n", "issue": "How should the city change transport?",
        "agent_opinions": _OPINIONS, "seed": 6,
        "params": {"n": 4, "max_tokens": 12}})
    assert status == 200, body
    after = counts()
    encoded = after["encoded"] - before["encoded"]
    reused = after["reused"] - before["reused"]
    assert 0 < encoded <= reused, (before, after)


@pytest.mark.parametrize("method", ["beam_search", "mcts"])
def test_a_token_search_method_answers_a_client_error_that_names_it(server, method):
    status, body = _post(server, {
        "method": method, "issue": "How should the city change transport?",
        "agent_opinions": _OPINIONS, "seed": 5, "params": {"max_tokens": 6}})
    assert status == 400, body
    error = body["error"]
    assert error["type"] == "method_unsupported_for_model"
    assert error["method"] == method and method in error["message"]
    assert "recurrent" in error["message"] and "token-search" in error["message"]
    assert error["request_id"]
