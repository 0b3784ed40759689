"""JoyAI-LLM-Flash's layers (latent attention with a one-buffer latent cache,
an absorbed form for a decode step and an expanded form for a span, a shared
expert beside the routed ones and a factor on the routed sum) through every
program the serving path reaches, held to the plain reference of
``tests/reference_joyai_flash.py`` on the tiny preset, in float32 on the CPU.

Tolerances, and why each:

``LOGIT_TOL`` 2e-4    logits are of unit order (the weights are drawn so);
                      float32 sums in another order (the absorbed form folds
                      the queries through the key matrix first, the program
                      scores [nope | rope] in one product where the reference
                      adds two) differ by a few 1e-6, through three layers.
                      Each planted fault moves them by 0.01 and more: the
                      shared expert dropped (0.9), the factor left out (0.7),
                      the pairs of the rotary turn taken (i, i + half) (0.5),
                      the latent's norm dropped (1.3), and the latent a token
                      leaves behind rounded to bfloat16 where float32 is
                      stated (0.01).
``LOGPROB_TOL`` 2e-4  the same, on mean log-probabilities of a continuation.
``GAP_TOL`` 2e-4      a greedily decoded token's reference logit may lie this
                      far below the reference's best: an argmax may change on
                      rounding, a wrong cache moves logits by 0.1 and more.
``FORM_TOL`` 2e-5     the absorbed and the expanded form on the same call:
                      the same numbers in another order of float32 sums.

The rule that picks a form (``transformer.latent_form``) gives a call of up
to 256 queries a row the absorbed form, and every sequence here is shorter:
the module runs with the threshold at 1 (fixture
``a_span_takes_the_expanded_form``), so that a span is expanded and a decode
step absorbed, and the tests of the rule itself set its own threshold back.
"""

import dataclasses
import hashlib
import inspect
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_joyai_flash as ref
from consensus_tpu.models import transformer as tf
from consensus_tpu.models.config import (
    ConfigurationUnsupported,
    LayerKindsUnsupported,
    get_model_config,
)
from consensus_tpu.models.generate import (
    generate_tokens,
    generate_tokens_segmented,
    generate_tokens_shared_trunk,
    generate_tokens_shared_trunk_segmented,
)
from consensus_tpu.models.transformer import init_params

LOGIT_TOL = 2e-4
LOGPROB_TOL = 2e-4
GAP_TOL = 2e-4
FORM_TOL = 2e-5

CONFIG = get_model_config("tiny-joyai-flash")
#: The published widths on the tiny preset's depth: what a token leaves behind.
PUBLISHED = dict(d_model=2048, n_heads=32, n_kv_heads=32, head_dim=192,
                 v_head_dim=128, q_lora_rank=1536, kv_lora_rank=512,
                 qk_nope_dim=128, qk_rope_dim=64)

_FAULTS = ("shared", "factor", "interleave", "kv_norm", "latent_dtype", "held")
REF_FORWARD = jax.jit(ref.forward, static_argnums=(0,), static_argnames=_FAULTS)
REF_LOGPROBS = jax.jit(ref.token_logprobs, static_argnums=(0,))


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


def _forward(params, ids, config=CONFIG):
    tokens = jnp.asarray(ids)[None]
    positions = jnp.arange(len(ids))[None]
    logits, _ = tf.forward(params, config, tokens, positions,
                           jnp.ones_like(tokens, bool))
    return np.asarray(logits[0])


#: The rule's own threshold: up to so many queries a row a call is absorbed.
RULE = tf._MLA_ABSORBED_QUERIES


def _set_threshold(queries):
    """The rule's threshold is read when a program is traced: the traces made
    under another are dropped with it."""
    tf._MLA_ABSORBED_QUERIES = queries
    jax.clear_caches()


@pytest.fixture(scope="module", autouse=True)
def a_span_takes_the_expanded_form():
    """The tests' spans are shorter than the rule's 256 queries.  With the
    threshold at 1 they take the expanded form, as the 4,096-wide trunk
    prefill and the embedder do at the published sizes, and a decode step the
    absorbed one: both forms are held to the reference by every test below
    that does not set the threshold itself."""
    _set_threshold(1)
    yield
    _set_threshold(RULE)


@pytest.fixture
def every_call_absorbed():
    _set_threshold(1 << 30)
    yield
    _set_threshold(1)


@pytest.fixture
def every_call_expanded():
    _set_threshold(0)
    yield
    _set_threshold(1)


@pytest.fixture
def the_rules_own_threshold():
    _set_threshold(RULE)
    yield
    _set_threshold(1)


# -- the preset and its weights -----------------------------------------------------


def test_the_preset_is_a_dense_latent_layer_and_two_routed_ones(params):
    c = CONFIG
    assert [(r.kind.name, r.at, r.cache_at, r.count) for r in c.layer_runs] == [
        ("latent_dense", 0, 0, 1), ("latent_moe", 0, 1, 2)]
    assert c.cache_kinds == (("latent", 3, 1),)
    assert c.cache_widths("latent") == (c.kv_lora_rank + c.qk_rope_dim, 0)
    assert c.head_dim == c.qk_nope_dim + c.qk_rope_dim and c.has_latent
    assert c.experts_held == (0, c.n_experts) and c.n_shared_experts == 1
    layers = params["layers"]
    assert set(layers) == {"latent_dense", "latent_moe"}
    moe = layers["latent_moe"]
    assert moe["w_qa"].shape == (2, 64, 48) and moe["w_qb"].shape == (2, 48, 4 * 24)
    assert moe["w_kva"].shape == (2, 64, 32 + 8)
    assert moe["w_kvb"].shape == (2, 32, 4 * (16 + 16))
    assert moe["wo"].shape == (2, 4 * 16, 64)
    assert moe["shared_gate"].shape == (2, 64, 32)
    assert moe["experts_gate"].shape == (2, 8, 64, 32)
    assert not {"wq", "wk", "wv"} & set(moe) and "router" not in layers["latent_dense"]
    assert layers["latent_dense"]["w_gate"].shape == (1, 64, 128)
    logits = _forward(params, _tokens(3, 40))
    assert 0.3 < float(np.std(logits)) < 3.0


def test_the_float32_leaves_stay_float32_in_a_bfloat16_tree():
    tree = jax.eval_shape(
        lambda: init_params(CONFIG, jax.random.PRNGKey(0), jnp.bfloat16))
    kind = tree["layers"]["latent_moe"]
    for leaf in ("router", "router_bias"):
        assert kind[leaf].dtype == jnp.float32, leaf
    for leaf in ("w_kvb", "kv_norm", "shared_down", "experts_up"):
        assert kind[leaf].dtype == jnp.bfloat16, leaf


# -- (a) the full forward ------------------------------------------------------------


@pytest.mark.parametrize("length", [5, 23, 70])
def test_the_full_forward_gives_the_references_logits(params, length):
    ids = _tokens(length, length)
    want = np.asarray(REF_FORWARD(CONFIG, params, jnp.asarray(ids)))
    np.testing.assert_allclose(_forward(params, ids), want, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("fault, least", [
    ({"shared": False}, 0.1), ({"factor": 1.0}, 0.1), ({"interleave": False}, 0.1),
    ({"kv_norm": False}, 0.1), ({"latent_dtype": jnp.bfloat16}, 20 * LOGIT_TOL)])
def test_a_planted_fault_fails_the_same_comparison(params, fault, least):
    """Each part of the mathematics, left out of the reference, moves the
    logits far past the tolerance; so does the cache's type: a latent kept in
    bfloat16 where float32 is stated."""
    ids = _tokens(7, 40)
    faulty = np.asarray(REF_FORWARD(CONFIG, params, jnp.asarray(ids), **fault))
    assert np.abs(_forward(params, ids) - faulty).max() > least


@pytest.mark.parametrize("pad", ["left", "right"])
def test_padded_positions_change_nothing(params, pad):
    ids = _tokens(31, 19)
    width = 32
    tokens = np.zeros((1, width), np.int32)
    valid = np.zeros((1, width), bool)
    at = slice(width - len(ids), width) if pad == "left" else slice(0, len(ids))
    tokens[0, at], valid[0, at] = ids, True
    positions = jnp.maximum(jnp.cumsum(jnp.asarray(valid), axis=1) - 1, 0)
    logits, _ = tf.forward(params, CONFIG, jnp.asarray(tokens), positions,
                           jnp.asarray(valid))
    want = np.asarray(REF_FORWARD(CONFIG, params, jnp.asarray(ids)))
    np.testing.assert_allclose(np.asarray(logits[0, at]), want, atol=LOGIT_TOL, rtol=0)


def test_streamed_scoring_gives_the_references_logprobs(params):
    rows = [_tokens(41, 30), _tokens(42, 12)]
    width = 32
    tokens = np.zeros((2, width), np.int32)
    valid = np.zeros((2, width), bool)
    for i, ids in enumerate(rows):
        tokens[i, :len(ids)], valid[i, :len(ids)] = ids, True
    got = np.asarray(tf.token_logprobs_streamed(
        params, CONFIG, jnp.asarray(tokens), jnp.asarray(valid), vocab_chunk=128))
    for i, ids in enumerate(rows):
        want = np.asarray(REF_LOGPROBS(CONFIG, params, jnp.asarray(ids)))
        np.testing.assert_allclose(got[i, :len(ids)], want, atol=LOGPROB_TOL, rtol=0)


# -- (c) the two forms -----------------------------------------------------------------


def test_the_form_follows_the_calls_queries_in_one_place(the_rules_own_threshold):
    # A decode step and a paged chunk of 128 or 256 queries: absorbed (the
    # chip's reading, PERF.md 5); an embedded text of 1,024 and the 4,096-wide
    # trunk prefill: expanded.
    assert [tf.latent_form(n) for n in (1, 128, 256)] == ["absorbed"] * 3
    assert [tf.latent_form(n) for n in (257, 512, 1024, 4096)] == ["expanded"] * 4
    # No option and no environment variable: a rule of shapes.
    assert "os.environ" not in inspect.getsource(tf.latent_form)
    assert list(inspect.signature(tf.latent_form).parameters) == ["queries"]


def test_a_span_in_the_absorbed_form_gives_the_expanded_forms_logits(
        params, every_call_absorbed):
    ids = _tokens(61, 33)
    assert tf.latent_form(len(ids)) == "absorbed"
    absorbed = _forward(params, ids)
    want = np.asarray(REF_FORWARD(CONFIG, params, jnp.asarray(ids)))
    np.testing.assert_allclose(absorbed, want, atol=LOGIT_TOL, rtol=0)


def _prefill_then_decode(params, ids, split):
    """Logits of ``ids`` from a prefill of ``ids[:split]`` into a dense cache
    and a step a token after it."""
    n = len(ids)
    tokens, positions = jnp.asarray(ids)[None], jnp.arange(n)[None]
    valid = jnp.ones((1, n), bool)
    cache = tf.make_cache(CONFIG, 1, n + 3, jnp.float32)
    out, cache = tf.forward(params, CONFIG, tokens[:, :split], positions[:, :split],
                            valid[:, :split], cache, 0)
    rows = [np.asarray(out[0])]
    for i in range(split, n):
        out, cache = tf.forward(params, CONFIG, tokens[:, i:i + 1],
                                positions[:, i:i + 1], valid[:, i:i + 1], cache, i)
        rows.append(np.asarray(out[0]))
    return np.concatenate(rows), cache


def test_a_decode_step_in_either_form_reads_the_same_cache(
        params, every_call_expanded):
    """(c): the same call, a decode step over the same latent cache, in the
    expanded form (forced here) and in the absorbed form (the rule's)."""
    ids = _tokens(62, 21)
    expanded, _ = _prefill_then_decode(params, ids, 13)
    _set_threshold(1)
    absorbed, _ = _prefill_then_decode(params, ids, 13)
    np.testing.assert_allclose(absorbed, expanded, atol=FORM_TOL, rtol=0)
    assert np.abs(absorbed - expanded).max() > 0  # two programs, not one


# -- (b) prefill, then decode through the latent cache ----------------------------------


def test_prefill_then_decode_through_the_dense_cache_gives_the_references_logits(
        params):
    ids = _tokens(63, 26)
    got, cache = _prefill_then_decode(params, ids, 17)
    want = np.asarray(REF_FORWARD(CONFIG, params, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    # One buffer: the latent and the rotary key, and no values of their own.
    assert cache.v == {"latent": None}
    assert cache.k["latent"].shape == (3, 1, 29, 1, CONFIG.latent_dim)


def test_prefill_then_decode_through_the_pages_gives_the_references_logits(params):
    """(b), paged: a prompt ingested in chunks of 8 into the latent pool (the
    expanded form over gathered pages), then a token at a time through the
    same program (one query a row: the absorbed form over the same pages);
    each step's last hidden state against the reference's full forward."""
    from consensus_tpu.models.stepper import make_page_state, paged_prefill_chunk

    rows = [_tokens(64, 27), _tokens(65, 14)]
    split, page, blocks = 11, 16, 3
    state = make_page_state(CONFIG, 2 * blocks, page, jnp.float32)
    tables = jnp.asarray(np.arange(2 * blocks, dtype=np.int32).reshape(2, blocks))
    sink = 2 * blocks
    want = [np.asarray(REF_FORWARD(CONFIG, params, jnp.asarray(ids))) for ids in rows]

    def run(start, width):
        """Positions [start, start + width) of both rows, one chunk."""
        nonlocal state
        tokens = np.zeros((2, width), np.int32)
        valid = np.zeros((2, width), bool)
        lengths = np.zeros((2,), np.int32)
        for r, ids in enumerate(rows):
            piece = ids[start:start + width]
            tokens[r, :len(piece)], valid[r, :len(piece)] = piece, True
            lengths[r] = start + len(piece)
        at = start + np.arange(width)[None, :]
        pages = np.where(valid, np.asarray(tables)[np.arange(2)[:, None], at // page],
                         sink)
        hidden, state = paged_prefill_chunk(
            params, CONFIG, jnp.asarray(tokens), jnp.asarray(valid), state, tables,
            jnp.asarray(lengths), jnp.asarray(pages),
            jnp.asarray(np.where(valid, at % page, 0)))
        return np.asarray(tf.project_logits(params, CONFIG, hidden)), lengths

    for start in range(0, split, 8):
        logits, lengths = run(start, min(8, split - start))
    for r in range(2):
        np.testing.assert_allclose(logits[r], want[r][split - 1], atol=LOGIT_TOL, rtol=0)
    for at in range(split, 27):
        logits, lengths = run(at, 1)
        for r, ids in enumerate(rows):
            if at < len(ids):
                np.testing.assert_allclose(
                    logits[r], want[r][at], atol=LOGIT_TOL, rtol=0)
    assert state.v_pages == {"latent": None}


def _greedy_gaps(params, prompt, generated):
    stream = np.concatenate([prompt, generated])
    logits = np.asarray(REF_FORWARD(CONFIG, params, jnp.asarray(stream)))
    return [float(logits[len(prompt) - 1 + j].max() - logits[len(prompt) - 1 + j][t])
            for j, t in enumerate(generated)]


PROMPTS = [_tokens(21, 17), _tokens(22, 9), _tokens(23, 26)]


@pytest.mark.parametrize("program", ["monolithic", "segmented"])
def test_classic_generation_decodes_what_the_reference_puts_first(params, program):
    tokens, valid = _left_pad(PROMPTS, 32)
    keys = jnp.zeros((3, 2), jnp.uint32)
    common = dict(temperature=jnp.zeros((3,)), eos_ids=jnp.asarray([-1], jnp.int32))
    if program == "monolithic":
        out = generate_tokens(params, CONFIG, tokens, valid, keys,
                              max_new_tokens=16, **common)
    else:  # the second segment reads the first as a frozen one-buffer block
        out = generate_tokens_segmented(
            params, CONFIG, tokens, valid, keys, max_new_tokens=16, seg_len=8,
            kv_quant=False, **common)
    generated = np.asarray(out.tokens)
    assert generated.shape == (3, 16)
    for prompt, row in zip(PROMPTS, generated):
        assert max(_greedy_gaps(params, prompt, row)) < GAP_TOL
    # 16 steps x 2 routed layers x 3 rows, 2 assignments each, every one held.
    held, rows, passes, reached = (int(n) for n in np.asarray(out.moe_held))
    assert (held, rows, passes) == (16 * 2 * 3 * 2, 16 * 2 * 3, 16 * 2)
    assert passes <= reached <= passes * 6  # three rows' six assignments a pass


@pytest.mark.parametrize("program", ["monolithic", "segmented"])
def test_shared_trunk_generation_is_the_classic_path_row_for_row(params, program):
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
    """(d): ``generate_tokens_shared_trunk``, the trunk's prefill in the
    expanded form and every step in the absorbed one over trunk and tail."""
    tokens1, valid1 = _left_pad([PROMPTS[2]], 32)
    out = generate_tokens_shared_trunk(
        params, CONFIG, tokens1, valid1, 2, jnp.zeros((2, 2), jnp.uint32),
        max_new_tokens=16, temperature=jnp.zeros((2,)),
        eos_ids=jnp.asarray([-1], jnp.int32))
    for row in np.asarray(out.tokens):
        assert max(_greedy_gaps(params, PROMPTS[2], row)) < GAP_TOL


def test_shared_context_scoring_gives_the_references_logprobs(params):
    context, conts = _tokens(51, 37), [_tokens(52, 9), _tokens(53, 14)]
    width = 16
    ctx = jnp.asarray(np.pad(context, (0, 48 - len(context))))[None]
    ctx_valid = (jnp.arange(48) < len(context))[None]
    cont = np.zeros((2, width), np.int32)
    cont_valid = np.zeros((2, width), bool)
    for i, ids in enumerate(conts):
        cont[i, :len(ids)], cont_valid[i, :len(ids)] = ids, True
    got = np.asarray(tf.shared_context_token_logprobs(
        params, CONFIG, ctx, ctx_valid, jnp.asarray(cont), jnp.asarray(cont_valid),
        vocab_chunk=128))
    for i, ids in enumerate(conts):
        want = np.asarray(REF_LOGPROBS(
            CONFIG, params, jnp.asarray(np.concatenate([context, ids]))))
        np.testing.assert_allclose(got[i, :len(ids)], want[len(context):],
                                   atol=LOGPROB_TOL, rtol=0)


# -- (e) the latent cache is one buffer -----------------------------------------------


def test_the_latent_pool_is_one_buffer_and_a_token_is_counted_once():
    from consensus_tpu.models.stepper import make_page_state

    state = make_page_state(CONFIG, 10, 16, jnp.float32)
    assert state.k_pages["latent"].shape == (3, 11, 16, 1, 40)
    assert state.v_pages == {"latent": None}
    assert len(jax.tree.leaves((state.k_pages, state.v_pages))) == 1
    assert CONFIG.kv_bytes_per_token(4) == 4 * 3 * 40
    # The published widths: pages x 16 x 576, 1,152 B a token a layer in
    # bfloat16 where the 32 expanded heads would be 20,480 B.
    full = dataclasses.replace(CONFIG, **PUBLISHED)
    pool = jax.eval_shape(lambda: make_page_state(full, 7, 16, jnp.bfloat16))
    assert pool.k_pages["latent"].shape == (3, 8, 16, 1, 576)
    assert pool.v_pages == {"latent": None}
    assert full.kv_bytes_per_token(2) == 3 * 1152
    assert full.n_heads * (full.head_dim + full.value_dim) * 2 == 20480
    five = dataclasses.replace(full, n_layers=5, hybrid_layer_pattern=(0,) * 5,
                               moe_layer_freq=(0, 1, 1, 1, 1))
    assert five.kv_bytes_per_token(2) == 5760
    cache = jax.eval_shape(lambda: tf.make_cache(full, 2, 64, jnp.bfloat16))
    assert cache.k["latent"].shape == (3, 2, 64, 1, 576) and cache.v["latent"] is None
    tail_k, tail_v = jax.eval_shape(
        lambda: tf.kv_buffers(full, (32, 64), jnp.bfloat16))
    assert tail_k["latent"].shape == (3, 32, 64, 1, 576) and tail_v["latent"] is None


# -- (f) the share and the model -------------------------------------------------------


@pytest.mark.parametrize("rows", [40, 700])  # the few-rows tile, the span's
def test_the_shares_sum_to_the_uncut_layer_with_the_shared_expert_once(rows):
    """The routed parts under ``experts_held`` (0, 4) and (4, 4), each times
    the factor, with the shared expert (which every chip computes alike) and
    the residual counted once, are the uncut reference's whole layer."""
    lp = jax.tree.map(
        lambda a: a[1],
        init_params(CONFIG, jax.random.PRNGKey(11), jnp.float32)["layers"]["latent_moe"])
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, CONFIG.d_model))
    t = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + CONFIG.rms_eps)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(x + ref.experts(CONFIG, lp, t, held=(0, 8)))
        shared = np.asarray(ref.shared_only(CONFIG, lp, t))
    total = np.asarray(x) - shared  # every share adds the shared expert: count it once
    held = 0
    for first in (0, 4):
        config = dataclasses.replace(CONFIG, experts_held=(first, 4))
        share = {**lp, **{leaf: lp[leaf][first:first + 4] for leaf in tf.EXPERT_LEAVES}}
        out, tally = jax.jit(tf.moe_block, static_argnums=0)(config, share, x)
        total = total + np.asarray(out - x)
        held += int(tally[0])
        assert int(tally[1]) == rows and int(tally[2]) == 1
    assert held == rows * CONFIG.experts_per_token  # every assignment, once
    np.testing.assert_allclose(total, want, atol=LOGIT_TOL, rtol=0)
    # And the factor is on the routed sum alone: a layer without it differs.
    plain = dataclasses.replace(CONFIG, routed_scaling_factor=None)
    out, _ = jax.jit(tf.moe_block, static_argnums=0)(plain, lp, x)
    assert np.abs(np.asarray(out) - want).max() > 0.1


@pytest.mark.parametrize("m, k, n, run, tile", [
    # A decode step of 32 rows, 8 of 256 experts a row: one row an expert.
    (256, 2048, 768, 1.0, (16, 2048, 768)),  # JoyAI-LLM-Flash, gate and up
    (256, 768, 2048, 1.0, (16, 768, 2048)),  # its down, the block whole
    (256, 4096, 2048, 1.0, (16, 4096, 512)),  # MiMo-V2-Flash: a quarter
    (256, 2048, 4096, 1.0, (16, 2048, 1024)),
    # A score chunk's block of 4,096 rows and a paged prefill's 2,048.
    (32768, 2048, 768, 128.0, (512, 1024, 768)),
    (32768, 4096, 2048, 128.0, tf._GROUPED_TILE),
    (16384, 2048, 4096, 64.0, tf._GROUPED_TILE),
])
def test_the_tile_follows_the_rows_an_expert_expects(m, k, n, run, tile):
    """At the published widths: a decode step's few rows an expert take the
    few-rows tile with as much of the weight block as fits twice in half the
    kernel's fast memory; a span keeps the tile it had."""
    got = tf.grouped_tiling(m, k, n, run)
    assert got == tile
    if got[0] == tf._FEW_ROWS_TILE:
        assert 2 * got[1] * got[2] * 2 <= tf._WEIGHT_BLOCK_BYTES


@pytest.mark.parametrize("rows", [1, 8, 32])
def test_a_decode_steps_rows_give_the_references_experts(rows):
    """At a decode step's rows the grouped products run at the few-rows tile
    and give the float32 reference's layer, every expert held, the shared
    one and the factor in; the tally counts the experts the rows reached."""
    lp = jax.tree.map(
        lambda a: a[1],
        init_params(CONFIG, jax.random.PRNGKey(11), jnp.float32)["layers"]["latent_moe"])
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, CONFIG.d_model))
    k = CONFIG.experts_per_token
    assert tf.grouped_tiling(rows * k, CONFIG.d_model, CONFIG.expert_hidden,
                             rows * k / CONFIG.n_experts)[0] == tf._FEW_ROWS_TILE
    t = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + CONFIG.rms_eps)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(x + ref.experts(CONFIG, lp, t))
        chosen = np.asarray(ref.routing(CONFIG, lp, t)[0])
    out, tally = jax.jit(tf.moe_block, static_argnums=0)(CONFIG, lp, x)
    np.testing.assert_allclose(np.asarray(out), want, atol=LOGIT_TOL, rtol=0)
    assert list(map(int, tally)) == [rows * k, rows, 1, len(set(chosen.ravel()))]


# -- (d) the backend: paged prefill in chunks, the fused score matrix -----------------


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


#: Contexts of 297, 37 and 9 byte-tokens (with the BOS): the first is
#: prefilled in two 256-token chunks, the last is shorter than a page.
CONTEXTS = ["the river rose. " * 18 + "and then", "short context of some words and more",
            "tiny ctx"]
CANDIDATES = ["we should build the bridge", "a longer statement, with a clause"]


@pytest.fixture(scope="module")
def reference_matrix(backend, params):
    want = np.zeros((len(CANDIDATES), len(CONTEXTS)))
    for a, context in enumerate(CONTEXTS):
        prefix = backend.tokenizer.encode(context, add_bos=True)
        for c, candidate in enumerate(CANDIDATES):
            cont = backend.tokenizer.encode(candidate)
            lp = REF_LOGPROBS(CONFIG, params, jnp.asarray(prefix + cont))
            want[c, a] = float(jnp.mean(lp[len(prefix):]))
    return want


def _counters():
    from consensus_tpu.obs.metrics import get_registry

    families = get_registry().snapshot()["families"]
    out = {"absorbed": 0, "expanded": 0}
    out.update({s["labels"]["form"]: s["value"] for s in families.get(
        "backend_mla_queries_total", {"series": []})["series"]})
    out["keys_expanded"] = sum(s["value"] for s in families.get(
        "backend_mla_keys_expanded_total", {"series": []})["series"])
    out["expert_calls"] = sum(s["value"] for s in families.get(
        "backend_moe_expert_calls_total", {"series": []})["series"])
    out["experts_read"] = sum(s["value"] for s in families.get(
        "backend_moe_experts_read_total", {"series": []})["series"])
    return out


def test_the_fused_score_matrix_gives_the_references_utilities(
        backend, reference_matrix):
    before = backend.matrix_stats["fallbacks"]
    counted = _counters()
    result = backend.score_matrix([_matrix_request(CONTEXTS, CANDIDATES)])[0]
    assert backend.matrix_stats["fallbacks"] == before and result.path == "fused"
    np.testing.assert_allclose(
        result.utilities, reference_matrix, atol=LOGPROB_TOL, rtol=0)
    after = _counters()
    # Two prefill chunks of 8 rows x 256 and one score chunk of 8 rows x 64,
    # 3 latent layers, all in the expanded form; the keys each row gathered.
    assert after["absorbed"] == counted["absorbed"]
    assert after["expanded"] - counted["expanded"] == 3 * (2 * 8 * 256 + 8 * 64)
    assert after["keys_expanded"] > counted["keys_expanded"]
    assert (after["keys_expanded"] - counted["keys_expanded"]) % (3 * 8 * 16) == 0
    # 2 routed layers, 8 held experts, three launches.
    assert after["expert_calls"] - counted["expert_calls"] == 3 * 2 * 8


def test_the_fused_score_matrix_in_the_form_the_rule_gives_its_chunks(
        backend, reference_matrix, the_rules_own_threshold):
    """Chunks of up to 256 queries a row take the absorbed form over the
    gathered pages: the same utilities, and no key expanded."""
    counted = _counters()
    result = backend.score_matrix([_matrix_request(CONTEXTS, CANDIDATES)])[0]
    np.testing.assert_allclose(
        result.utilities, reference_matrix, atol=LOGPROB_TOL, rtol=0)
    after = _counters()
    assert after["expanded"] == counted["expanded"]
    assert after["keys_expanded"] == counted["keys_expanded"]
    assert after["absorbed"] - counted["absorbed"] == 3 * (2 * 8 * 256 + 8 * 64)


def test_a_second_copy_of_the_latent_is_not_what_the_pool_holds(backend):
    """The pool of a score matrix is sized by a token's one buffer."""
    assert backend._kv_page_bytes(16) == 16 * 4 * 3 * 40
    assert backend.kv_quant is False  # no int8 form of a latent pool
    assert backend.kv_cache_identity()[-1] == ("kinds", (("latent", 3, 1, 40, 0),))


def test_the_rotary_turn_dropped_in_the_paged_path_fails_the_same_comparison(
        backend, reference_matrix, monkeypatch):
    """The planted fault in the program: the pairs (i, i + half) turned."""
    monkeypatch.setattr(backend, "config",
                        dataclasses.replace(CONFIG, rope_interleave=False))
    result = backend.score_matrix([_matrix_request(CONTEXTS, CANDIDATES)])[0]
    gap = np.abs(np.asarray(result.utilities) - reference_matrix)
    assert gap.min() > 3 * LOGPROB_TOL and gap.max() > 100 * LOGPROB_TOL


def test_the_backend_generates_and_embeds_and_counts_both_forms(backend):
    from consensus_tpu.backends.base import GenerationRequest
    from consensus_tpu.obs.metrics import get_registry

    counted = _counters()
    requests = [GenerationRequest(user_prompt="what should we do?", max_tokens=8,
                                  temperature=0.7, seed=100 + i) for i in range(8)]
    results = backend.generate(requests)
    assert len(results) == 8 and len({r.text for r in results}) > 1
    after = _counters()
    # Eight rows a step, the 16 steps of the program that a budget of 8 runs
    # in, three latent layers: one query each.
    assert after["absorbed"] - counted["absorbed"] == 8 * 16 * 3
    assert after["expanded"] > counted["expanded"]  # the trunk's prefill
    assert after["expert_calls"] > counted["expert_calls"]
    vectors = backend.embed(["one text", "another, longer text"])
    assert vectors.shape == (2, CONFIG.d_model)
    np.testing.assert_allclose(np.linalg.norm(vectors, axis=1), 1.0, atol=1e-5)
    gauges = {s["labels"]["kind"]: s["value"] for s in get_registry().snapshot()[
        "families"]["backend_kv_bytes_per_token"]["series"]}
    assert gauges["latent"] == gauges["all"] == 4 * 3 * 40


def test_a_generation_launch_counts_the_experts_it_read(backend, monkeypatch):
    """``backend_moe_experts_read_total`` grows by the tally's held experts
    reached, and ``backend_moe_expert_calls_total`` by every held expert of
    every routed pass, as before: read over calls is the share read."""
    from consensus_tpu.backends.base import GenerationRequest

    tallies = []
    record = backend._record_moe
    monkeypatch.setattr(backend, "_record_moe",
                        lambda tally: (tallies.append(np.asarray(tally)), record(tally)))
    counted = _counters()
    backend.generate([GenerationRequest(user_prompt="one row", max_tokens=8,
                                        temperature=0.7, seed=5)])
    after = _counters()
    (tally,) = tallies
    held, rows, passes, reached = (int(n) for n in tally)
    assert after["experts_read"] - counted["experts_read"] == reached
    assert after["expert_calls"] - counted["expert_calls"] == passes * 8
    assert passes <= reached <= passes * 8


# -- budgets -------------------------------------------------------------------------------


def test_a_score_chunks_and_an_embedding_batchs_temporaries_at_the_published_widths(
        backend, monkeypatch):
    full = dataclasses.replace(
        CONFIG, **PUBLISHED, n_layers=5, hybrid_layer_pattern=(0,) * 5,
        moe_layer_freq=(0, 1, 1, 1, 1), ffn_hidden=7168, expert_hidden=768,
        n_experts=256, experts_per_token=8, experts_held=(0, 256),
        vocab_size=129280)
    monkeypatch.setattr(backend, "config", full)
    monkeypatch.setattr(backend, "params", {"embed": jnp.zeros((1,), jnp.bfloat16)})
    # A row of a 256-wide chunk over 4,096 keys: 134 MB of float32 logits and
    # the weights beside them; in the expanded form 84 MB of keys and values
    # to keep beside them, 151 MB while they are made (the module's threshold
    # of 1), in the absorbed form the 4.7 MB of gathered latents alone (the
    # rule's own threshold).
    logits = 256 * 32 * 4096 * 4
    expanded = 4096 * 32 * (192 + 128) * 2
    assert (logits, expanded) == (134217728, 83886080)

    def a_row(keys):
        return (backend._score_chunk_transient_bytes(2, 256, keys)
                - backend._score_chunk_transient_bytes(1, 256, keys))

    assert logits + expanded < a_row(4096) < 2 * (logits + expanded)
    assert backend._score_chunk_transient_bytes(32, 256, 2048) > 5e9
    assert backend._score_chunk_transient_bytes(8, 256, 2048) < 3e9
    # An embedding batch: a head at a time, beside the keys and values made.
    assert backend._dense_attention_bytes(4, 1536, 1536) == 4 * 1536 * (
        1536 * 6 + 2 * 32 * (256 + 320))
    monkeypatch.setattr(backend, "max_batch_rows", 32)
    monkeypatch.setattr(backend, "max_context", 4096)
    assert backend._embed_rows_allowed(["a" * 1500] * 20) == 32
    assert backend._embed_rows_allowed(["a" * 4000] * 20) == 8
    monkeypatch.setattr(tf, "_MLA_ABSORBED_QUERIES", RULE)
    assert 1.5 * logits < a_row(4096) < 2 * logits
    # What the weights leave (5 GB of 16): 32 rows at 2,048 keys, 16 at 4,096.
    assert backend._score_chunk_transient_bytes(32, 256, 2048) < 4.6e9
    assert backend._score_chunk_transient_bytes(32, 256, 4096) > 5e9
    # An embedded text of 256: one key-value head, 32 query heads at once.
    assert backend._dense_attention_bytes(4, 256, 256) == 4 * 256 * 32 * 256 * 6


# -- (g) what refuses, by name ------------------------------------------------------------


def test_token_search_the_stream_path_a_mesh_and_int8_refuse_by_name(backend, params):
    from consensus_tpu.backends.base import GenerationRequest
    from consensus_tpu.backends.engine import DecodeEngine
    from consensus_tpu.backends.tpu import TPUBackend

    with pytest.raises(LayerKindsUnsupported, match="token-search session"):
        backend.open_fused_token_search(None)
    with pytest.raises(LayerKindsUnsupported, match="generate_stream"):
        backend.generate_stream([GenerationRequest(user_prompt="p", max_tokens=4)])
    with pytest.raises(LayerKindsUnsupported, match="decode_steps"):
        DecodeEngine(backend, slots=2, num_pages=64, auto_start=False,
                     decode_steps=4)
    with pytest.raises(LayerKindsUnsupported, match="tp > 1"):
        TPUBackend(config=CONFIG, params=params, dtype="float32", tp=2)
    with pytest.raises(LayerKindsUnsupported, match="int8 weights"):
        TPUBackend(config=CONFIG, dtype="float32", quantization="int8")
    assert TPUBackend(config=CONFIG, params=params, dtype="float32",
                      kv_quant=True).kv_quant is False
    assert issubclass(LayerKindsUnsupported, ConfigurationUnsupported)


def test_the_prefix_cache_across_requests_declines_and_counts(backend):
    """An engine over a latent pool keeps no run of pages across requests: an
    insert and a lookup are declined, and counted where the backend's other
    counters are."""
    from consensus_tpu.backends.engine import DecodeEngine
    from consensus_tpu.obs.metrics import get_registry

    def declined():
        series = get_registry().snapshot()["families"].get(
            "backend_prefix_runs_declined_total", {"series": []})["series"]
        return sum(s["value"] for s in series)

    before = declined()
    engine = DecodeEngine(backend, slots=2, num_pages=64, auto_start=False,
                          prefix_cache=True)
    try:
        assert engine.layer_kinds
        cache = engine.prefix_cache
        pages = engine.pool.alloc(2)
        assert cache.insert(list(range(32)), pages) is False
        assert cache.lookup(list(range(32))) == ([], 0)
        assert cache.stats()["declined_runs"] == 2 and cache.stats()["entries"] == 0
    finally:
        engine.close()
    assert declined() - before == 2


@pytest.mark.parametrize("program", [
    "search_prefill", "search_step", "suffix_propose", "rollout_scored",
    "rollout_scored_many", "rollout_verify_many", "paged_decode_step",
    "paged_decode_steps", "paged_verify_steps", "paged_gather_step"])
def test_a_program_of_one_cache_says_so_when_traced(program):
    from consensus_tpu.models import stepper

    fn = getattr(stepper, program)
    args = {name: CONFIG if name == "config" else None
            for name, p in inspect.signature(fn).parameters.items()
            if p.default is inspect.Parameter.empty or name == "config"}
    with pytest.raises(LayerKindsUnsupported, match=program):
        fn(**args)


def test_the_pallas_kernels_the_int8_tail_and_the_loader_refuse_by_name(params):
    tokens, valid = _left_pad([PROMPTS[0]], 32)
    flash = dataclasses.replace(CONFIG, use_flash_attention=True)
    with pytest.raises(LayerKindsUnsupported, match="flash_attention"):
        tf.forward(params, flash, tokens, jnp.maximum(jnp.cumsum(valid, 1) - 1, 0), valid)
    kernel = dataclasses.replace(CONFIG, use_decode_attention=True)
    keys = jnp.zeros((1, 2), jnp.uint32)
    with pytest.raises(LayerKindsUnsupported, match="decode_attention"):
        generate_tokens(params, kernel, tokens, valid, keys, max_new_tokens=4)
    with pytest.raises(LayerKindsUnsupported, match="int8"):
        generate_tokens_segmented(params, CONFIG, tokens, valid, keys,
                                  max_new_tokens=16, seg_len=8, kv_quant=True)
    from consensus_tpu.models import loader

    with pytest.raises(LayerKindsUnsupported):
        loader.load_params("/nowhere", CONFIG)


def test_the_partition_rules_name_every_leaf(params):
    from consensus_tpu.parallel.mesh import match_partition_rules

    specs = match_partition_rules(params)
    assert set(specs["layers"]) == {"latent_dense", "latent_moe"}
    for kind in ("latent_dense", "latent_moe"):
        assert set(specs["layers"][kind]) == set(params["layers"][kind])


@pytest.mark.parametrize("change, says", [
    ({"q_lora_rank": 0}, "all of them"),
    ({"qk_rope_dim": 0}, "all of them"),
    ({"v_head_dim": None}, "all of them"),
    ({"head_dim": 32}, "qk_nope_dim"),
    ({"hybrid_layer_pattern": (0, 1, 0), "sliding_window": 8}, "pattern of zeros"),
    ({"rotary_dim": 8}, "no rotary_dim"),
    ({"moe_layer_freq": (0, 0, 0)}, "need routed layers"),
])
def test_a_latent_configuration_is_held_to_its_keys(change, says):
    with pytest.raises(ValueError, match=says):
        dataclasses.replace(CONFIG, **change)


def test_the_latent_keys_alone_do_not_make_a_configuration():
    dense = get_model_config("tiny-llama3")
    with pytest.raises(ValueError, match="all of them"):
        dataclasses.replace(dense, rope_interleave=True)
    with pytest.raises(ValueError, match="need routed layers"):
        dataclasses.replace(get_model_config("tiny-mimo-v2"),
                            moe_layer_freq=(0,) * 7, n_shared_experts=1)


def test_the_programs_name_the_new_scopes():
    from test_falcon_h1 import _lower

    text = _lower("generate_tokens_shared_trunk", CONFIG).as_text(debug_info=True)
    for scope in ("attention_latent", "mla_absorb", "mla_expand", "moe_shared",
                  "moe_router", "moe_experts", "moe_combine", "kv_write"):
        assert re.search(rf'[/"]{scope}[/"]', text), scope
    assert not re.search(r'[/"]attention[/"]', text)
    text = _lower("paged_score_chunk", CONFIG).as_text(debug_info=True)
    for scope in ("attention_latent", "mla_expand", "moe_shared"):
        assert re.search(rf'[/"]{scope}[/"]', text), scope
    assert not re.search(r'[/"]mla_absorb[/"]', text)  # a span: expanded alone
    _set_threshold(RULE)  # the rule's own: a chunk of 32 queries is absorbed
    text = _lower("paged_score_chunk", CONFIG).as_text(debug_info=True)
    _set_threshold(1)
    assert re.search(r'[/"]mla_absorb[/"]', text)
    assert not re.search(r'[/"]mla_expand[/"]', text)


# -- (h) the accepted configurations: nothing moved ---------------------------------------

#: sha256 of ``lowered.as_text()`` of the two programs at the sizes of
#: ``tests/test_falcon_h1.py`` ``_lower``, and of ``init_params``' leaves under
#: PRNGKey(7), at PR 34's commit, the parent of the PR that brought latent
#: attention: the hybrid block's and the layer kinds' programs lower to the
#: text they had, and their weights are the numbers they were.  (The dense
#: presets' pins are ``tests/test_falcon_h1.py``'s.)  The two
#: ``tiny-mimo-v2/jit-init`` pins are of ``init_params`` under one ``jit``, as
#: ``benchmark/lib/harness.make_params`` wraps it: the experts are drawn one
#: at a time into their place since that PR, and under the ``jit`` that serves
#: them they are the parent's bits.  The two ``tiny-mimo-v2`` program pins
#: are PR 37's: a decode step's routed product went from the masked form to
#: the grouped one at a few-rows tile, and the routed layers' tally gained
#: its fourth entry (``reached``).  The pinned chunk is 8 x 32 = 256 rows,
#: which the masked form took too; a chunk past 256 rows (8 x 64) lowers to
#: its parent's text but for the tally's sum of the groups reached.  The two
#: ``tiny-llama3`` pins are of PR 35's commit, this PR's parent (the same text as
#: ``tests/test_falcon_h1.py``'s dense pins of PR 31): a configuration with
#: no routed layer, as SmolLM2's cell is, lowers to what it did before.
PARENT = {
    "tiny-falcon-h1/generate_tokens_shared_trunk":
        "963efd219ff76c21cbb9865dc22b2ccd68a12a3b1a8c376ea2fb1dbc95ff1232",
    "tiny-falcon-h1/paged_score_chunk":
        "309dd0e88e8f6def7723d0e7e574b9f29abc362fcf40b0265fc0b4bd4dae3113",
    "tiny-falcon-h1/init/float32":
        "e07e8c16ff6fbefb79b3d57c6cc4878bb6e7ea757dbe21022541d933dcaeea03",
    "tiny-falcon-h1/init/bfloat16":
        "c81aab2e937d8bda6cb377cb4238a9b40f7742f4ed596d40a3cc818daec9b7eb",
    "tiny-mimo-v2/generate_tokens_shared_trunk":
        "a8bdb3c984526d721d45a1f808e9ad918b44f87269db388ae61451bf0ee33ac5",
    "tiny-mimo-v2/paged_score_chunk":
        "8e42e6d2d568230ac8c935ea9552e076258cb4623e7c8fe8618d5b64537f8d19",
    "tiny-mimo-v2/jit-init/float32":
        "6124e444913f6b1bddc67a413397dead809adb6f97318392818cb92efcf3de2a",
    "tiny-mimo-v2/jit-init/bfloat16":
        "e7326b1bff1a4b96827d93be130a75bff9130949ce4a8408919f9ed9a46847a8",
    "tiny-llama3/generate_tokens_shared_trunk":
        "2b529415ac0ad18d2a77e82dcebfdfa0c49b5e5b7dc7ce2ec6d4c44ad85b89c5",
    "tiny-llama3/paged_score_chunk":
        "8c0d22e16f9d9e2d057cb8a3ba95a2a3fc068cb547772ad527505174d94d6947",
}


def _lower_accepted(program, config):
    from test_falcon_h1 import _lower

    if program != "paged_score_chunk" or not config.has_ssm:
        return _lower(program, config)
    from consensus_tpu.models.stepper import make_page_state, paged_score_chunk

    tree = jax.eval_shape(
        lambda: init_params(config, jax.random.PRNGKey(0), jnp.float32))
    rows, width = 8, 32
    tokens = jnp.zeros((rows, width), jnp.int32)
    valid = jnp.ones((rows, width), bool)
    state = jax.eval_shape(lambda: make_page_state(config, 32, 16, ssm_rows=rows))
    return paged_score_chunk.lower(
        tree, config, tokens, tokens, valid, valid, state,
        jnp.zeros((rows, 4), jnp.int32), jnp.full((rows,), width, jnp.int32),
        tokens, tokens, ssm_rows=jnp.zeros((rows,), jnp.int32))


def _tree_digest(tree):
    digest = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        digest.update(jax.tree_util.keystr(path).encode())
        digest.update(np.asarray(leaf.astype(jnp.float32)).tobytes())
    return digest.hexdigest()


@pytest.mark.parametrize("model", ["tiny-falcon-h1", "tiny-mimo-v2", "tiny-llama3"])
@pytest.mark.parametrize("program", ["generate_tokens_shared_trunk",
                                     "paged_score_chunk"])
def test_an_accepted_configurations_lowered_text_is_the_parents(model, program):
    text = _lower_accepted(program, get_model_config(model)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT[f"{model}/{program}"]
    assert "latent" not in text and "mla_" not in text


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_hybrid_blocks_weights_are_the_parents_bit_for_bit(dtype):
    tree = init_params(get_model_config("tiny-falcon-h1"), jax.random.PRNGKey(7),
                       jnp.dtype(dtype))
    assert _tree_digest(tree) == PARENT[f"tiny-falcon-h1/init/{dtype}"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_bounded_expert_draws_are_the_parents_bit_for_bit(dtype):
    """``by_expert`` draws an expert at a time into its place; under the one
    ``jit`` that the benchmark wraps ``init_params`` in, every leaf of the
    layer kinds' tree (the experts among them) is what the parent drew."""
    tree = jax.jit(init_params, static_argnums=(0, 2))(
        get_model_config("tiny-mimo-v2"), jax.random.PRNGKey(7), jnp.dtype(dtype))
    assert _tree_digest(tree) == PARENT[f"tiny-mimo-v2/jit-init/{dtype}"]


def test_an_experts_draw_is_one_experts_float32_at_a_time():
    """What lives at once while a leaf of experts is drawn is one expert's
    float32 numbers, whatever is held: the lowered program has no float32
    array of every held expert's."""
    many = dataclasses.replace(CONFIG, n_experts=64, experts_held=(0, 64),
                               experts_per_token=2)
    text = jax.jit(init_params, static_argnums=(0, 2)).lower(
        many, jax.random.PRNGKey(0), jnp.bfloat16).as_text()
    assert "tensor<2x64x64x32xbf16>" in text  # the leaf, in its place
    assert not re.search(r"tensor<(64x2|2x64)x64x32xf32>", text)


# -- through the service: POST /v1/consensus -------------------------------------------


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


@pytest.mark.parametrize("method", ["best_of_n", "zero_shot", "habermas_machine"])
def test_a_method_answers_through_the_engine(server, method):
    status, body = _post(server, {
        "method": method, "issue": "How should the city change transport?",
        "agent_opinions": _OPINIONS, "seed": 5,
        "params": {"n": 4, "max_tokens": 12} if method == "best_of_n"
        else {"max_tokens": 12}})
    assert status == 200, body
    assert body["statement"].strip() and not body.get("degraded")
    assert set(body["utilities"]) == set(_OPINIONS) and body["welfare"]
    engine = server.scheduler.batching.engine
    assert engine.layer_kinds and not engine.recurrent


@pytest.mark.parametrize("method", ["beam_search", "mcts"])
def test_a_token_search_method_answers_a_client_error_that_names_it(server, method):
    status, body = _post(server, {
        "method": method, "issue": "How should the city change transport?",
        "agent_opinions": _OPINIONS, "seed": 5, "params": {"max_tokens": 6}})
    assert status == 400, body
    error = body["error"]
    assert error["type"] == "method_unsupported_for_model"
    assert error["method"] == method and method in error["message"]
    assert "token-search" in error["message"] and error["request_id"]
