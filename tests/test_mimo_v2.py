"""MiMo-V2-Flash's layers (full and window attention with their own key-value
heads and pools, sinks, key heads wider than value heads, routed experts of
which the program holds a share) through every program the serving path
reaches, held to the plain reference of ``tests/reference_mimo_v2.py`` on the
tiny preset, in float32 on the CPU.

Tolerances, and why each:

``LOGIT_TOL`` 2e-4    logits are of unit order (the weights are drawn so);
                      float32 sums over at most 192 terms in another order
                      differ by a few 1e-6, through seven layers.  Each of
                      the planted faults moves them by 0.05 and more: the
                      sink dropped (1.5), the selection bias used in the
                      weights (0.3), the router's product in bfloat16 (0.1:
                      a top-8 boundary flips where two scores are near).
``LOGPROB_TOL`` 2e-4  the same, on mean log-probabilities of a continuation.
``GAP_TOL`` 2e-4      a greedily decoded token's reference logit may lie this
                      far below the reference's best: an argmax may change on
                      rounding, a wrong cache moves logits by 0.1 and more.
"""

import dataclasses
import inspect
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_mimo_v2 as ref
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

CONFIG = get_model_config("tiny-mimo-v2")

REF_FORWARD = jax.jit(ref.forward, static_argnums=(0,),
                      static_argnames=("sink", "bias_in_weights", "router_dtype"))
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


# -- the preset and its weights -----------------------------------------------------


def test_the_preset_has_three_kinds_in_seven_layers_and_draws_unit_logits(params):
    c = CONFIG
    assert [(r.kind.name, r.at, r.cache_at, r.count) for r in c.layer_runs] == [
        ("full_dense", 0, 0, 1), ("window_moe", 0, 0, 4),
        ("full_moe", 0, 1, 1), ("window_moe", 4, 4, 1)]
    assert c.cache_kinds == (("full", 2, 2), ("window", 5, 4))
    assert c.head_dim != c.value_dim and c.rotary_dim < c.head_dim
    assert c.sliding_window < 17  # shorter than the tests' sequences
    assert c.experts_held == (8, 8) and c.n_experts == 32
    layers = params["layers"]
    assert set(layers) == {"full_dense", "window_moe", "full_moe"}
    assert layers["window_moe"]["wk"].shape == (5, 64, 4 * 24)
    assert layers["window_moe"]["wv"].shape == (5, 64, 4 * 16)
    assert layers["full_moe"]["wk"].shape == (1, 64, 2 * 24)
    assert layers["window_moe"]["experts_gate"].shape == (5, 8, 64, 32)
    assert "attn_sink" not in layers["full_moe"] and "router" not in layers["full_dense"]
    # Sinks and bias off zero, and the logits of unit order.
    assert float(jnp.abs(layers["window_moe"]["attn_sink"]).mean()) > 0.3
    assert float(jnp.abs(layers["window_moe"]["router_bias"]).mean()) > 0.03
    logits = _forward(params, _tokens(3, 40))
    assert 0.3 < float(np.std(logits)) < 3.0


def test_the_float32_leaves_stay_float32_in_a_bfloat16_tree():
    tree = jax.eval_shape(
        lambda: init_params(CONFIG, jax.random.PRNGKey(0), jnp.bfloat16))
    kind = tree["layers"]["window_moe"]
    for leaf in ("attn_sink", "router", "router_bias"):
        assert kind[leaf].dtype == jnp.float32, leaf
    assert kind["experts_gate"].dtype == jnp.bfloat16 and kind["wq"].dtype == jnp.bfloat16


def test_an_experts_weights_do_not_depend_on_which_share_holds_it():
    """Expert e is the same numbers whether this program holds experts 8-15
    or all 32: the share can be tied to the model."""
    whole = init_params(dataclasses.replace(CONFIG, experts_held=(0, 32)),
                        jax.random.PRNGKey(11), jnp.float32)
    share = init_params(CONFIG, jax.random.PRNGKey(11), jnp.float32)
    for kind in ("window_moe", "full_moe"):
        for leaf in ("experts_gate", "experts_up", "experts_down"):
            np.testing.assert_array_equal(
                np.asarray(whole["layers"][kind][leaf][:, 8:16]),
                np.asarray(share["layers"][kind][leaf]))
        np.testing.assert_array_equal(np.asarray(whole["layers"][kind]["router"]),
                                      np.asarray(share["layers"][kind]["router"]))


# -- the full forward ----------------------------------------------------------------


@pytest.mark.parametrize("length", [5, 23, 70])
def test_the_full_forward_gives_the_references_logits(params, length):
    ids = _tokens(length, length)
    want = np.asarray(REF_FORWARD(CONFIG, params, jnp.asarray(ids)))
    np.testing.assert_allclose(_forward(params, ids), want, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("fault", [
    {"sink": False}, {"bias_in_weights": True}, {"router_dtype": jnp.bfloat16}])
def test_a_planted_fault_fails_the_same_comparison(params, fault):
    """The sink dropped, the selection bias used in the weights, a bfloat16
    router: each moves the reference's own logits far past the tolerance the
    program is held to, so the comparison above would fail on it."""
    worst = 0.0
    for seed in (23, 70):
        ids = _tokens(seed, seed)
        got = _forward(params, ids)
        faulty = np.asarray(REF_FORWARD(CONFIG, params, jnp.asarray(ids), **fault))
        worst = max(worst, float(np.abs(got - faulty).max()))
        with pytest.raises(AssertionError):
            np.testing.assert_allclose(got, faulty, atol=LOGIT_TOL, rtol=0)
    assert worst > 100 * LOGIT_TOL


def test_the_bias_changes_the_selection_and_never_the_weights(params):
    lp = jax.tree.map(lambda a: a[0], params["layers"]["window_moe"])
    t = jax.random.normal(jax.random.PRNGKey(4), (400, CONFIG.d_model))
    chosen, weights = tf.route(CONFIG, lp, t)
    unbiased, plain = tf.route(
        CONFIG, {**lp, "router_bias": jnp.zeros_like(lp["router_bias"])}, t)
    changed = np.mean(np.sort(np.asarray(chosen), 1) != np.sort(np.asarray(unbiased), 1))
    assert 0.02 < changed < 0.6  # it decides near ties, not everywhere
    np.testing.assert_allclose(np.asarray(weights).sum(1), 1.0, atol=1e-5)
    same = np.all(np.asarray(chosen) == np.asarray(unbiased), axis=1)
    np.testing.assert_allclose(np.asarray(weights)[same], np.asarray(plain)[same],
                               atol=1e-6)


@pytest.mark.parametrize("pad", ["left", "right"])
def test_padded_positions_change_nothing(params, pad):
    ids = _tokens(31, 21)
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
    tokens = np.zeros((2, 32), np.int32)
    valid = np.zeros((2, 32), bool)
    for i, ids in enumerate(rows):
        tokens[i, :len(ids)], valid[i, :len(ids)] = ids, True
    got = np.asarray(tf.token_logprobs_streamed(
        params, CONFIG, jnp.asarray(tokens), jnp.asarray(valid), vocab_chunk=128))
    for i, ids in enumerate(rows):
        want = np.asarray(REF_LOGPROBS(CONFIG, params, jnp.asarray(ids)))
        np.testing.assert_allclose(got[i, :len(ids)], want, atol=LOGPROB_TOL, rtol=0)


# -- the share and the model ---------------------------------------------------------


def _skewed(lp, favourite=3, by=4.0):
    """A router that sends most rows to one expert."""
    bias = lp["router_bias"].at[favourite].add(by)
    return {**lp, "router_bias": bias}


@pytest.mark.parametrize("rows", [40, 700])  # the few-rows tile, the span's
def test_the_shares_of_all_ranks_sum_to_the_uncut_layer(rows):
    """The routed layer's result summed over the shares of all four ranks (8
    experts each of 32) is the uncut reference's for the whole layer, no row
    dropped under a router skewed so that one expert takes most of the rows."""
    whole_config = dataclasses.replace(CONFIG, experts_held=(0, 32))
    whole = init_params(whole_config, jax.random.PRNGKey(11), jnp.float32)
    lp_whole = _skewed(jax.tree.map(lambda a: a[2], whole["layers"]["window_moe"]))
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, CONFIG.d_model))
    t = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + CONFIG.rms_eps)
    chosen, _ = ref.routing(whole_config, lp_whole, t)
    assert np.mean(np.any(np.asarray(chosen) == 3, axis=1)) > 0.9  # the skew
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.experts(whole_config, lp_whole, t, held=(0, 32)))
    total = np.zeros_like(want)
    held = 0
    for rank in range(4):
        config = dataclasses.replace(CONFIG, experts_held=(8 * rank, 8))
        lp = {**lp_whole, **{leaf: lp_whole[leaf][8 * rank:8 * rank + 8]
                             for leaf in ("experts_gate", "experts_up", "experts_down")}}
        out, tally = jax.jit(tf.moe_block, static_argnums=0)(config, lp, x)
        total += np.asarray(out - x)
        held += int(tally[0])
        assert int(tally[1]) == rows and int(tally[2]) == 1
    assert held == rows * CONFIG.experts_per_token  # every assignment, once
    np.testing.assert_allclose(total, want, atol=LOGIT_TOL, rtol=0)


def _tiled(monkeypatch, run):
    """``grouped_tiling`` as if every group expected ``run`` rows: 0 gives
    the few-rows tile of a decode step, infinity the span's."""
    rule = tf.grouped_tiling
    monkeypatch.setattr(tf, "grouped_tiling",
                        lambda m, k, n, _, *rest: rule(m, k, n, run, *rest))


@pytest.mark.parametrize("form", ["span_tile", "blocked"])
def test_the_span_forms_and_the_decode_tiling_agree(params, monkeypatch, form):
    """The held experts' result of the grouped products at a decode step's
    tile, and at the span's tile or a block of rows at a time."""
    lp = _skewed(jax.tree.map(lambda a: a[1], params["layers"]["window_moe"]), 11)
    x = jax.random.normal(jax.random.PRNGKey(9), (200, CONFIG.d_model))
    _tiled(monkeypatch, 0.0)
    decode, tally = tf.moe_block(CONFIG, lp, x)
    assert int(tally[0]) > 200  # most rows reach the favourite, held here
    _tiled(monkeypatch, float("inf"))
    if form == "blocked":
        monkeypatch.setattr(tf, "_MOE_BLOCK_ROWS", 64)  # four blocks, the last padded
    other, tally_other = tf.moe_block(CONFIG, lp, x)
    np.testing.assert_allclose(np.asarray(other), np.asarray(decode), atol=2e-5)
    assert list(map(int, tally)) == list(map(int, tally_other))


def _held_reference(lp, x, held=CONFIG.experts_held):
    t = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + CONFIG.rms_eps)
    with jax.default_matmul_precision("highest"):
        return np.asarray(x + ref.experts(CONFIG, lp, t, held=held)), t


@pytest.mark.parametrize("rows", [1, 8, 32])
def test_a_decode_steps_rows_give_the_references_experts(params, rows):
    """At a decode step's rows the grouped products run at the few-rows tile
    and give the float32 reference's held part, some experts held."""
    lp = jax.tree.map(lambda a: a[2], params["layers"]["window_moe"])
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, CONFIG.d_model))
    assert tf.grouped_tiling(rows * 8, CONFIG.d_model, CONFIG.expert_hidden,
                             rows * 8 / CONFIG.n_experts)[0] == tf._FEW_ROWS_TILE
    want, t = _held_reference(lp, x)
    out, tally = jax.jit(tf.moe_block, static_argnums=0)(CONFIG, lp, x)
    np.testing.assert_allclose(np.asarray(out), want, atol=LOGIT_TOL, rtol=0)
    chosen, _ = ref.routing(CONFIG, lp, t)
    first, count = CONFIG.experts_held
    reached = {int(e) for e in np.asarray(chosen).ravel() if first <= e < first + count}
    assert list(map(int, tally)) == [
        int(np.sum((np.asarray(chosen) >= first) & (np.asarray(chosen) < first + count))),
        rows, 1, len(reached)]


@pytest.mark.parametrize("rows", [8, 32])
def test_an_expert_no_row_reached_is_never_read(params, rows):
    """A held expert that no row was sent to has NaN for its three matrices:
    the result is finite and the one its true matrices give (a product over
    every held expert under a mask would carry NaN x 0 = NaN into every
    row), and the tally counts the held experts the rows did reach."""
    first, count = CONFIG.experts_held
    unreached = first + 5
    lp = jax.tree.map(lambda a: a[2], params["layers"]["window_moe"])
    lp = _skewed(lp, unreached, -100.0)  # no row's top 8 takes it
    x = jax.random.normal(jax.random.PRNGKey(40 + rows), (rows, CONFIG.d_model))
    poisoned = {**lp, **{leaf: lp[leaf].at[5].set(jnp.nan) for leaf in tf.EXPERT_LEAVES}}
    block = jax.jit(tf.moe_block, static_argnums=0)
    want, tally_want = block(CONFIG, lp, x)
    got, tally = block(CONFIG, poisoned, x)
    assert np.all(np.isfinite(np.asarray(got)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    _, t = _held_reference(lp, x)
    chosen = np.asarray(ref.routing(CONFIG, lp, t)[0])
    assert unreached not in chosen
    reached = {int(e) for e in chosen.ravel() if first <= e < first + count}
    assert 0 < len(reached) < count
    assert int(tally[3]) == int(tally_want[3]) == len(reached)


# -- prefill, then decode through the caches by kind --------------------------------


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
    else:
        out = generate_tokens_segmented(
            params, CONFIG, tokens, valid, keys, max_new_tokens=16, seg_len=8,
            kv_quant=False, **common)
    generated = np.asarray(out.tokens)
    assert generated.shape == (3, 16)
    for prompt, row in zip(PROMPTS, generated):
        assert max(_greedy_gaps(params, prompt, row)) < GAP_TOL
    # 16 steps x 6 routed layers x 3 rows, 8 assignments each, a quarter held.
    held, rows, passes, reached = (int(n) for n in np.asarray(out.moe_held))
    assert (rows, passes) == (16 * 6 * 3, 16 * 6)
    assert 0.1 < held / (rows * 8) < 0.45
    # Three rows' 24 assignments reach some of the 8 held experts a pass.
    assert 0 < reached < passes * 8


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
    tokens1, valid1 = _left_pad([PROMPTS[2]], 32)
    out = generate_tokens_shared_trunk(
        params, CONFIG, tokens1, valid1, 2, jnp.zeros((2, 2), jnp.uint32),
        max_new_tokens=16, temperature=jnp.zeros((2,)),
        eos_ids=jnp.asarray([-1], jnp.int32))
    for row in np.asarray(out.tokens):
        assert max(_greedy_gaps(params, PROMPTS[2], row)) < GAP_TOL


def test_the_caches_are_by_kind_at_each_kinds_heads_and_widths():
    cache = tf.make_cache(CONFIG, 3, 40, jnp.float32)
    assert {k: v.shape for k, v in cache.k.items()} == {
        "full": (2, 3, 40, 2, 24), "window": (5, 3, 40, 4, 24)}
    assert {k: v.shape for k, v in cache.v.items()} == {
        "full": (2, 3, 40, 2, 16), "window": (5, 3, 40, 4, 16)}
    from consensus_tpu.models.stepper import make_page_state

    state = make_page_state(CONFIG, 10, 16, jnp.float32)
    assert state.k_pages["window"].shape == (5, 11, 16, 4, 24)
    assert state.v_pages["full"].shape == (2, 11, 16, 2, 16)
    assert state.moe_held.shape == (len(tf.MOE_TALLY),) == (4,)
    # 2 x 2 x (24 + 16) + 5 x 4 x (24 + 16) values a token.
    assert CONFIG.kv_bytes_per_token(4) == 4 * (2 * 2 * 40 + 5 * 4 * 40)
    dense = get_model_config("tiny-llama3")
    assert dense.kv_bytes_per_token(2) == 2 * 2 * dense.n_layers * 2 * 16
    assert make_page_state(dense, 10, 16).moe_held is None


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


# -- the backend: paged prefill in chunks, the fused score matrix ---------------------


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
#: prefilled in two 256-token chunks, every one is longer than the window of
#: 8, the last shorter than a page.
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


def _moe_counters():
    from consensus_tpu.obs.metrics import get_registry

    families = get_registry().snapshot()["families"]
    out = {"held": 0, "absent": 0}  # a series is there from its first count
    out.update({s["labels"]["held"]: s["value"] for s in families.get(
        "backend_moe_assignments_total", {"series": []})["series"]})
    calls = families.get("backend_moe_expert_calls_total", {"series": []})["series"]
    out["calls"] = sum(s["value"] for s in calls)
    return out


def test_the_fused_score_matrix_gives_the_references_utilities(
        backend, reference_matrix):
    before = backend.matrix_stats["fallbacks"]
    counted = _moe_counters()
    result = backend.score_matrix([_matrix_request(CONTEXTS, CANDIDATES)])[0]
    assert backend.matrix_stats["fallbacks"] == before and result.path == "fused"
    np.testing.assert_allclose(
        result.utilities, reference_matrix, atol=LOGPROB_TOL, rtol=0)
    after = _moe_counters()
    assert after["held"] > counted.get("held", 0)
    assert after["absent"] > counted.get("absent", 0)
    # Two prefill chunks and one score chunk, 6 routed layers, 8 held experts.
    assert after["calls"] - counted["calls"] == 3 * 6 * 8


def test_a_score_matrixs_pool_and_tables_grow_in_steps(backend, reference_matrix):
    """With layers of more than one kind a paged program is a loop a run of
    layers and slow to compile: the matrix's pool and tables are sized in
    steps, so a candidate a page longer (4 private pages a row where the
    others take 3, 22 blocks where they take 21) meets the programs the
    shorter ones compiled, and the cells both matrices share read the same.
    A configuration of one kind keeps its tables to the block."""
    import types

    from consensus_tpu.backends import tpu
    from consensus_tpu.models.stepper import paged_prefill_chunk, paged_score_chunk

    step = tpu._KINDS_TABLE_STEP_BLOCKS
    assert [backend._table_blocks(n) for n in (1, step, step + 1)] == [
        step, step, 2 * step]
    dense = types.SimpleNamespace(config=get_model_config("tiny-llama3"))
    assert tpu.TPUBackend._table_blocks(dense, step + 1) == step + 1
    backend.score_matrix([_matrix_request(CONTEXTS, CANDIDATES)])
    compiled = (paged_score_chunk._cache_size(), paged_prefill_chunk._cache_size())
    longer = [CANDIDATES[0], "a statement that runs on for a page more than the two"]
    result = backend.score_matrix([_matrix_request(CONTEXTS, longer)])[0]
    assert compiled == (
        paged_score_chunk._cache_size(), paged_prefill_chunk._cache_size())
    np.testing.assert_allclose(
        result.utilities[0], reference_matrix[0], atol=LOGPROB_TOL, rtol=0)


def test_a_sink_dropped_in_the_paged_path_fails_the_same_comparison(
        backend, reference_matrix, monkeypatch):
    """The planted fault in the program: ``paged_attention`` without the
    window layers' sinks."""
    from consensus_tpu.models import stepper

    real = stepper.paged_attention
    monkeypatch.setattr(
        stepper, "paged_attention",
        lambda *args, sink=None, **kwargs: real(*args, **kwargs))
    jax.clear_caches()
    try:
        result = backend.score_matrix([_matrix_request(CONTEXTS, CANDIDATES)])[0]
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    gap = np.abs(np.asarray(result.utilities) - reference_matrix)
    # Every cell is past the tolerance, the worst a hundred times.
    assert gap.min() > 3 * LOGPROB_TOL and gap.max() > 100 * LOGPROB_TOL


def test_the_backend_generates_and_embeds_and_counts_its_experts(backend):
    from consensus_tpu.backends.base import GenerationRequest
    from consensus_tpu.obs.metrics import get_registry

    counted = _moe_counters()
    requests = [GenerationRequest(user_prompt="what should we do?", max_tokens=8,
                                  temperature=0.7, seed=100 + i) for i in range(8)]
    results = backend.generate(requests)
    assert len(results) == 8 and len({r.text for r in results}) > 1
    after = _moe_counters()
    assert after["calls"] > counted["calls"]
    sent = (after["held"] + after["absent"]) - (counted["held"] + counted["absent"])
    assert sent % (8 * 6 * 8) == 0  # rows x routed layers x experts a token, a step
    vectors = backend.embed(["one text", "another, longer text"])
    assert vectors.shape == (2, CONFIG.d_model)
    np.testing.assert_allclose(np.linalg.norm(vectors, axis=1), 1.0, atol=1e-5)
    gauges = {s["labels"]["kind"]: s["value"] for s in get_registry().snapshot()[
        "families"]["backend_kv_bytes_per_token"]["series"]}
    assert gauges == {"full": 4 * 2 * 2 * 40, "window": 4 * 5 * 4 * 40,
                      "all": 4 * 40 * (4 + 20)}


# -- budgets ---------------------------------------------------------------------------


def test_a_tokens_bytes_are_the_sum_over_the_kinds(backend):
    assert backend._kv_page_bytes(16) == 16 * 4 * 40 * (2 * 2 + 5 * 4)
    assert backend.kv_quant is False  # no int8 form of a cache by kind
    assert backend.kv_cache_identity()[-1] == (
        "kinds", (("full", 2, 2, 24, 16), ("window", 5, 4, 24, 16)))
    # The published widths: 2,560 B a full layer, 5,120 B a window layer.
    full = dataclasses.replace(
        CONFIG, n_heads=64, n_kv_heads=4, swa_kv_heads=8, head_dim=192,
        v_head_dim=128, rotary_dim=64)
    assert full.kv_bytes_per_token(2) == 2 * 2560 + 5 * 5120 == 30 * 1024


def test_a_score_chunks_and_an_embedding_batchs_temporaries_at_the_published_widths(
        backend, monkeypatch):
    from consensus_tpu.backends import tpu

    full = dataclasses.replace(
        CONFIG, d_model=4096, n_heads=64, n_kv_heads=4, swa_kv_heads=8,
        head_dim=192, v_head_dim=128, rotary_dim=64, ffn_hidden=16384,
        expert_hidden=2048, n_experts=256, experts_held=(0, 16), vocab_size=19072)
    monkeypatch.setattr(backend, "config", full)
    bf16 = {"embed": jnp.zeros((1,), jnp.bfloat16)}
    monkeypatch.setattr(backend, "params", bf16)
    # 64 rows x 256 over 1,792 keys: the float32 logits of 64 heads, their
    # shares beside the sink's and the weights.  The TPU compiler's own count
    # (described v5e, PR 32) is 8.48 GB of temporaries at 32 rows, 15.8 GB
    # with weights and pool, and 4.32 GB at 16 rows, 11.6 GB in all: the
    # count here has to send the cell to 16.
    logits = lambda rows: rows * 256 * 64 * 1792 * 10
    assert backend._score_chunk_transient_bytes(64, 256, 1792) > logits(64) > 18e9
    assert backend._score_chunk_transient_bytes(32, 256, 1792) > 8.48e9
    assert 4.32e9 < backend._score_chunk_transient_bytes(16, 256, 1792) < 7e9
    # An embedding batch: a key-value group's 16 heads at a time.
    assert backend._dense_attention_bytes(32, 1536, 1536) == 32 * 1536 * 1536 * 16 * 6
    monkeypatch.setattr(backend, "max_batch_rows", 32)
    monkeypatch.setattr(backend, "max_context", 4096)
    texts = ["a" * 1500] * 20
    assert backend._embed_rows_allowed(texts) == 8
    assert backend._embed_rows_allowed(["a" * 900] * 20) == 32
    assert backend._embed_rows_allowed(["a" * 300] * 3) == 4
    monkeypatch.setattr(backend, "config", get_model_config("tiny-llama3"))
    assert backend._embed_rows_allowed(texts) == backend.max_batch_rows


# -- what refuses, by name ---------------------------------------------------------------


def test_token_search_the_stream_path_and_a_mesh_refuse_by_name(backend, params):
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
    from consensus_tpu.backends.session import FusedSessionUnavailable
    from consensus_tpu.serve.scheduler import TRANSIENT_EXCEPTIONS

    assert issubclass(LayerKindsUnsupported, ConfigurationUnsupported)
    assert issubclass(LayerKindsUnsupported, ValueError)
    assert not issubclass(LayerKindsUnsupported, TRANSIENT_EXCEPTIONS)
    assert not issubclass(LayerKindsUnsupported, FusedSessionUnavailable)


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


def test_the_pallas_kernels_and_the_int8_tail_refuse_by_name(params):
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


def test_the_partition_rules_name_every_leaf(params):
    from consensus_tpu.parallel.mesh import match_partition_rules

    specs = match_partition_rules(params)
    assert set(specs["layers"]) == {"full_dense", "window_moe", "full_moe"}
    assert set(specs["layers"]["window_moe"]) == set(params["layers"]["window_moe"])


def test_a_configuration_of_kinds_is_held_to_its_keys():
    with pytest.raises(ValueError, match="one entry a layer"):
        dataclasses.replace(CONFIG, n_layers=6)
    with pytest.raises(ValueError, match="experts_held"):
        dataclasses.replace(CONFIG, experts_held=(28, 8))
    with pytest.raises(ValueError, match="need hybrid_layer_pattern"):
        dataclasses.replace(get_model_config("tiny-llama3"), swa_sink=True)
    with pytest.raises(ValueError, match="sliding_window"):
        dataclasses.replace(CONFIG, sliding_window=None)


def test_the_programs_name_the_new_scopes():
    from test_falcon_h1 import _lower

    text = _lower("generate_tokens_shared_trunk", CONFIG).as_text(debug_info=True)
    for scope in ("attention_window", "attention", "moe_router", "moe_dispatch",
                  "moe_experts", "moe_combine", "kv_write"):
        assert re.search(rf'[/"]{scope}[/"]', text), scope
    text = _lower("paged_score_chunk", CONFIG).as_text(debug_info=True)
    for scope in ("attention_window", "moe_experts"):
        assert re.search(rf'[/"]{scope}[/"]', text), scope


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


@pytest.mark.parametrize("method", ["best_of_n", "zero_shot"])
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
    assert "more than one kind" in error["message"]
    assert "token-search" in error["message"] and error["request_id"]
