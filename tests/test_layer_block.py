"""The seam between the one layer and the four cache layouts.

``transformer.layer_block`` is the only place the layer is written out and
``transformer.scan_layers`` the only loop over the layers; ``forward``,
``forward_trunk_tail``, ``forward_shared_trunk`` and
``stepper._paged_forward`` each hand it an ``attend`` over their own cache.

(a) Every body agrees with ``forward`` without a cache on the same tokens:
an agreement between bodies, not a recorded number.  Cells that another
file already holds at this level are left there (``tests/test_engine.py``
``TestPagedProgramNumerics``: paged against dense on ``tiny-gemma2`` and
``tiny-llama3``; ``tests/test_transformer.py``
``test_kv_cache_decode_matches_full_forward``: the dense cache on
``tiny-gemma2``).

(b) The structure itself: one reader of the layer's weights and one scan
under ``layers`` in ``consensus_tpu/models``.
"""

import ast
import functools
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from consensus_tpu.models import stepper
from consensus_tpu.models import transformer as tf
from consensus_tpu.models.config import get_model_config

#: Longer than ``tiny-gemma2``'s window of 16, so local layers mask.
PROMPT, STEPS, ROWS = 18, 6, 3
SEGMENT = STEPS // 2  # the int8 case freezes one block of this width


PRESETS = ("tiny-gemma2", "tiny-llama3", "tiny-falcon-h1")


@functools.lru_cache(maxsize=None)
def _model(preset):
    c = get_model_config(preset)
    params = tf.init_params(c, jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    prompts = rng.randint(1, c.vocab_size, size=(2, PROMPT)).astype(np.int32)
    tails = rng.randint(1, c.vocab_size, size=(ROWS, STEPS)).astype(np.int32)
    return c, params, prompts, tails


def _reference(c, params, tokens):
    """``forward`` without a cache: hidden states at the last position."""
    rows, width = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(width), (rows, width))
    hidden, _ = tf.forward(
        params, c, jnp.asarray(tokens), positions,
        jnp.ones((rows, width), bool), return_hidden=True)
    return np.asarray(hidden[:, -1])


def _prefilled(c, params, tokens, width):
    """A dense cache of ``width`` columns holding ``tokens`` from column 0."""
    rows, span = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(span), (rows, span))
    _, cache = tf.forward(
        params, c, jnp.asarray(tokens), positions, jnp.ones((rows, span), bool),
        tf.make_cache(c, rows, width), 0, return_hidden=True)
    return cache


def _rows_of_one_prompt(prompts, tails):
    return np.concatenate(
        [np.broadcast_to(prompts[:1], (ROWS, PROMPT)), tails], axis=1)


def dense_cache(c, params, prompts, tails):
    """``forward`` with a cache: all but the last token prefilled, the last
    one written at its column and attended over the whole buffer."""
    tokens = _rows_of_one_prompt(prompts, tails)
    width = tokens.shape[1]
    cache = _prefilled(c, params, tokens[:, :-1], width)
    hidden, _ = tf.forward(
        params, c, jnp.asarray(tokens[:, -1:]),
        jnp.full((ROWS, 1), width - 1), jnp.ones((ROWS, 1), bool),
        cache, width - 1, return_hidden=True)
    return hidden[:, 0], tokens


def trunk_tail(c, params, prompts, tails, quantized=False):
    """Teacher-forced decode steps over one shared trunk row.  Quantised, the
    tail is int8 with scales and ``SEGMENT`` columns wide: the first segment
    is frozen whole into a read-only block, as ``generate._segmented_loop``
    does."""
    trunk = _prefilled(c, params, prompts[:1], PROMPT)
    kv_shape = (c.n_layers, ROWS, SEGMENT if quantized else STEPS,
                c.n_kv_heads, c.head_dim)

    def empty_tail():
        if not quantized:
            return jnp.zeros(kv_shape)
        return (jnp.zeros(kv_shape, jnp.int8),
                jnp.zeros(kv_shape[:-1] + (1,), jnp.float32))

    width = kv_shape[2]
    tail_k, tail_v, frozen = empty_tail(), empty_tail(), {}
    ssm = tf.fork_ssm(trunk.ssm, ROWS) if c.has_ssm else None
    for step in range(STEPS):
        start = step - step % width
        if quantized and step == SEGMENT:
            frozen = dict(
                frozen_k=(tail_k,), frozen_v=(tail_v,),
                frozen_positions=(jnp.broadcast_to(
                    PROMPT + jnp.arange(SEGMENT), (ROWS, SEGMENT)),))
            tail_k, tail_v = empty_tail(), empty_tail()
        hidden, tail_k, tail_v, ssm = tf.forward_trunk_tail(
            params, c, jnp.asarray(tails[:, step]),
            jnp.full((ROWS,), PROMPT + step), trunk, tail_k, tail_v,
            jnp.broadcast_to(PROMPT + start + jnp.arange(width), (ROWS, width)),
            jnp.asarray(step - start, jnp.int32), n_slots=ROWS, n_roles=1,
            ssm=ssm, **frozen)
    return hidden, _rows_of_one_prompt(prompts, tails)


def trunk_tail_int8(c, params, prompts, tails):
    return trunk_tail(c, params, prompts, tails, quantized=True)


def shared_trunk(c, params, prompts, tails):
    """Every path's suffix over both roles' trunk rows: (P, R) hidden states,
    each the plain forward of that role's prompt and that path's suffix."""
    trunk = _prefilled(c, params, prompts, PROMPT)
    hidden = tf.forward_shared_trunk(
        params, c, jnp.asarray(tails), trunk,
        jnp.full((prompts.shape[0],), PROMPT - 1))
    tokens = np.stack([
        np.concatenate([prompt, tail]) for tail in tails for prompt in prompts])
    return hidden.reshape(len(tokens), -1), tokens


def paged(c, params, prompts, tails):
    """The chunked paged prefill: three chunks of eight tokens into pages of
    four; the last chunk's hidden states at its last column."""
    tokens = _rows_of_one_prompt(prompts, tails)
    page, chunk, blocks = 4, 8, tokens.shape[1] // 4
    state = stepper.make_page_state(
        c, ROWS * blocks, page, jnp.float32, ssm_rows=ROWS)
    tables = jnp.arange(ROWS * blocks, dtype=jnp.int32).reshape(ROWS, blocks)
    for start in range(0, tokens.shape[1], chunk):
        at = start + np.arange(chunk)
        hidden, state = stepper.paged_prefill_chunk(
            params, c, jnp.asarray(tokens[:, start:start + chunk]),
            jnp.ones((ROWS, chunk), bool), state, tables,
            jnp.full((ROWS,), start + chunk, jnp.int32),
            tables[:, at // page], jnp.broadcast_to(at % page, (ROWS, chunk)))
    return hidden, tokens


#: Cells another file holds (see the module's docstring).
ELSEWHERE = {("tiny-gemma2", dense_cache), ("tiny-gemma2", paged),
             ("tiny-llama3", paged)}
CELLS = [(preset, body) for preset in PRESETS
         for body in (dense_cache, trunk_tail, trunk_tail_int8, shared_trunk,
                      paged)
         if (preset, body) not in ELSEWHERE]


@pytest.mark.parametrize(
    "preset,body", CELLS, ids=[f"{p}-{b.__name__}" for p, b in CELLS])
def test_every_body_gives_the_plain_forwards_hidden_states(preset, body):
    c, params, prompts, tails = _model(preset)
    hidden, tokens = body(c, params, prompts, tails)
    want = _reference(c, params, tokens)
    got = np.asarray(hidden)
    assert got.shape == want.shape
    if body is trunk_tail_int8:
        # tests/test_segmented_decode.py holds the quantiser to half a step
        # of the int8 grid; keys and values each within theirs leave the
        # hidden states within one step, 1/127 of the largest value.
        assert np.abs(got - want).max() <= np.abs(want).max() / 127
        assert np.abs(got - want).max() > 0  # and the tail really is int8
    else:
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# (b) The layer is written once and looped over once
# ---------------------------------------------------------------------------

_MODELS = pathlib.Path(tf.__file__).resolve().parent


@functools.lru_cache(maxsize=None)
def _outermost_definitions():
    """(``file:name``, syntax tree) of every function and class at the top
    level of a file under ``consensus_tpu/models``."""
    return [(f"{path.name}:{fn.name}", fn)
            for path in sorted(_MODELS.glob("*.py"))
            for fn in ast.parse(path.read_text()).body
            if isinstance(fn, (ast.FunctionDef, ast.ClassDef))]


def _functions_that(match):
    """``file:function`` of every outermost function under
    ``consensus_tpu/models`` in which ``match(node)`` holds for some node."""
    return [name for name, fn in _outermost_definitions()
            if any(match(node) for node in ast.walk(fn))]


def _reads_layer_leaf(name):
    """``lp["<name>"]``: a subscript of a variable called ``lp``."""
    def match(node):
        return (isinstance(node, ast.Subscript)
                and isinstance(node.value, ast.Name) and node.value.id == "lp"
                and isinstance(node.slice, ast.Constant)
                and node.slice.value == name)
    return match


def _calls(name):
    def match(node):
        if not isinstance(node, ast.Call):
            return False
        fn = node.func
        return (fn.id if isinstance(fn, ast.Name) else getattr(fn, "attr", "")
                ) == name
    return match


def _scans_under_layers(node):
    """A ``with jax.named_scope("layers")`` whose body calls ``lax.scan``."""
    if not isinstance(node, ast.With):
        return False
    scoped = any(
        isinstance(item.context_expr, ast.Call)
        and getattr(item.context_expr.func, "attr", "") == "named_scope"
        and item.context_expr.args
        and getattr(item.context_expr.args[0], "value", None) == "layers"
        for item in node.items)
    return scoped and any(_calls("scan")(inner) for inner in ast.walk(node))


class TestTheLayerIsWrittenOnce:
    @pytest.mark.parametrize("leaf", ["attn_norm", "wq", "wk", "wv"])
    def test_one_function_reads_the_attention_weights(self, leaf):
        assert _functions_that(_reads_layer_leaf(leaf)) == [
            "transformer.py:layer_block"]

    @pytest.mark.parametrize("piece", ["ssm_mixer", "attn_out_block",
                                       "ffn_block"])
    def test_one_function_calls_each_piece_of_the_block(self, piece):
        assert _functions_that(_calls(piece)) == ["transformer.py:layer_block"]

    def test_one_function_scans_over_the_layers(self):
        assert _functions_that(_scans_under_layers) == [
            "transformer.py:scan_layers"]
        assert _functions_that(_calls("layer_block")) == [
            "transformer.py:scan_layers"]

    def test_the_four_bodies_loop_through_scan_layers(self):
        assert _functions_that(_calls("scan_layers")) == [
            "stepper.py:_paged_forward", "transformer.py:forward",
            "transformer.py:forward_trunk_tail",
            "transformer.py:forward_shared_trunk"]
