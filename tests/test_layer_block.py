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

(c) What a layer reads and updates rides that scan's carry whole (page pools,
the dense cache, the decode tail, the recurrent state), and a write in place
touches the rows it names and nothing else.
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


# ---------------------------------------------------------------------------
# (c) What a layer writes is carried, and written where it lies
# ---------------------------------------------------------------------------

PAGES, PAGE, BLOCKS, CHUNK = 3 * ROWS, 4, 3, 8


def _subjaxprs(eqn):
    for value in eqn.params.values():
        for sub in value if isinstance(value, (list, tuple)) else [value]:
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _layer_scans(jaxpr):
    """Every ``scan`` equation under the scope ``layers``, at any depth."""
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "scan"
                and str(eqn.source_info.name_stack).split("/")[-1] == "layers"):
            yield eqn
        for sub in _subjaxprs(eqn):
            yield from _layer_scans(sub)


def _paged_arguments(c, rows=ROWS):
    tokens = jnp.zeros((rows, CHUNK), jnp.int32)
    return dict(
        tokens=tokens, valid=jnp.ones((rows, CHUNK), bool),
        tables=jnp.zeros((rows, BLOCKS), jnp.int32),
        lengths=jnp.full((rows,), CHUNK, jnp.int32),
        state=jax.eval_shape(lambda: stepper.make_page_state(
            c, PAGES, PAGE, jnp.float32, ssm_rows=rows)))


def _trace(program, c, params):
    """(the program's jaxpr, the shapes of every buffer its layers read and
    update, those of them that some layer scan of the program may only
    read)."""
    shapes = jax.eval_shape(lambda: params)
    a = _paged_arguments(c)
    ssm = jax.tree.leaves(a["state"].ssm)
    written = [a["state"].k_pages.shape] + [leaf.shape for leaf in ssm]
    read_elsewhere = []
    if program == "paged_prefill_chunk":
        jaxpr = jax.make_jaxpr(lambda p, state: stepper.paged_prefill_chunk(
            p, c, a["tokens"], a["valid"], state, a["tables"], a["lengths"],
            a["tokens"], a["tokens"]))(shapes, a["state"])
    elif program == "paged_score_chunk":
        more = dict(ssm_rows=jnp.zeros((ROWS,), jnp.int32)) if c.has_ssm else {}
        jaxpr = jax.make_jaxpr(lambda p, state: stepper.paged_score_chunk(
            p, c, a["tokens"], a["tokens"], a["valid"], a["valid"], state,
            a["tables"], a["lengths"], a["tokens"], a["tokens"], **more))(
                shapes, a["state"])
    else:
        from consensus_tpu.models.generate import generate_tokens_shared_trunk

        jaxpr = jax.make_jaxpr(lambda p: generate_tokens_shared_trunk(
            p, c, jnp.zeros((1, PROMPT), jnp.int32), jnp.ones((1, PROMPT), bool),
            ROWS, jnp.zeros((ROWS, 2), jnp.uint32), max_new_tokens=STEPS,
            temperature=jnp.ones((ROWS,)),
            eos_ids=jnp.asarray([-1], jnp.int32)))(shapes)
        kv = (c.n_kv_heads, c.head_dim)
        written = [(c.n_layers, 1, PROMPT) + kv, (c.n_layers, ROWS, STEPS) + kv]
        read_elsewhere = written[:1]  # the prefill writes the trunk, a step reads it
        for rows in (1, ROWS) if c.has_ssm else ():
            written += [leaf.shape for leaf in jax.tree.leaves(
                jax.eval_shape(lambda: tf.make_ssm_state(c, rows)))]
    return jaxpr.jaxpr, set(written), set(read_elsewhere)


@pytest.mark.parametrize("preset", PRESETS)
@pytest.mark.parametrize("program", [
    "paged_score_chunk", "paged_prefill_chunk", "generate_tokens_shared_trunk"])
def test_the_layer_scan_carries_what_a_layer_writes(preset, program):
    """Pools, cache, tail and state are among the carried values of the scan
    under ``layers`` and among neither its scanned inputs nor its stacked
    outputs: handed in and taken back that way, each is sliced out of the
    stack and stacked back whole every layer."""
    c, params, _, _ = _model(preset)
    jaxpr, written, read_elsewhere = _trace(program, c, params)
    scans = list(_layer_scans(jaxpr))
    assert scans
    carried = set()
    for scan in scans:
        consts, carry = scan.params["num_consts"], scan.params["num_carry"]
        carried |= {v.aval.shape for v in scan.invars[consts:consts + carry]}
        scanned = {v.aval.shape for v in scan.invars[consts + carry:]}
        stacked = {v.aval.shape for v in scan.outvars[carry:]}
        assert not scanned & (written - read_elsewhere), scanned & written
        assert not stacked & written, stacked & written
    assert written <= carried, written - carried


def _filled_pool(c, seed):
    """A pool with something in every cell of every page, and a state a row."""
    state = stepper.make_page_state(c, PAGES, PAGE, jnp.float32, ssm_rows=ROWS)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    return jax.tree.map(
        lambda leaf: jax.random.normal(next(keys), leaf.shape, leaf.dtype), state)


@pytest.mark.parametrize("preset", PRESETS)
@pytest.mark.parametrize("program", ["paged_prefill_chunk", "paged_score_chunk"])
def test_a_paged_write_touches_the_cells_it_names_and_no_other(preset, program):
    """On a donated pool: rows 0 and 1 write their columns into their own
    pages, row 2 has no valid column and writes to the sink.  Every cell of
    every layer that no cursor names (the pages of the context both rows
    read, row 2's pages, the offsets past a row's columns) is bit for bit
    what it was; the row with no valid column keeps its state at every
    layer."""
    c, params, prompts, _ = _model(preset)
    state = _filled_pool(c, 3)
    before = jax.tree.map(np.asarray, state)
    sink = PAGES
    columns = np.arange(CHUNK)
    # Pages 0-1: a context all rows read.  Row r writes pages 2 + 2r, 3 + 2r.
    tables = np.stack([[0, 1, 2 + 2 * r, 3 + 2 * r] for r in range(ROWS)])
    valid = np.ones((ROWS, CHUNK), bool)
    valid[1, 5:] = False
    valid[2] = False
    own = 2 + 2 * np.arange(ROWS)[:, None] + columns[None, :] // PAGE
    write_pages = np.where(valid, own, sink).astype(np.int32)
    write_offsets = np.where(valid, columns[None, :] % PAGE, 0).astype(np.int32)
    lengths = 2 * PAGE + valid.sum(axis=1).astype(np.int32)
    tokens = jnp.asarray(np.resize(prompts, (ROWS, CHUNK)))
    args = (jnp.asarray(valid), state, jnp.asarray(tables.astype(np.int32)),
            jnp.asarray(lengths), jnp.asarray(write_pages),
            jnp.asarray(write_offsets))
    if program == "paged_prefill_chunk":
        _, after = stepper.paged_prefill_chunk(params, c, tokens, *args)
    else:
        more = dict(ssm_rows=jnp.arange(ROWS)) if c.has_ssm else {}
        _, after = stepper.paged_score_chunk(
            params, c, tokens, tokens, args[0], *args, **more)
    named = np.zeros((PAGES + 1, PAGE), bool)
    named[write_pages, write_offsets] = True
    assert named[:sink].sum() == valid.sum()
    for was, now in ((before.k_pages, after.k_pages),
                     (before.v_pages, after.v_pages)):
        now = np.asarray(now)
        assert now.shape == was.shape
        np.testing.assert_array_equal(now[:, ~named], was[:, ~named])
        changed = (now[:, :sink] != was[:, :sink]).any(axis=(-1, -2))
        np.testing.assert_array_equal(
            changed, np.broadcast_to(named[:sink], changed.shape))
    if c.has_ssm:
        for was, now in zip(before.ssm, after.ssm):
            now = np.asarray(now)
            if program == "paged_score_chunk":  # the snapshots stay
                np.testing.assert_array_equal(now, was)
            else:
                np.testing.assert_array_equal(now[:, 2], was[:, 2])
                moved = (now[:, :2] != was[:, :2]).reshape(len(now), 2, -1)
                assert moved.any(axis=-1).all()


@pytest.mark.parametrize("preset", PRESETS)
@pytest.mark.parametrize("quantized", [False, True], ids=["plain", "int8"])
def test_a_decode_step_writes_one_column_of_the_tail(preset, quantized):
    """The step's K/V land in column ``write_col`` of every layer of the
    carried tail; the columns before and past it are the zeros they were."""
    c, params, prompts, tails = _model(preset)
    trunk = _prefilled(c, params, prompts[:1], PROMPT)
    shape = (c.n_layers, ROWS, STEPS, c.n_kv_heads, c.head_dim)
    empty = ((jnp.zeros(shape, jnp.int8), jnp.zeros(shape[:-1] + (1,)))
             if quantized else jnp.zeros(shape))
    column = 2
    _, tail_k, tail_v, _ = tf.forward_trunk_tail(
        params, c, jnp.asarray(tails[:, 0]), jnp.full((ROWS,), PROMPT), trunk,
        empty, empty,
        jnp.broadcast_to(PROMPT + jnp.arange(STEPS), (ROWS, STEPS)),
        jnp.asarray(column, jnp.int32), n_slots=ROWS, n_roles=1,
        ssm=tf.fork_ssm(trunk.ssm, ROWS) if c.has_ssm else None)
    for tail in (tail_k, tail_v):
        for leaf, was in zip(jax.tree.leaves(tail), jax.tree.leaves(empty)):
            leaf = np.asarray(leaf)
            assert leaf.shape == was.shape and leaf.dtype == was.dtype
            others = np.delete(leaf, column, axis=2)
            np.testing.assert_array_equal(others, np.zeros_like(others))
            assert (leaf[:, :, column] != 0).any(axis=(-1, -2)).all()
