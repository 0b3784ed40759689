"""The host's preparation of a call, held to what it replaced.

A score matrix's paged prefill and score chunks are laid out with array
arithmetic, and a call's PRNG keys are folded by one program; the per-token
and per-row loops they replaced live on here as the oracle.  Every array a
program is handed has to equal, element for element, what those loops made:
for a dense configuration, one with recurrent layers (a row a context,
``ssm_rows``) and one with layers of more than one kind (tables in steps).
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from consensus_tpu.backends.base import GenerationRequest
from consensus_tpu.backends.score_matrix import AgentContext, ScoreMatrixRequest
from consensus_tpu.backends.tpu import TPUBackend, _bucket
from consensus_tpu.models.config import get_model_config

from paged_capture import CHUNK_ARRAYS, PREFILL_ARRAYS, captured_matrix

PAGE = TPUBackend._SCORE_PAGE_SIZE


# -- the oracle: the loops as they stood before the arrays ---------------------------


def prefill_by_loops(backend, prefix_ids, shared, sink):
    """``_prefill_shared_pages``' arrays, a launch a dictionary, written a
    token at a time."""
    ps = PAGE
    pre = [p for p in prefix_ids if shared[p][1] > 0]
    if not pre:
        return []
    if backend.config.has_ssm:
        pre = list(prefix_ids)
    n_rows = _bucket(len(pre), minimum=8)
    max_n0 = max(shared[p][2] for p in pre)
    chunk = min(256, _bucket(max_n0, minimum=ps))
    n_blocks = backend._table_blocks(max(shared[p][1] for p in pre))
    tables = np.full((n_rows, n_blocks), -1, np.int32)
    for r, p in enumerate(pre):
        first, npg, _ = shared[p]
        tables[r, :npg] = np.arange(first, first + npg, dtype=np.int32)
    tables[len(pre):] = tables[0]
    pad_id = backend.tokenizer.pad_id
    launches = []
    for k in range(0, max_n0, chunk):
        tokens = np.full((n_rows, chunk), pad_id, np.int32)
        valid = np.zeros((n_rows, chunk), bool)
        lengths = np.zeros((n_rows,), np.int32)
        write_pages = np.full((n_rows, chunk), sink, np.int32)
        write_offsets = np.zeros((n_rows, chunk), np.int32)
        for r, p in enumerate(pre):
            ids = prefix_ids[p]
            first, _, n0 = shared[p]
            hi = min(n0, k + chunk)
            lengths[r] = hi
            if hi <= k:
                continue
            piece = ids[k:hi]
            valid[r, : len(piece)] = True
            tokens[r, : len(piece)] = piece
            for j in range(len(piece)):
                write_pages[r, j] = first + (k + j) // ps
                write_offsets[r, j] = (k + j) % ps
        tokens[len(pre):] = tokens[0]
        valid[len(pre):] = valid[0]
        lengths[len(pre):] = lengths[0]
        launches.append(dict(tokens=tokens, valid=valid, tables=tables,
                             lengths=lengths, write_pages=write_pages,
                             write_offsets=write_offsets))
    return launches


def chunk_by_loops(backend, chunk, shared, prefix_ids, n_rows, width,
                   max_blocks, shared_total, max_private, sink):
    """``_score_matrix_chunk``'s arrays, written a token at a time."""
    ps = PAGE
    pad_id = backend.tokenizer.pad_id
    tokens = np.full((n_rows, width), pad_id, np.int32)
    targets = np.zeros((n_rows, width), np.int32)
    score_mask = np.zeros((n_rows, width), bool)
    chunk_valid = np.zeros((n_rows, width), bool)
    tables = np.full((n_rows, max_blocks), -1, np.int32)
    lengths = np.zeros((n_rows,), np.int32)
    write_pages = np.full((n_rows, width), sink, np.int32)
    write_offsets = np.zeros((n_rows, width), np.int32)
    snapshot_of = {p: i for i, p in enumerate(prefix_ids)}
    ssm_rows = np.zeros((n_rows,), np.int32)
    for r, (prefix, cont, q_len, n_private) in enumerate(chunk):
        ids = prefix_ids[prefix]
        first, npg, n0 = shared[prefix]
        ssm_rows[r] = snapshot_of[prefix]
        stream = ids + cont
        block = stream[n0 : n0 + q_len]
        tokens[r, : q_len] = block
        chunk_valid[r, : q_len] = True
        lengths[r] = n0 + q_len
        tables[r, :npg] = np.arange(first, first + npg, dtype=np.int32)
        base = shared_total + r * max_private
        tables[r, npg : npg + n_private] = np.arange(
            base, base + n_private, dtype=np.int32
        )
        for j in range(q_len):
            pos = n0 + j
            write_pages[r, j] = base + pos // ps - n0 // ps
            write_offsets[r, j] = pos % ps
            if pos + 1 < len(stream):
                targets[r, j] = stream[pos + 1]
        lo = len(ids) - 1 - n0
        score_mask[r, lo : lo + len(cont)] = bool(cont)
    n_real = len(chunk)
    tokens[n_real:] = tokens[0]
    targets[n_real:] = targets[0]
    chunk_valid[n_real:] = chunk_valid[0]
    lengths[n_real:] = lengths[0]
    tables[n_real:] = tables[0]
    ssm_rows[n_real:] = ssm_rows[0]
    return dict(tokens=tokens, targets=targets, score_mask=score_mask,
                chunk_valid=chunk_valid, tables=tables, lengths=lengths,
                write_pages=write_pages, write_offsets=write_offsets,
                ssm_rows=ssm_rows if backend.config.has_ssm else None)


# -- the three kinds of configuration, the contexts' lengths -------------------------

#: Contexts by the ids they make with the BOS (one a byte, ``chat=False``).
CONTEXTS = {
    # None reaches a page: nothing is prefilled; a recurrent configuration
    # still names a snapshot a context.
    "shorter_than_a_page": [9, 16, 3],
    # 17 and 33 ids: the last full page ends one id before the end, so one
    # token is re-fed a row; 32 ids: the second page is full and re-fed whole.
    "on_a_page_boundary": [17, 32, 33, 48],
    # Two prefill chunks of 256, the short contexts idle in the second; one
    # context shorter than a page beside them.
    "longer_than_a_prefill_chunk": [300, 40, 5, 257],
}
#: An empty candidate (a row that scores nothing), one of one token, and 28
#: more: three or four contexts make 90 or 120 rows, a chunk of 64 and one
#: of 26 or 56 real rows whose pad rows repeat its first.
CANDIDATES = ["", "x"] + ["statement %d %s" % (i, "ab" * (i % 7)) for i in range(28)]


@pytest.fixture(scope="module", params=["tiny-llama3", "tiny-falcon-h1", "tiny-mimo-v2"])
def backend(request):
    config = get_model_config(request.param)
    assert (request.param, config.has_ssm, config.has_layer_kinds) in {
        ("tiny-llama3", False, False), ("tiny-falcon-h1", True, False),
        ("tiny-mimo-v2", False, True)}
    return TPUBackend(config=config, dtype="float32", max_context=1024,
                      max_batch_rows=8)


def _same(got, want, names):
    for name in names:
        if want[name] is None:
            assert got[name] is None, name
            continue
        assert got[name].dtype == want[name].dtype, name
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


@pytest.mark.parametrize("lengths", sorted(CONTEXTS))
def test_the_paged_programs_get_the_arrays_the_loops_made(
        backend, lengths, monkeypatch):
    contexts = ["c" * (n - 1) for n in CONTEXTS[lengths]]
    request = ScoreMatrixRequest(
        agents=tuple(AgentContext(context=c, chat=False) for c in contexts),
        candidates=tuple(CANDIDATES))
    seen = captured_matrix(backend, request, monkeypatch)

    prefix_ids, shared, sink = seen["prefill_args"]
    assert sorted(len(ids) for ids in prefix_ids.values()) == sorted(CONTEXTS[lengths])
    want = prefill_by_loops(backend, prefix_ids, shared, sink)
    assert len(seen["prefill"]) == len(want)
    assert len(want) == {"shorter_than_a_page": 0, "on_a_page_boundary": 1,
                         "longer_than_a_prefill_chunk": 2}[lengths]
    for got, launch in zip(seen["prefill"], want):
        _same(got, launch, PREFILL_ARRAYS)

    rows = len(CANDIDATES) * len(contexts)
    assert sum(len(args[0]) for args in seen["chunk_args"]) == rows
    assert len(seen["chunks"]) == len(seen["chunk_args"]) >= 2
    for got, args in zip(seen["chunks"], seen["chunk_args"]):
        _same(got, chunk_by_loops(backend, *args), CHUNK_ARRAYS)
    # The last chunk is not full: its pad rows repeat its first row's shape,
    # write to the sink and score nothing.
    last, (last_rows, *_) = seen["chunks"][-1], seen["chunk_args"][-1]
    assert len(last_rows) < last["tokens"].shape[0]
    assert (last["write_pages"][len(last_rows):] == sink).all()
    assert not last["score_mask"][len(last_rows):].any()
    # The empty candidate's rows score nothing and are a token short.
    first_rows = seen["chunks"][0]
    assert not first_rows["score_mask"][: len(contexts)].any()


# -- a call's keys -----------------------------------------------------------------


def _keys_by_rows(backend, kind, seeds):
    """``_row_keys`` as it was: a ``PRNGKey``, a ``fold_in`` and a slot of a
    ``stack`` a row, the fold hashed from the row's parts."""

    def fold_seed(*parts):
        digest = hashlib.blake2b(repr(parts).encode(), digest_size=4).digest()
        fold = int.from_bytes(digest, "big") % (2**31)
        return jax.random.fold_in(jax.random.PRNGKey(backend.base_seed), fold)

    keys = []
    for row, seed in enumerate(seeds):
        if seed is None:
            backend._unseeded_calls += 1
            keys.append(fold_seed(kind, "unseeded", row, backend._unseeded_calls))
        else:
            keys.append(fold_seed(kind, seed))
    return jnp.stack(keys)


@pytest.fixture(scope="module")
def gemma():
    return TPUBackend(model="tiny-gemma2", max_context=256, base_seed=0)


@pytest.mark.parametrize("seeds", [
    [3, 7, 0, 2**31 + 5, 3], [None] * 5, [3, None, 7, None, 0],
], ids=["seeded", "unseeded", "mixed"])
def test_a_calls_keys_are_the_per_row_keys_bit_for_bit(gemma, seeds):
    backend = TPUBackend(config=gemma.config, params=gemma.params,
                         max_context=256, base_seed=3)
    backend._unseeded_calls = 40
    want = np.asarray(_keys_by_rows(backend, "generate", seeds))
    after = backend._unseeded_calls
    assert after == 40 + seeds.count(None)
    backend._unseeded_calls = 40
    got = backend._row_keys("generate", seeds)
    assert backend._unseeded_calls == after  # a nonce an unseeded row, as before
    assert got.dtype == jnp.uint32 and got.shape == (len(seeds), 2)
    np.testing.assert_array_equal(np.asarray(got), want)
    # A seeded row's key is its own whatever the rows beside it; an unseeded
    # row's changes with the nonce.
    again = np.asarray(backend._row_keys("generate", seeds))
    for row, seed in enumerate(seeds):
        assert (again[row] == want[row]).all() == (seed is not None)
    # Another kind of call folds other keys; the session's one key is a row's.
    assert not np.array_equal(np.asarray(backend._row_keys("next_token", [3])),
                              np.asarray(backend._row_keys("generate", [3])))
    np.testing.assert_array_equal(
        np.asarray(backend._row_keys("search", [11]))[0],
        np.asarray(backend._fold_seed("search", 11)))


def test_the_keys_pinned_from_the_parent_commit():
    """``_row_keys("generate", [3, None, 7, None, 0])`` then
    ``_row_keys("next_token", [None, 5])`` on a new backend of base seed 3,
    as commit b7b53e6 printed them."""
    backend = TPUBackend(model="tiny-gemma2", max_context=256, base_seed=3)
    assert np.asarray(backend._row_keys("generate", [3, None, 7, None, 0])).tolist() == [
        [3975172277, 159757983], [2105389491, 2222229611], [3441679331, 3627656443],
        [2978531928, 3342122745], [4238648226, 2023204847]]
    assert backend._unseeded_calls == 2
    assert np.asarray(backend._row_keys("next_token", [None, 5])).tolist() == [
        [3225809101, 3849731705], [2881616741, 607452473]]
    assert backend._unseeded_calls == 3


def test_a_seeded_generation_call_returns_the_parents_tokens(gemma):
    """Tokens of commit b7b53e6 for the same seeded calls: four rows over one
    prompt (the shared trunk) and three prompts (the classic path)."""
    shared = [GenerationRequest(
        user_prompt="Should the town build a new playground?",
        system_prompt="Write one statement.", max_tokens=8, temperature=0.9,
        seed=11 + i) for i in range(4)]
    assert [r.token_ids for r in gemma.generate(shared)] == [
        (172, 170, 217, 76, 187, 107, 70, 136), (133, 176, 181, 9, 193, 125, 239, 234),
        (159, 105, 32, 189, 123, 227, 251, 213), (79, 127, 47, 89, 34, 129, 29, 140)]
    classic = [GenerationRequest(user_prompt=f"Prompt {i}", max_tokens=6,
                                 temperature=0.8, seed=40 + i) for i in range(3)]
    assert [r.token_ids for r in gemma.generate(classic)] == [
        (189, 157, 112, 133, 126, 90), (149, 210, 134, 249, 128, 34),
        (37, 29, 14, 132, 94, 159)]
