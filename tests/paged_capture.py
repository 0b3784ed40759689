"""What ``TPUBackend`` hands its score-matrix programs, without running them.

``captured_matrix`` runs ``backend.score_matrix`` with the four programs of
``models/stepper.py`` that the fused path calls replaced by recorders, so the
host's whole preparation runs (tokenising, the shared pages' plan, the
prefill's and the chunks' arrays) and nothing is compiled: the arrays a
program would have got are there to be compared, element for element.
"""

import types

import numpy as np


class CountingTokenizer:
    """A tokenizer that notes every text it is asked to encode."""

    def __init__(self, inner):
        self._inner = inner
        self.texts = []

    def encode(self, text, add_bos=False):
        self.texts.append(text)
        return self._inner.encode(text, add_bos=add_bos)

    def __getattr__(self, name):
        return getattr(self._inner, name)


PREFILL_ARRAYS = ("tokens", "valid", "tables", "lengths", "write_pages",
                  "write_offsets")
CHUNK_ARRAYS = ("tokens", "targets", "score_mask", "chunk_valid", "tables",
                "lengths", "write_pages", "write_offsets", "ssm_rows")


def captured_matrix(backend, request, monkeypatch):
    """``{"prefill_args", "prefill", "chunk_args", "chunks"}`` of one
    ``score_matrix`` call: the arguments ``_prefill_shared_pages`` and each
    ``_score_matrix_chunk`` were called with, and the arrays (NumPy, by the
    programs' parameter names) each launch of ``paged_prefill_chunk`` and
    ``paged_score_chunk`` was handed."""
    from consensus_tpu.models import stepper

    seen = {"prefill_args": None, "prefill": [], "chunk_args": [], "chunks": []}

    def make_page_state(config, num_pages, page_size, dtype=None, mesh=None,
                        ssm_rows=0):
        return types.SimpleNamespace(
            moe_held=None,
            ssm=types.SimpleNamespace(h=np.zeros((1, ssm_rows))))

    def paged_prefill_chunk(params, config, tokens, valid, state, tables,
                            lengths, write_pages, write_offsets, mesh=None):
        given = (tokens, valid, tables, lengths, write_pages, write_offsets)
        seen["prefill"].append(
            {k: np.asarray(v) for k, v in zip(PREFILL_ARRAYS, given)})
        return None, state

    def paged_score_chunk(params, config, tokens, targets, score_mask,
                          chunk_valid, state, tables, lengths, write_pages,
                          write_offsets, mesh=None, ssm_rows=None):
        given = (tokens, targets, score_mask, chunk_valid, tables, lengths,
                 write_pages, write_offsets, ssm_rows)
        seen["chunks"].append({
            k: None if v is None else np.asarray(v)
            for k, v in zip(CHUNK_ARRAYS, given)})
        zeros = np.zeros((tokens.shape[0],), np.float32)
        return (zeros, zeros, zeros, zeros), state

    def utility_matrix(stats, n_candidates, n_agents, stat, rule, default):
        return (np.zeros((n_candidates, n_agents), np.float32),
                np.zeros((n_candidates,), np.float32), None)

    for fake in (make_page_state, paged_prefill_chunk, paged_score_chunk,
                 utility_matrix):
        monkeypatch.setattr(stepper, fake.__name__, fake)

    prefill, chunk = backend._prefill_shared_pages, backend._score_matrix_chunk

    def noting_prefill(state, prefix_ids, shared, sink, mesh):
        seen["prefill_args"] = (prefix_ids, shared, sink)
        return prefill(state, prefix_ids, shared, sink, mesh)

    def noting_chunk(state, rows, shared, prefix_ids, n_rows, width,
                     max_blocks, shared_total, max_private, sink, mesh):
        seen["chunk_args"].append((rows, shared, prefix_ids, n_rows, width,
                                   max_blocks, shared_total, max_private, sink))
        return chunk(state, rows, shared, prefix_ids, n_rows, width,
                     max_blocks, shared_total, max_private, sink, mesh)

    monkeypatch.setattr(backend, "_prefill_shared_pages", noting_prefill)
    monkeypatch.setattr(backend, "_score_matrix_chunk", noting_chunk)
    result = backend.score_matrix([request])[0]
    assert result.path == "fused"
    return seen
