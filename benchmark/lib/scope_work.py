"""The terms of ``work.span_flops`` and ``work.step_bytes``, each alone.

``work`` counts a span's FLOPs as a matrix term (2 per layer-matrix parameter
per position), an attention term (4 x context x heads x head size per
position per layer) and a head term (the vocabulary projection where a
position is scored or sampled), and a decode step's bytes as the weights and
the cache.  A kernel's roofline needs its own term only.  Each term is what
``work`` computes for a model cut down to that term, so the same functions,
and ``useful.tally`` walking the same records, give it:

* no widths (``d_model`` and ``ffn_hidden`` 0) leaves the attention FLOPs and
  the cache bytes;
* no layers leaves the head's FLOPs and, as weights, the embedding, the final
  norm and an untied head: of those bytes the head's table alone is the
  head term's;
* the matrix term is the rest.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List

from benchmark.lib import work

TERMS = ("attention", "head", "matrix")


def attention_model(model: Dict[str, Any]) -> Dict[str, Any]:
    return {**model, "d_model": 0, "ffn_hidden": 0}


def head_model(model: Dict[str, Any]) -> Dict[str, Any]:
    return {**model, "n_layers": 0}


def attention_flops(model: Dict[str, Any], start: int, count: int) -> float:
    return work.span_flops(attention_model(model), start, count)


def head_flops(model: Dict[str, Any], with_head: int) -> float:
    return work.span_flops(head_model(model), 0, 0, with_head)


def matrix_flops(model: Dict[str, Any], count: int) -> float:
    return (work.span_flops(model, 0, count) - attention_flops(model, 0, count))


def cache_bytes(model: Dict[str, Any], cached_positions: int) -> float:
    return work.step_bytes(attention_model(model), cached_positions)


def head_bytes(model: Dict[str, Any]) -> float:
    """What a decode step reads of the head: its table once."""
    return float(model["vocab_size"] * model["d_model"] * work.BF16_BYTES)


def tally_terms(tally: Callable[..., Dict[str, Dict[str, float]]],
                model: Dict[str, Any], calls: List[Dict[str, Any]],
                lo: float, hi: float) -> Dict[str, Dict[str, Dict[str, float]]]:
    """``{term: {kind of call: {"flops", "bytes"}}}`` over the records that
    ``tally`` (``useful.tally``) walks.  The attention term's bytes are the
    cache's, the head term's the head's table a decode step, the matrix
    term's the rest of the weights."""
    whole = tally(model, calls, lo, hi)
    parts = {"attention": tally(attention_model(model), calls, lo, hi),
             "head": tally(head_model(model), calls, lo, hi)}
    out: Dict[str, Dict[str, Dict[str, float]]] = {term: {} for term in TERMS}
    for kind, entry in whole.items():
        rest = {"flops": entry["flops"], "bytes": entry["bytes"]}
        for term, by_kind in parts.items():
            part = by_kind.get(kind, {"flops": 0.0, "bytes": 0.0})
            part = {"flops": part["flops"], "bytes": part["bytes"]}
            if term == "head":
                # A model without layers still holds its embedding and its
                # final norm: of those bytes the head's table alone.
                part["bytes"] *= head_bytes(model) / work.weight_bytes(
                    head_model(model))
            out[term][kind] = part
            rest = {key: rest[key] - part[key] for key in rest}
        out["matrix"][kind] = rest
    return out
