"""A work count of its own, as a later PR's configuration brings one: the
dense count and one term more, ``post_norm``, for the two RMSNorms a layer
that ``use_post_norms`` adds (their weights read once a launch; a square, a
mean and a scale, some 3 FLOPs a value a position).  For the harness's test
on the CPU only."""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark.work import dense

TERMS = dense.TERMS + ("post_norm",)


def _values(model: Dict[str, Any]) -> int:
    return 2 * model["n_layers"] * model["d_model"]


def _with(own: float, term: Optional[str], rest: Any) -> float:
    """The new term alone, a dense term as the dense count has it, or the
    whole: the dense whole and the new term."""
    if term == "post_norm":
        return own
    return rest(term) + (own if term is None else 0)


def param_count(model: Dict[str, Any]) -> int:
    return dense.param_count(model) + _values(model)


def weight_bytes(model: Dict[str, Any], term: Optional[str] = None) -> int:
    return _with(_values(model) * dense.BF16_BYTES, term,
                 lambda t: dense.weight_bytes(model, t))


def span_flops(model: Dict[str, Any], start: int, count: int,
               with_head: int = 0, term: Optional[str] = None) -> float:
    return _with(3.0 * _values(model) * count, term,
                 lambda t: dense.span_flops(model, start, count, with_head, t))


def step_bytes(model: Dict[str, Any], cached_positions: int, rows: int,
               term: Optional[str] = None) -> float:
    return _with(float(_values(model) * dense.BF16_BYTES), term,
                 lambda t: dense.step_bytes(model, cached_positions, rows, t))
