"""Useful work of a stretch of the run, from the records of the calls into
the backend layer and nothing the implementation did: tokens by kind, the
FLOPs they need, and the bytes the decode launches must read, as the cell's
work file (``benchmark/work/<name>.py``) counts them."""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmark.lib import reference as ref


class Lengths:
    """Token counts of texts, each text tokenized once."""

    def __init__(self) -> None:
        self._cache: Dict[Tuple[str, bool], int] = {}

    def __call__(self, text: str, add_bos: bool = False) -> int:
        key = (text, add_bos)
        if key not in self._cache:
            self._cache[key] = len(ref.encode(text, add_bos))
        return self._cache[key]


def overlap(start: float, end: float, lo: float, hi: float) -> float:
    """Share of [start, end] that lies in [lo, hi]."""
    if end <= start:
        return 1.0 if lo <= start <= hi else 0.0
    return max(0.0, min(end, hi) - max(start, lo)) / (end - start)


#: Request seeds lie some 1e8 apart; the rows of one statement are
#: ``seed`` .. ``seed + n - 1``.
_SEED_RUN = 4096


def _statements(calls: List[Dict[str, Any]]):
    """The rows of the ``generate`` calls, by the statement they serve: the
    same prompt under one run of request seeds, whatever the number of calls
    the program made of them.  Yields (prompt, [(call, tokens made), ...])."""
    by_prompt: Dict[str, List[Tuple[int, Dict[str, Any], int]]] = {}
    for call in calls:
        if call["kind"] != "generate":
            continue
        for request, result in zip(call["requests"], call["results"]):
            render = ref.chat_prompt if request.chat else ref.raw_prompt
            text = render(request.user_prompt, request.system_prompt)
            by_prompt.setdefault(text, []).append(
                (request.seed or 0, call, len(result.token_ids)))
    for text, rows in by_prompt.items():
        rows.sort(key=lambda row: row[0])
        group = [rows[0]]
        for row in rows[1:]:
            if row[0] - group[-1][0] > _SEED_RUN:
                yield text, [(call, made) for _, call, made in group]
                group = []
            group.append(row)
        yield text, [(call, made) for _, call, made in group]


def tally(work: Any, model: Dict[str, Any], calls: List[Dict[str, Any]],
          lo: float, hi: float, lengths: "Lengths | None" = None,
          term: Optional[str] = None) -> Dict[str, Dict[str, float]]:
    """By kind of call (``generate``, ``score_matrix``, ``embed``): launches,
    useful tokens, FLOPs and (for generation) the bytes the decode steps must
    read, each call counted by the share of its span inside [lo, hi].
    ``work`` is the cell's work file; with ``term`` the FLOPs and bytes are
    that term's alone (one of ``work.TERMS``).

    The work is what a statement needs, not what the program made of it: a
    statement's prompt is prefilled once and every decode step reads the
    weights once for all of its rows, into however many calls the program
    split them; a call carries its rows' share of its statement's work."""
    n = lengths or Lengths()
    out: Dict[str, Dict[str, float]] = {}

    def add(kind: str, share: float, tokens: float, flops: float,
            bytes_: float = 0.0, launches: float = 1.0) -> None:
        entry = out.setdefault(
            kind, {"launches": 0.0, "tokens": 0.0, "flops": 0.0, "bytes": 0.0})
        entry["launches"] += share * launches
        entry["tokens"] += share * tokens
        entry["flops"] += share * flops
        entry["bytes"] += share * bytes_

    for text, rows in _statements(calls):
        p = n(text, True)
        counts = [made for _, made in rows]
        tokens = p + sum(counts)
        flops = work.span_flops(model, 0, p, 1, term=term) + sum(
            work.span_flops(model, p, t, t, term=term) for t in counts)
        bytes_ = float(work.weight_bytes(model, term=term))
        for step in range(1, max(counts) + 1):
            cached = p + sum(min(t, step - 1) for t in counts)
            decoding = sum(1 for t in counts if t >= step)
            bytes_ += work.step_bytes(model, cached, decoding, term=term)
        for call, _ in rows:
            part = overlap(call["start"], call["end"], lo, hi) / len(rows)
            if part:
                add("generate", part, tokens, flops, bytes_, launches=0.0)
    for call in calls:
        kind = call["kind"]
        share = overlap(call["start"], call["end"], lo, hi)
        if not share:
            continue
        if kind == "generate":
            add(kind, share, 0, 0.0)  # the launch; its work is counted above
        elif kind == "score_matrix":
            tokens, flops = 0, 0.0
            for request in call["requests"]:
                prefixes = [ref.score_prefix(a.context, a.system_prompt, a.chat, a.role)
                            for a in request.agents]
                for text in set(prefixes):
                    tokens += n(text, True)
                    flops += work.span_flops(model, 0, n(text, True), term=term)
                for candidate in request.candidates:
                    c = n(candidate)
                    for text in prefixes:
                        tokens += c
                        flops += work.span_flops(model, n(text, True), c, c,
                                                 term=term)
            add(kind, share, tokens, flops)
        elif kind == "embed":
            prompts = [n(text, True) for text in call["requests"]]
            add(kind, share, sum(prompts),
                sum(work.span_flops(model, 0, p, term=term) for p in prompts))
    return out


def matrix_sizes(calls: List[Dict[str, Any]],
                 lengths: "Lengths | None" = None) -> List[List[Any]]:
    """The distinct score matrices of the records, as [stat, candidates,
    agents, longest candidate in tokens, each agent's prefix in tokens]: what
    the generated text made of each request's sizes."""
    n = lengths or Lengths()
    out: List[List[Any]] = []
    for call in calls:
        if call["kind"] != "score_matrix":
            continue
        for request in call["requests"]:
            prefixes = [n(ref.score_prefix(a.context, a.system_prompt, a.chat, a.role),
                          True) for a in request.agents]
            entry = [request.stat, len(request.candidates), len(request.agents),
                     max((n(c) for c in request.candidates), default=0), prefixes]
            if entry not in out:
                out.append(entry)
    return out
