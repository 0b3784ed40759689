"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests it finished (drawn from
the seed, the longest prompt among them, and a greedy one) is held against the plain float32 reference, from what the timed path
itself returned: the HTTP response, and the records of the calls into the
backend layer that served it.

Numbers compared, each with a limit of its own in the cell's file:

``matrix_gap``      widest |served - reference| mean log-probability over the
                    score-matrix cells compared: the evaluator's utilities in
                    the response (every agent) and, for best-of-N, the chosen
                    candidate and a few others under every agent.
``greedy_gap``      widest gap by which a greedily served token's logit lies
                    below the reference's best sampleable logit: every token
                    of every row of the greedy request compared.
``generated``       rows of the compared requests' generation that are not
                    ``max_tokens`` sampleable ids each, and rows missing of
                    the ``n`` asked for (limit 0): sampled rows too.
``selection``       statements that are not what the benchmark's own replay of
                    the welfare rule picks from the served numbers (0 or 1 a
                    request; the limit is 0).
``truncated``       prompts the backend cut to fit its context (limit 0).
``weights``         weight leaves whose bits differ from the reference's own
                    draw from the same seed, and leaves that only the served
                    tree or only the reference has (limit 0).

The forward is the cell's reference file (``benchmark/references/``); the
tokenizer and the prompts' rendering are common to all of them.
"""

from __future__ import annotations

import math
import random
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmark.lib import reference as ref

def _sanitize(value: float) -> float:
    if math.isnan(value):
        return -10.0
    if math.isinf(value):
        return 20.0 if value > 0 else -20.0
    return value


def egalitarian_pick(utilities: Sequence[Sequence[float]]) -> int:
    """Index of the first candidate with the largest least utility."""
    welfare = [min(_sanitize(float(u)) for u in row) for row in utilities]
    return int(np.argmax(np.asarray(welfare, np.float32)))


class Numbers:
    """The numbers compared, each the worst over what was compared."""

    def __init__(self) -> None:
        self.values: Dict[str, float] = {}
        self.compared: Dict[str, int] = {}
        self.notes: List[str] = []

    def add(self, name: str, value: float, count: int = 1) -> None:
        self.values[name] = max(self.values.get(name, 0.0), float(value))
        self.compared[name] = self.compared.get(name, 0) + count


def choose_sample(cell: Any, sent: List[Any], calls: List[Dict[str, Any]],
                  seed: int) -> List[Any]:
    """Finished requests in the order they are to be compared: the one with
    the longest body of opinions among the mix's own, a greedy one, the
    rest shuffled from the seed.  ``gather`` takes as many from the front as
    the cell's file asks for."""
    finished = [s for s in sent if s.status == 200 and isinstance(s.body, dict)
                and isinstance(s.body.get("statement"), str)]
    if not finished:
        return []
    rng = random.Random(f"check:{int(seed)}")
    # How much the agents' contexts hold, by the statement they were asked about.
    chars = {q.candidates[0]: sum(len(a.context) for a in q.agents)
             for call in calls if call["kind"] == "score_matrix"
             for q in call["requests"] if q.stat == "moments" and q.candidates}
    greedy = [s for s in finished if s.payload["params"].get("temperature") == 0.0]
    chosen = [max([s for s in finished if s not in greedy] or finished,
                  key=lambda s: chars.get(s.body["statement"], 0))]
    greedy = [s for s in greedy if s not in chosen]
    if greedy:
        chosen.append(rng.choice(greedy))
    rest = [s for s in finished if s not in chosen]
    rng.shuffle(rest)
    return chosen + rest


def _matrix_rows(request: Any, candidates: Sequence[int]) -> List[Tuple[List[int], int]]:
    rows = []
    for c in candidates:
        cont = ref.encode(request.candidates[c])
        for agent in request.agents:
            prefix = ref.encode(ref.score_prefix(
                agent.context, agent.system_prompt, agent.chat, agent.role),
                add_bos=True)
            rows.append((prefix + cont, len(cont)))
    return rows


def _own(found: List[Tuple[Any, Any, Dict[str, Any]]], sent: Any):
    """Of the recorded (request, result, call) that fit a sent request by
    what they say, the one made while it was in flight.  None where there is
    none, or where two that differ remain (two requests in flight that
    generated the same text under different scenarios): the caller compares
    another request instead."""
    mine = [f for f in found
            if f[2]["start"] >= sent.sent and f[2]["end"] <= sent.done]
    if not mine:
        return None
    first = mine[0]
    if any(f[0].agents != first[0].agents or f[0].candidates != first[0].candidates
           for f in mine[1:]):
        return None
    return first


def gather(cell: Any, ordered: List[Any], calls: List[Dict[str, Any]],
           numbers: Numbers, seed: int):
    """What to run through the reference for the first requests of
    ``ordered`` that the records place, as many as the cell's file asks for,
    and what the served side said at each: (jobs, the requests compared),
    a job being (kind, rows, served, request)."""
    rng = random.Random(f"check-rows:{int(seed)}")
    plan = cell.workload.get("check", {})
    want = int(plan.get("sample_requests", 3))
    n_extra = int(plan.get("matrix_candidates", 1)) - 1
    matrices = [(request, result, call)
                for call in calls if call["kind"] == "score_matrix"
                for request, result in zip(call["requests"], call["results"])]
    generations = [(request, result, call)
                   for call in calls if call["kind"] == "generate"
                   for request, result in zip(call["requests"], call["results"])]
    jobs, compared = [], []
    for sent in ordered:
        if len(compared) >= want:
            break
        mine = _gather_one(sent, matrices, generations, numbers, rng, n_extra)
        if mine is None:
            numbers.notes.append(f"{sent.payload['request_id']}: the records "
                                 "do not place its calls; another is compared")
            continue
        jobs.extend(mine)
        compared.append(sent)
    return jobs, compared


def _gather_one(sent, matrices, generations, numbers, rng, n_extra):
    statement = sent.body["statement"]
    method = sent.payload["method"]
    agents = list(sent.body.get("utilities", {}))
    jobs = []
    # The evaluator's matrix: this statement under every agent.
    evaluator = _own([m for m in matrices
                      if m[0].stat == "moments" and len(m[0].agents) == len(agents)
                      and tuple(m[0].candidates) == (statement,)], sent)
    if evaluator is None:
        return None
    served = [sent.body["utilities"][a]["avg_logprob"] for a in agents]
    jobs.append(("matrix", _matrix_rows(evaluator[0], [0]), served, sent))
    if method == "best_of_n":
        scored = _own([m for m in matrices
                       if m[0].stat == "mean" and len(m[0].agents) == len(agents)
                       and statement in m[0].candidates], sent)
        if scored is None:
            return None
        request, result, _ = scored
        utilities = np.asarray(result.utilities, np.float64).reshape(
            len(request.candidates), len(request.agents))
        best = egalitarian_pick(utilities)
        numbers.add("selection", 0 if request.candidates[best] == statement else 1)
        others = [c for c in range(len(request.candidates)) if c != best]
        rng.shuffle(others)
        picked = [best] + others[:n_extra]
        served = [float(utilities[c, a]) for c in picked
                  for a in range(len(request.agents))]
        jobs.append(("matrix", _matrix_rows(request, picked), served, sent))
        # The rows this request generated: seeds ``seed`` .. ``seed + n - 1``.
        params = sent.payload["params"]
        n, base = int(params["n"]), int(sent.payload["seed"])
        rows = [g for g in generations
                if g[0].seed is not None and 0 <= g[0].seed - base < n
                and g[2]["start"] >= sent.sent and g[2]["end"] <= sent.done]
        if not rows:
            return None
        wrong = n - len({g[0].seed for g in rows})
        for _, made, _ in rows:
            ids = made.token_ids
            wrong += int(len(ids) != int(params["max_tokens"])
                         or any(t >= ref.BYTE_VOCAB for t in ids))
        numbers.add("generated", wrong, len(rows))
        if params.get("temperature") == 0.0:
            # Greedy rows say the same thing unless a slot is at fault: each
            # distinct row goes through the reference once and stands for
            # all that equal it.
            distinct: Dict[Tuple[Any, ...], int] = {}
            for asked, made, _ in rows:
                render = ref.chat_prompt if asked.chat else ref.raw_prompt
                key = (render(asked.user_prompt, asked.system_prompt),
                       tuple(int(t) for t in made.token_ids))
                distinct[key] = distinct.get(key, 0) + 1
            for (prompt, ids), count in distinct.items():
                jobs.append(("greedy",
                             [(ref.encode(prompt, add_bos=True) + list(ids), len(ids))],
                             [count], sent))
    return jobs


def differing_leaves(served: Dict[str, int], own: Dict[str, int]) -> Tuple[int, int]:
    """Of two trees' ``weights_checksum``: (leaves that differ, leaves
    compared).  A leaf that one tree has and the other lacks differs."""
    paths = set(served) | set(own)
    return sum(1 for p in paths if served.get(p) != own.get(p)), len(paths)


def compare(reference: Any, cfg: Any, weights: Any, jobs: List[Any],
            numbers: Numbers, control: bool = False) -> None:
    """Run every job's rows through ``reference`` (the cell's reference
    file) and fold the gaps into ``numbers``.  With ``control`` the same
    forward in float8 takes the served side's place, and the numbers are the
    control's."""
    flat = [row for _, rows, _, _ in jobs for row in rows]
    kinds = [kind for kind, rows, _, _ in jobs for _ in rows]
    served = [None if values is None else values[r]
              for _, rows, values, _ in jobs for r in range(len(rows))]
    if not flat:
        return
    scored = reference.score_rows(cfg, weights, flat)
    if control:
        low = reference.score_rows(cfg, weights, flat, precision="fp8")
        # Greedy under the control: the reference's logit of the token the
        # lower precision puts first, at each position of the same rows.
        greedy = [i for i, kind in enumerate(kinds) if kind == "greedy"]
        first = dict(zip(greedy, reference.score_rows(cfg, weights, [
            (flat[i][0], flat[i][1], [int(t) for t in low[i].best_id])
            for i in greedy])))
    for index, (kind, got) in enumerate(zip(kinds, scored)):
        n = flat[index][1]
        if kind == "matrix":
            value = (float(np.mean(low[index].logprob)) if control
                     else float(served[index]))
            numbers.add("matrix_gap", abs(value - float(np.mean(got.logprob))))
        elif kind == "greedy":
            target = first[index].target_logit if control else got.target_logit
            numbers.add("greedy_gap", float(np.max(got.best_logit - target)),
                        n * int(served[index]))


def verdict(numbers: Numbers, limits: Dict[str, float]) -> Tuple[bool, Dict[str, Any]]:
    """``correct`` and the block printed beside it: each number compared,
    how many readings it is the worst of, and its limit.  A number that the
    cell's file gives a limit and the run did not read is not correct: a
    window that finished no greedy request has no generated token held to
    the reference."""
    block: Dict[str, Any] = {}
    ok = True
    for name, value in sorted(numbers.values.items()):
        limit = limits.get(name)
        block[name] = {"value": value, "limit": limit,
                       "compared": numbers.compared[name]}
        if limit is None or not value <= limit:
            ok = False
    for name in limits:
        if name not in numbers.values:
            block[name] = {"value": None, "limit": limits[name], "compared": 0}
            ok = False
    if numbers.notes:
        block["notes"] = numbers.notes[:5]
    return ok, block


def control_numbers(reference: Any, cfg: Any, weights: Any, jobs: List[Any],
                    numbers: Numbers) -> Numbers:
    """The numbers of the control: the float8 forward's gaps, beside the
    exact numbers of the run it was put into."""
    control = Numbers()
    for name in numbers.values:
        if not name.endswith("_gap"):
            control.add(name, numbers.values[name], numbers.compared[name])
    compare(reference, cfg, weights, jobs, control, control=True)
    return control
