"""Token-search sessions: stateful propose-and-score for token-level decoders.

A session fixes the search context once — the reference-policy prompt and
the per-agent prompts (same model, different prefix: SURVEY §0) — and then
serves the decoders' per-step primitive:

    propose k next tokens per active slot from the reference policy, and
    score every proposal under every agent policy.

Two implementations:

* :class:`PrefixTokenSearchSession` — backend-agnostic fallback.  Each step
  re-submits full prefixes through ``Backend.next_token_logprobs`` +
  ``Backend.score`` (exactly round 1's beam-search data flow; works on
  fake/API backends).  O(T^2) total model work.
* :class:`TPUTokenSearchSession` (constructed by
  ``TPUBackend.open_token_search``) — persistent per-(slot x role) KV caches
  on device; each step is ONE fused program (models/stepper.py).  O(T).

Semantics note: the fallback re-tokenizes ``prompt + sequence_string`` every
step (the reference's behavior — its "sequence" is a string of API token
strings, beam_search.py:433-435), while the TPU session appends token *ids*
to persistent caches — the true token-level-MDP state.  The two coincide
except when a tokenizer would merge a sequence boundary on re-encoding.
"""

from __future__ import annotations

import dataclasses
from typing import List, NamedTuple, Optional, Sequence, Tuple

from consensus_tpu.backends.base import (
    BAN_BIAS,
    NextTokenRequest,
    ScoreRequest,
)


class FusedSessionUnavailable(Exception):
    """A backend's fused session implementation declined this spec (e.g. the
    KV caches would not fit in device memory) — use the generic fallback."""


class ScoredCandidate(NamedTuple):
    token: str
    token_id: int
    ref_logprob: float  # proposal logprob under the reference policy
    agent_logprobs: Tuple[float, ...]  # one per agent, search-order


@dataclasses.dataclass(frozen=True)
class SearchSpec:
    """Immutable description of one token search."""

    ref_system: Optional[str]
    ref_user: str
    agent_prompts: Tuple[Tuple[Optional[str], str], ...]  # (system, user) per agent
    n_slots: int
    k: int
    temperature: float = 1.0
    seed: Optional[int] = None
    sample: bool = True  # Gumbel-top-k vs deterministic top-k proposals
    bias_against_tokens: Tuple[str, ...] = ()
    bias_value: float = BAN_BIAS
    max_steps: int = 64
    failure_logprob: float = -10.0  # substituted when a backend scores nothing
    #: Speculative rollout verification (Leviathan et al.): an n-gram
    #: self-draft proposer emits ``spec_draft_len`` suffix tokens per leaf
    #: and the target model verifies the whole draft in one parallel
    #: forward (models/stepper.rollout_verify_many), with standard
    #: rejection keeping token streams byte-identical to the sequential
    #: scan.  TPU fused sessions only — the full-prefix fallback's rollout
    #: is already ONE batched generate call, so speculation is accepted
    #: and ignored there (trivially byte-identical).
    speculative: bool = False
    spec_draft_len: int = 8
    #: Route the fallback session's (prefix x candidate x agent) scoring
    #: through the utility-matrix seam (backends/score_matrix.py): fused
    #: on-device on backends that implement ``score_matrix``, byte-identical
    #: batched per-call fallback elsewhere.  Off restores the flat
    #: per-cell ``Backend.score`` batches.
    matrix_scoring: bool = True


class PrefixTokenSearchSession:
    """Fallback session: full-prefix batched calls per step (any backend)."""

    def __init__(self, backend, spec: SearchSpec):
        self.backend = backend
        self.spec = spec
        self._sequences = [""] * spec.n_slots
        self._step = 0
        #: Backend protocol calls actually submitted (the fallback's unit of
        #: host->device round trips).  Decoders read the delta per statement.
        self.dispatch_count = 0

    # -- protocol ------------------------------------------------------------

    def propose(self) -> List[List[ScoredCandidate]]:
        """Root proposals (every slot starts with the empty sequence)."""
        return self._propose_and_score()

    def close(self) -> None:
        """No device state to release in the full-prefix fallback."""

    def advance_and_propose(
        self, parents: Sequence[int], chosen: Sequence[ScoredCandidate]
    ) -> List[List[ScoredCandidate]]:
        """Advance slot i to ``parents[i]``'s sequence + ``chosen[i]``, then
        propose and score for the new state of every slot."""
        spec = self.spec
        if len(parents) != spec.n_slots or len(chosen) != spec.n_slots:
            raise ValueError(
                f"expected {spec.n_slots} (parent, token) pairs, got "
                f"{len(parents)}/{len(chosen)}"
            )
        self._sequences = [
            self._sequences[parent] + cand.token
            for parent, cand in zip(parents, chosen)
        ]
        self._step += 1
        return self._propose_and_score()

    def propose_suffixes(
        self, suffixes: Sequence[Sequence[ScoredCandidate]], salt: int
    ) -> List[List[ScoredCandidate]]:
        """Propose + score k candidates for each tree path hanging off the
        trunk (slot 0's sequence).  Full-prefix fallback: one batched
        next-token call over all paths plus one batched score call over
        (path x candidate x agent)."""
        spec = self.spec
        if spec.n_slots != 1:
            raise ValueError("propose_suffixes requires an n_slots=1 session")
        if not suffixes:
            return []
        trunk = self._sequences[0]
        prefixes = [
            trunk + "".join(c.token for c in suffix) for suffix in suffixes
        ]
        return self._proposals_for(prefixes, family=1, index=salt)

    def rollout_from(
        self, suffix: Sequence[ScoredCandidate], depth: int, salt: int
    ) -> Tuple[List[int], str, List[float], bool]:
        """Continue ``depth`` reference-policy tokens past trunk+suffix and
        return (rollout token ids, rollout text, per-agent total logprob of
        the rollout tokens, ok).  Delegates to :meth:`rollout_many` — one
        generate call + one batched score call either way, so results are
        bit-identical to the historical single-path implementation."""
        return self.rollout_many([suffix], depth, [salt])[0]

    def rollout_many(
        self,
        suffixes: Sequence[Sequence[ScoredCandidate]],
        depth: int,
        salts: Sequence[int],
    ) -> List[Tuple[List[int], str, List[float], bool]]:
        """Batched :meth:`rollout_from`: ONE generate call over all paths and
        ONE score call over (path x agent).  Row i uses ``salts[i]`` in the
        family-2 seed map, so each row's result is bit-identical to a
        sequential ``rollout_from(suffixes[i], depth, salts[i])`` call."""
        from consensus_tpu.backends.base import GenerationRequest

        spec = self.spec
        if spec.n_slots != 1:
            raise ValueError("rollout_many requires an n_slots=1 session")
        if len(salts) != len(suffixes):
            raise ValueError(
                f"expected {len(suffixes)} salts, got {len(salts)}"
            )
        if not suffixes:
            return []
        trunk = self._sequences[0]
        prefixes = [
            trunk + "".join(c.token for c in suffix) for suffix in suffixes
        ]
        seed = spec.seed
        results = self.backend.generate(
            [
                GenerationRequest(
                    user_prompt=spec.ref_user + prefix,
                    system_prompt=spec.ref_system,
                    max_tokens=depth,
                    temperature=spec.temperature,
                    # Family 2 = rollouts (0 = trunk steps, 1 = suffix
                    # proposals) in the injective (seed, family, index, row)
                    # seed map of _proposals_for.  The salt is the row-unique
                    # coordinate here, so batching preserves per-path streams.
                    seed=((seed * 3 + 2) * 1_000_000_000 + salt * 1000)
                    if seed is not None
                    else None,
                    chat=False,
                )
                for prefix, salt in zip(prefixes, salts)
            ]
        )
        self.dispatch_count += 1
        n_agents = len(spec.agent_prompts)
        if getattr(spec, "matrix_scoring", True):
            return self._rollout_totals_matrix(prefixes, results, n_agents)
        score_requests: List[ScoreRequest] = []
        starts: List[Optional[int]] = []
        for prefix, result in zip(prefixes, results):
            if result.ok and result.text:
                starts.append(len(score_requests))
                for a_system, a_user in spec.agent_prompts:
                    score_requests.append(
                        ScoreRequest(
                            context=a_user + prefix,
                            continuation=result.text,
                            system_prompt=a_system,
                            chat=False,
                        )
                    )
            else:
                starts.append(None)
        scores = self.backend.score(score_requests) if score_requests else []
        if score_requests:
            self.dispatch_count += 1
        out: List[Tuple[List[int], str, List[float], bool]] = []
        for result, start in zip(results, starts):
            if not result.ok:
                out.append(([], "", [], False))
            elif not result.text:
                out.append(([], "", [0.0] * n_agents, True))
            else:
                row = scores[start : start + n_agents]
                totals = [
                    (sum(s.logprobs) if s.ok else spec.failure_logprob)
                    for s in row
                ]
                out.append((list(result.token_ids), result.text, totals, True))
        return out

    def _rollout_totals_matrix(
        self, prefixes, results, n_agents: int
    ) -> List[Tuple[List[int], str, List[float], bool]]:
        """Rollout returns via the utility-matrix seam: one (1 x agents)
        matrix per successful rollout, all submitted in ONE backend call —
        the same dispatch count as the flat score batch it replaces, and
        byte-identical values over the per-call fallback (stat "sum" is
        the sequential Python sum the flat path used)."""
        from consensus_tpu.backends.score_matrix import (
            AgentContext,
            ScoreMatrixRequest,
            score_matrix_many,
        )

        spec = self.spec
        matrix_requests: List[ScoreMatrixRequest] = []
        rows: List[Optional[int]] = []
        for prefix, result in zip(prefixes, results):
            if result.ok and result.text:
                rows.append(len(matrix_requests))
                matrix_requests.append(
                    ScoreMatrixRequest(
                        agents=tuple(
                            AgentContext(
                                context=a_user + prefix,
                                system_prompt=a_system,
                                chat=False,
                            )
                            for a_system, a_user in spec.agent_prompts
                        ),
                        candidates=(result.text,),
                        stat="sum",
                        default=spec.failure_logprob,
                    )
                )
            else:
                rows.append(None)
        matrices = None
        if matrix_requests and n_agents:
            matrices = score_matrix_many(self.backend, matrix_requests)
            self.dispatch_count += 1
        out: List[Tuple[List[int], str, List[float], bool]] = []
        for result, row in zip(results, rows):
            if not result.ok:
                out.append(([], "", [], False))
            elif not result.text:
                out.append(([], "", [0.0] * n_agents, True))
            else:
                totals = (
                    [float(v) for v in matrices[row].utilities[0]]
                    if matrices is not None
                    else []
                )
                out.append((list(result.token_ids), result.text, totals, True))
        return out

    # -- internals -----------------------------------------------------------

    def _proposals_for(
        self, prefixes: Sequence[str], family: int, index: int
    ) -> List[List[ScoredCandidate]]:
        """One batched next-token call over ``prefixes`` + one batched score
        call over (prefix x candidate x agent).  ``(seed, family, index,
        row)`` tuples map injectively onto request seeds (index < 1e6 —
        generous for salts/steps; row < 1000 — far above any path fan-out),
        so no two seeded requests across a seed sweep ever collide."""
        spec = self.spec
        seed = spec.seed
        if not (0 <= index < 1_000_000 and len(prefixes) <= 1000):
            raise ValueError(
                f"seed-map bounds exceeded: index={index}, rows={len(prefixes)}"
            )
        requests = [
            NextTokenRequest(
                user_prompt=spec.ref_user + prefix,
                system_prompt=spec.ref_system,
                k=spec.k,
                temperature=spec.temperature,
                seed=(
                    (seed * 3 + family) * 1_000_000_000 + index * 1000 + row
                )
                if seed is not None
                else None,
                mode="sample" if spec.sample else "topk",
                bias_against_tokens=spec.bias_against_tokens,
                bias_value=spec.bias_value,
                chat=False,
            )
            for row, prefix in enumerate(prefixes)
        ]
        proposals = self.backend.next_token_logprobs(requests)
        self.dispatch_count += 1
        if getattr(spec, "matrix_scoring", True):
            return self._score_proposals_matrix(prefixes, proposals)

        score_requests = []
        for prefix, candidates in zip(prefixes, proposals):
            for candidate in candidates:
                for a_system, a_user in spec.agent_prompts:
                    score_requests.append(
                        ScoreRequest(
                            context=a_user + prefix,
                            continuation=candidate.token,
                            system_prompt=a_system,
                            chat=False,
                        )
                    )
        scores = self.backend.score(score_requests)
        if score_requests:
            self.dispatch_count += 1
        return self._zip_scores(proposals, scores)

    def _score_proposals_matrix(
        self, prefixes: Sequence[str], proposals
    ) -> List[List[ScoredCandidate]]:
        """Proposal scoring via the utility-matrix seam: one
        (candidates x agents) matrix per prefix — same cells, same order,
        ONE backend call for all prefixes (matching the flat batch's
        dispatch count).  Stat "last" is the per-call path's
        ``logprobs[-1]`` exactly, so fallback values are byte-identical."""
        from consensus_tpu.backends.score_matrix import (
            AgentContext,
            ScoreMatrixRequest,
            score_matrix_many,
        )

        spec = self.spec
        n_agents = len(spec.agent_prompts)
        matrix_requests = [
            ScoreMatrixRequest(
                agents=tuple(
                    AgentContext(
                        context=a_user + prefix,
                        system_prompt=a_system,
                        chat=False,
                    )
                    for a_system, a_user in spec.agent_prompts
                ),
                candidates=tuple(c.token for c in candidates),
                stat="last",
                default=spec.failure_logprob,
            )
            for prefix, candidates in zip(prefixes, proposals)
        ]
        total_cells = sum(len(c) for c in proposals) * n_agents
        matrices = None
        if total_cells:
            matrices = score_matrix_many(self.backend, matrix_requests)
            self.dispatch_count += 1
        out: List[List[ScoredCandidate]] = []
        for i, candidates in enumerate(proposals):
            slot_out = []
            for ci, candidate in enumerate(candidates):
                agent_lps = (
                    tuple(float(v) for v in matrices[i].utilities[ci])
                    if matrices is not None
                    else ()
                )
                slot_out.append(
                    ScoredCandidate(
                        token=candidate.token,
                        token_id=candidate.token_id,
                        ref_logprob=candidate.logprob,
                        agent_logprobs=agent_lps,
                    )
                )
            out.append(slot_out)
        return out

    def _propose_and_score(self) -> List[List[ScoredCandidate]]:
        # Seed family 0: trunk/beam steps (family 1 = suffix trees) — the
        # families must stay disjoint or a suffix level whose salt equals a
        # later trunk step would replay its exact proposal requests.
        return self._proposals_for(
            self._sequences, family=0, index=self._step
        )

    def _zip_scores(self, proposals, scores) -> List[List[ScoredCandidate]]:
        spec = self.spec
        n_agents = len(spec.agent_prompts)
        out: List[List[ScoredCandidate]] = []
        flat = 0
        for candidates in proposals:
            slot_out = []
            for candidate in candidates:
                agent_lps = tuple(
                    (s.logprobs[-1] if s.ok else spec.failure_logprob)
                    for s in scores[flat : flat + n_agents]
                )
                flat += n_agents
                slot_out.append(
                    ScoredCandidate(
                        token=candidate.token,
                        token_id=candidate.token_id,
                        ref_logprob=candidate.logprob,
                        agent_logprobs=agent_lps,
                    )
                )
            out.append(slot_out)
        return out


def open_token_search(backend, spec: SearchSpec):
    """Session factory: a backend offering ``open_fused_token_search`` (TPU,
    or the batching wrapper delegating to its inner TPU backend) gets first
    refusal; on :class:`FusedSessionUnavailable` — or with no fused
    implementation at all — the full-prefix fallback runs over ``backend``
    ITSELF, so e.g. a batching wrapper keeps merging the fallback's calls
    through its queue."""
    from consensus_tpu.obs.metrics import get_registry

    opened = get_registry().counter(
        "token_search_sessions_total",
        "Token-search sessions opened, by implementation: fused (persistent "
        "device caches, one program per step) or prefix (the full-prefix "
        "fallback, taken when a backend has no fused session or declines).",
        labels=("kind",),
    )
    maker = getattr(backend, "open_fused_token_search", None)
    if maker is not None:
        try:
            session = maker(spec)
            opened.labels("fused").inc()
            return session
        except FusedSessionUnavailable:
            pass
    opened.labels("prefix").inc()
    session = PrefixTokenSearchSession(backend, spec)
    # Continuous-batching seam: over an engine-mode batching adapter the
    # fallback's per-step calls already land in the engine's iteration loop
    # as (prefill, decode-step, score) slot operations; registering the
    # session here additionally surfaces its slot footprint in the engine's
    # pressure stats (/healthz), same as fused sessions.
    engine = getattr(backend, "engine", None)
    if engine is not None and hasattr(engine, "track_session"):
        session = engine.track_session(session, spec)
    return session
