"""Statement evaluation: per-agent utilities, welfare metrics, LLM judge.

Reference: ``src/evaluation.py`` (1 644 LoC; SURVEY §2.10).  Output schema
parity is exact — column names match the reference's
``evaluation_results.csv`` / ``ranking_results.csv`` so downstream
aggregation is interchangeable.  Per statement:

* cosine-similarity utilities: statement + opinion embeddings (one batched
  ``embed`` call) → per-agent cosine (reference :161-272);
* logprob utilities: the statement teacher-force-scored under an
  agent-aligned evaluation prompt (one batched ``score`` call over agents)
  → per-agent avg logprob, avg probability ``mean(exp(lp))``, perplexity
  ``exp(-avg_logprob)`` (reference :182-230, 329-335);
* welfare per utility family (reference :274-394): egalitarian = min,
  utilitarian = sum, log-Nash = ``sum(log(max(u, 1e-9)))`` — with the
  reference's convention that *egalitarian perplexity is the MAX* because
  lower perplexity is better (:366-391);
* optional LLM-judge 1-5 representation scores per agent and a comparative
  ranking across all methods' statements (reference :413-632, 636-893) via
  a pluggable judge backend (the reference hardcodes OpenAI; judge
  "o3" aliases to gpt-4.1 there, :447-462 — routing happens in the API
  backend here).

The (statements × agents) utility tensor is assembled in single batched
backend calls — the decoder-side redesign (SURVEY §2.16) applied to
evaluation.
"""

from __future__ import annotations

import json
import logging
import pathlib
import re
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import pandas as pd
import yaml

from consensus_tpu.backends.base import Backend, GenerationRequest, ScoreRequest
from consensus_tpu.utils.identifiers import create_method_identifier
from consensus_tpu.utils.io_atomic import sanitize_frame_for_csv

logger = logging.getLogger(__name__)

UTILITY_EPSILON = 1e-9

#: Agent-aligned scoring context (reference src/evaluation.py:182-193).
EVAL_SYSTEM_TEMPLATE = (
    "Issue: {issue}\n\nAgent's Opinion: {opinion}\n\n"
    "Here is a consensus statement that perfectly aligns with the agent's "
    "opinion:"
)

_JSON_RE = re.compile(r"\{.*\}", re.DOTALL)


def _welfare_triplet(utilities: np.ndarray) -> Tuple[float, float, float]:
    """(egalitarian, utilitarian, log-Nash) for higher-is-better utilities."""
    return (
        float(np.min(utilities)),
        float(np.sum(utilities)),
        float(np.sum(np.log(np.maximum(utilities, UTILITY_EPSILON)))),
    )


class StatementEvaluator:
    def __init__(
        self,
        backend: Backend,
        evaluation_model: str = "",
        judge_backend: Optional[Backend] = None,
        llm_judge_model: str = "",
        embedder: Optional[Any] = None,
        matrix_scoring: bool = True,
    ):
        self.backend = backend
        self.evaluation_model = evaluation_model
        self.judge_backend = judge_backend
        self.llm_judge_model = llm_judge_model
        #: Route the (statement x agent) utility pass through the
        #: score_matrix seam (fused on-device where available; byte-exact
        #: per-call fallback elsewhere).  False keeps the flat score batch.
        self.matrix_scoring = bool(matrix_scoring)
        # Cosine-family embeddings: a dedicated encoder when configured
        # (reference uses BAAI/bge-large-en-v1.5, src/utils.py:376-407),
        # else the generation LM's pooled hiddens (consensus_tpu.embedding).
        if embedder is None:
            from consensus_tpu.embedding import get_embedder

            embedder = get_embedder(None, backend)  # honors EVAL_EMBEDDER env
        self.embedder = embedder

    # ------------------------------------------------------------------
    # Single-statement metrics
    # ------------------------------------------------------------------

    def evaluate_statement(
        self,
        statement: str,
        issue: str,
        agent_opinions: Dict[str, str],
        include_llm_judge: bool = False,
    ) -> Dict[str, Any]:
        return self.evaluate_statements_batched(
            [statement], issue, agent_opinions, include_llm_judge
        )[0]

    def evaluate_statements_batched(
        self,
        statements: List[str],
        issue: str,
        agent_opinions: Dict[str, str],
        include_llm_judge: bool = False,
    ) -> List[Dict[str, Any]]:
        """Metrics for N statements with THREE backend batches total.

        The per-statement path made one embed + one score (+ one judge)
        call per statement — on the device backend that is hundreds of
        small (~6-row) dispatches per evaluation phase, each paying the
        dispatch floor.  Here the whole results frame ships as ONE embed batch
        (statements + each opinion ONCE), one (statement x agent) score
        batch, and one judge batch; per-row results are unchanged
        (backends chunk internally; row values are batch-independent).
        """
        agents = list(agent_opinions.items())
        n, a = len(statements), len(agents)
        if n == 0:
            return []

        # -- cosine utilities (one embed batch; opinions embedded once) ---
        vectors = self.embedder.embed(
            list(statements) + [op for _, op in agents]
        )
        stmt_vecs, opinion_vecs = vectors[:n], vectors[n:]

        # -- logprob utilities (one score batch over statements x agents) -
        moments = self._score_moments(statements, issue, agents)

        judge_scores_all: List[Optional[List[Optional[float]]]] = [None] * n
        if include_llm_judge and self.judge_backend is not None:
            judge_scores_all = self._judge_scores_batched(
                statements, issue, agents
            )

        return [
            self._assemble_metrics(
                agents,
                stmt_vecs[i],
                opinion_vecs,
                moments[i * a : (i + 1) * a],
                judge_scores_all[i],
            )
            for i in range(n)
        ]

    def _score_moments(
        self,
        statements: List[str],
        issue: str,
        agents: List[Tuple[str, str]],
    ) -> List[Tuple[float, float]]:
        """Flat (statement-major, agent-minor) list of per-cell
        ``(mean logprob, mean prob)`` in float64 — the evaluator's
        perplexity accounting.  Matrix path: ONE utility-matrix call with
        ``stat="moments"`` (utilities carry the mean logprob, ``aux`` the
        mean prob); the fallback backend reduces the identical per-call
        rows with identical float64 expressions, so metrics are
        byte-stable across the seam."""
        if self.matrix_scoring:
            from consensus_tpu.backends.score_matrix import (
                AgentContext,
                ScoreMatrixRequest,
                score_matrix_many,
            )

            result = score_matrix_many(
                self.backend,
                [
                    ScoreMatrixRequest(
                        agents=tuple(
                            AgentContext(
                                context=EVAL_SYSTEM_TEMPLATE.format(
                                    issue=issue, opinion=opinion
                                ),
                                chat=True,
                                # Reference parity: eval template in the
                                # system slot, the statement scored as
                                # user-turn content (evaluation.py:182).
                                role="user",
                            )
                            for _, opinion in agents
                        ),
                        candidates=tuple(statements),
                        stat="moments",
                    )
                ],
            )[0]
            utilities = np.asarray(result.utilities, dtype=np.float64)
            aux = np.asarray(result.aux, dtype=np.float64)
            return [
                (float(lp), float(p))
                for lp, p in zip(utilities.ravel(), aux.ravel())
            ]
        requests = [
            ScoreRequest(
                context=EVAL_SYSTEM_TEMPLATE.format(issue=issue, opinion=opinion),
                continuation=statement,
                chat=True,
                # Reference parity: eval template in the system slot, the
                # statement scored as user-turn content (evaluation.py:182).
                role="user",
            )
            for statement in statements
            for _, opinion in agents
        ]
        out = []
        for result in self.backend.score(requests):
            lps = np.asarray(result.logprobs, dtype=np.float64)
            avg_lp = float(lps.mean()) if lps.size else -10.0
            avg_p = float(np.exp(lps).mean()) if lps.size else 0.0
            out.append((avg_lp, avg_p))
        return out

    def _assemble_metrics(
        self,
        agents: List[Tuple[str, str]],
        statement_vec,
        opinion_vecs,
        moments: List[Tuple[float, float]],
        judge_scores: Optional[List[Optional[float]]],
    ) -> Dict[str, Any]:
        """Metric-column assembly from precomputed ``(avg_logprob,
        avg_prob)`` moments (shared by the single and batched paths —
        column names/semantics pinned by the golden run dir)."""
        metrics: Dict[str, Any] = {}
        cosines = opinion_vecs @ statement_vec  # embeddings are unit-norm
        for (name, _), cos in zip(agents, cosines):
            metrics[f"cosine_similarity_{name}"] = float(cos)
            metrics[f"utility_cosine_similarity_{name}"] = float(cos)

        avg_logprobs, avg_probs, perplexities = [], [], []
        for (name, _), (avg_lp, avg_p) in zip(agents, moments):
            ppl = float(np.exp(-avg_lp))
            avg_logprobs.append(avg_lp)
            avg_probs.append(avg_p)
            perplexities.append(ppl)
            metrics[f"avg_logprob_{name}"] = avg_lp
            metrics[f"utility_avg_logprob_{name}"] = avg_lp
            metrics[f"perplexity_{name}"] = ppl

        # -- welfare blocks ------------------------------------------------
        egal, util, nash = _welfare_triplet(np.asarray(cosines))
        metrics["egalitarian_welfare_cosine"] = egal
        metrics["utility_egalitarian_welfare_cosine"] = egal
        metrics["utilitarian_welfare_cosine"] = util
        metrics["utility_utilitarian_welfare_cosine"] = util
        metrics["log_nash_welfare_cosine"] = nash
        metrics["utility_log_nash_welfare_cosine"] = nash

        egal, util, nash = _welfare_triplet(np.asarray(avg_probs))
        metrics["egalitarian_welfare_avg_prob"] = egal
        metrics["utility_egalitarian_welfare_logprob"] = egal
        metrics["utilitarian_welfare_avg_prob"] = util
        metrics["utility_utilitarian_welfare_logprob"] = util
        metrics["log_nash_welfare_avg_prob"] = nash
        metrics["utility_log_nash_welfare_logprob"] = nash

        ppl_arr = np.asarray(perplexities)
        # Egalitarian perplexity = MAX: the worst-off agent has the highest
        # perplexity (reference :366-391).
        metrics["egalitarian_welfare_perplexity"] = float(np.max(ppl_arr))
        metrics["utilitarian_welfare_perplexity"] = float(np.sum(ppl_arr))
        metrics["log_nash_welfare_perplexity"] = float(
            np.sum(np.log(np.maximum(ppl_arr, UTILITY_EPSILON)))
        )

        # -- optional LLM-judge representation scores ----------------------
        if judge_scores is not None:
            for (name, _), score in zip(agents, judge_scores):
                metrics[f"judge_score_{name}"] = score
            valid = np.asarray([s for s in judge_scores if s is not None])
            if valid.size:
                egal, util, nash = _welfare_triplet(valid)
                metrics["egalitarian_welfare_judge_score"] = egal
                metrics["utilitarian_welfare_judge_score"] = util
                metrics["log_nash_welfare_judge_score"] = nash

        return metrics

    def _judge_scores_batched(
        self, statements: List[str], issue: str, agents: List[Tuple[str, str]]
    ) -> List[List[Optional[float]]]:
        """1-5 representation score per (statement, agent), JSON-mode judge
        calls (reference :413-579) — ONE batched generate over the whole
        (statement x agent) grid."""
        requests = [
            GenerationRequest(
                user_prompt=(
                    "You are an impartial judge. Rate on a 1-5 scale how well "
                    "the consensus statement represents this participant's "
                    "opinion. Respond in JSON: "
                    '{"representation score": <1-5>, "explanation": "..."}\n\n'
                    f"Issue: {issue}\n\nParticipant's opinion: {opinion}\n\n"
                    f"Consensus statement: {statement}"
                ),
                max_tokens=300,
                temperature=0.0,
                chat=True,
            )
            for statement in statements
            for _, opinion in agents
        ]
        results = self.judge_backend.generate(requests)
        scores: List[Optional[float]] = []
        for result in results:
            payload = _extract_json(result.text) if result.ok else None
            score = payload.get("representation score") if payload else None
            try:
                score = float(score)
                scores.append(score if 1.0 <= score <= 5.0 else None)
            except (TypeError, ValueError):
                scores.append(None)
        a = len(agents)
        return [scores[i * a : (i + 1) * a] for i in range(len(statements))]

    # ------------------------------------------------------------------
    # Comparative ranking across methods (one judge call per agent)
    # ------------------------------------------------------------------

    def evaluate_comparative_rankings(
        self,
        method_statements: Dict[str, str],
        issue: str,
        agent_opinions: Dict[str, str],
        seed: Optional[int] = None,
    ) -> Tuple[pd.DataFrame, pd.DataFrame, Dict[str, Any]]:
        """Rank every method's statement from each agent's perspective.

        Returns (ranking_results, ranking_reasoning, matrix) mirroring the
        reference's three artifacts (run_experiment_with_eval.py:297-320):
        per-method rank stats incl. ``is_maximin_best`` (method minimizing
        its worst-case rank, reference src/evaluation.py:861-876) and
        ``is_utilitarian_best`` (lowest average rank, :878-891).
        """
        if self.judge_backend is None:
            raise ValueError("evaluate_comparative_rankings needs a judge backend")
        methods = list(method_statements)
        agents = list(agent_opinions.items())
        start = time.perf_counter()

        numbered = "\n".join(
            f"{i + 1}. [{m}] {method_statements[m]}" for i, m in enumerate(methods)
        )
        requests = [
            GenerationRequest(
                user_prompt=(
                    "You are an impartial judge. Rank ALL the candidate "
                    "consensus statements below by how well each represents "
                    "this participant's opinion (rank 1 = best). Respond in "
                    'JSON: {"reasoning": "...", "ranking": [<statement '
                    "numbers, best first>], \"method_ranking\": "
                    '{"<method>": <rank>, ...}} using every statement and '
                    "method exactly once.\n\n"
                    f"Issue: {issue}\n\nParticipant's opinion: {opinion}\n\n"
                    f"Candidate statements:\n{numbered}"
                ),
                max_tokens=1000,
                temperature=0.0,
                seed=seed,
                chat=True,
            )
            for _, opinion in agents
        ]
        responses = self.judge_backend.generate(requests)

        rank_matrix: Dict[str, Dict[str, Optional[int]]] = {m: {} for m in methods}
        reasoning_rows = []
        for (agent_name, _), response in zip(agents, responses):
            payload = _extract_json(response.text) if response.ok else None
            ranking = (payload or {}).get("method_ranking") or {}
            if len(ranking) != len(methods):
                # Reconstruction fallback (reference src/evaluation.py:
                # 769-801): small local judges often emit a usable raw
                # ``ranking`` array (statement numbers, best first, matching
                # the prompt's 1-indexed numbering) even when the
                # method-name map is missing or truncated.
                ranking = _reconstruct_method_ranking(
                    (payload or {}).get("ranking"), methods
                ) or ranking
            reasoning_rows.append(
                {
                    "agent": agent_name,
                    "reasoning": (payload or {}).get("reasoning", ""),
                    "raw_response": response.text,
                }
            )
            for method in methods:
                value = ranking.get(method)
                try:
                    rank_matrix[method][agent_name] = int(value)
                except (TypeError, ValueError):
                    rank_matrix[method][agent_name] = None

        from consensus_tpu.utils.identifiers import parse_method_identifier

        rows = []
        for method in methods:
            base, params, _ = parse_method_identifier(method)
            ranks = [r for r in rank_matrix[method].values() if r is not None]
            row: Dict[str, Any] = {
                "method": base,
                "seed": seed,
                "method_with_params": method,
                **{f"param_{k}": v for k, v in params.items()},
                "min_rank": min(ranks) if ranks else None,
                "max_rank": max(ranks) if ranks else None,
                "avg_rank": float(np.mean(ranks)) if ranks else None,
            }
            for agent_name, _ in agents:
                row[f"rank_{agent_name}"] = rank_matrix[method][agent_name]
            rows.append(row)
        frame = pd.DataFrame(rows)

        if frame["max_rank"].notna().any():
            best_max = frame["max_rank"].min()
            frame["is_maximin_best"] = (frame["max_rank"] == best_max).astype(int)
        else:
            frame["is_maximin_best"] = 0
        if frame["avg_rank"].notna().any():
            best_avg = frame["avg_rank"].min()
            frame["is_utilitarian_best"] = (frame["avg_rank"] == best_avg).astype(int)
        else:
            frame["is_utilitarian_best"] = 0

        matrix = {
            "methods": methods,
            "agents": [name for name, _ in agents],
            "ranks": {m: rank_matrix[m] for m in methods},
            "comparative_ranking_time_s": round(time.perf_counter() - start, 3),
        }
        return frame, pd.DataFrame(reasoning_rows), matrix

    # ------------------------------------------------------------------
    # Results-file driver
    # ------------------------------------------------------------------

    def evaluate_results_frame(
        self,
        results: pd.DataFrame,
        issue: str,
        agent_opinions: Dict[str, str],
        include_llm_judge: bool = False,
    ) -> pd.DataFrame:
        """Evaluate every statement row of a generation results frame
        (reference evaluate_statements, :895-1019) — all rows through the
        BATCHED evaluator (three backend batches for the whole frame
        instead of 2-3 small dispatches per statement)."""
        kept: List[Tuple[Any, pd.Series, Dict[str, Any], str]] = []
        for index, row in results.iterrows():
            statement = row.get("statement", "")
            if not isinstance(statement, str) or not statement.strip():
                continue
            # Error-sentinel statements are excluded like the reference's
            # 'statement != "ERROR"' filters (src/evaluation.py:665, :1112).
            if statement.lstrip().startswith("[ERROR"):
                continue
            error = row.get("error_message")
            if not pd.isna(error) and str(error).strip():
                continue
            params = {
                k: row[k]
                for k in results.columns
                if k.startswith("param_") and pd.notna(row[k])
            }
            method_key = create_method_identifier(
                row["method"], params, include_seed=True, seed_value=row.get("seed")
            )
            kept.append((index, row, params, method_key))

        start = time.perf_counter()
        all_metrics = self.evaluate_statements_batched(
            [row["statement"] for _, row, _, _ in kept],
            issue,
            agent_opinions,
            include_llm_judge,
        )
        # Per-row time is the amortized batch wall (the batch IS the unit
        # of work now; the old per-statement stopwatch would double-count).
        per_row_s = round((time.perf_counter() - start) / max(len(kept), 1), 3)

        rows = []
        for (index, row, params, method_key), metrics in zip(kept, all_metrics):
            out_row: Dict[str, Any] = {
                "method": row["method"],
                "issue": issue,
                "statement": row["statement"],
                "method_with_params": method_key,
                "seed": row.get("seed"),
                "original_row_index": index,
                "evaluation_time_s": per_row_s,
            }
            for k in params:
                out_row[k] = params[k]
            out_row.update(metrics)
            rows.append(out_row)
        return pd.DataFrame(rows)

    def evaluate_results_file(
        self,
        results_csv: str,
        config: Optional[Dict[str, Any]] = None,
        output_dir: Optional[str] = None,
        include_llm_judge: bool = False,
    ) -> Dict[int, pd.DataFrame]:
        """Per-seed evaluation of a run directory's results.csv, writing
        ``evaluation/<model>/seed_N/evaluation_results.csv`` +
        ``evaluation_config.yaml`` (reference :1072-1428)."""
        results_path = pathlib.Path(results_csv)
        run_dir = results_path.parent
        if config is None:
            with open(run_dir / "config.yaml") as fh:
                config = yaml.safe_load(fh)
        scenario = config.get("scenario", {})
        issue = scenario.get("issue", "")
        agent_opinions = dict(scenario.get("agent_opinions", {}))

        results = pd.read_csv(results_csv)
        model_dir = sanitize_model_name(self.evaluation_model or "model")
        base = pathlib.Path(output_dir) if output_dir else run_dir / "evaluation"

        frames: Dict[int, pd.DataFrame] = {}
        for seed_index, seed in enumerate(sorted(results["seed"].unique())):
            subset = results[results["seed"] == seed]
            frame = self.evaluate_results_frame(
                subset, issue, agent_opinions, include_llm_judge
            )
            seed_dir = base / model_dir / f"seed_{seed_index}"
            seed_dir.mkdir(parents=True, exist_ok=True)
            sanitize_frame_for_csv(frame).to_csv(
                seed_dir / "evaluation_results.csv", index=False)
            with open(seed_dir / "evaluation_config.yaml", "w") as fh:
                yaml.safe_dump(
                    {
                        "evaluation_model": self.evaluation_model,
                        "seed": int(seed),
                        "include_llm_judge": include_llm_judge,
                    },
                    fh,
                )
            frames[int(seed)] = frame
        return frames


def sanitize_model_name(model: str) -> str:
    """Model id → directory name (reference uses '/'→'_')."""
    return model.replace("/", "_")


def _extract_json(text: str) -> Optional[Dict[str, Any]]:
    """Pull the first JSON object out of a judge response."""
    if not text:
        return None
    match = _JSON_RE.search(text)
    if not match:
        return None
    try:
        return json.loads(match.group(0))
    except json.JSONDecodeError:
        return None


def _reconstruct_method_ranking(
    raw_ranking: Any, methods: List[str]
) -> Optional[Dict[str, int]]:
    """Recover a method->rank map from the judge's raw ``ranking`` array
    (reference src/evaluation.py:769-801).

    The array lists statement numbers best-first, 1-indexed by the
    prompt's numbering, which follows ``methods`` order; position i (also
    1-indexed) is the rank.  Returns None unless the array has exactly one
    entry per method and every entry maps to a distinct method — a partial
    reconstruction is worse than an honest None (it would skew the
    min/max/avg rank columns).
    """
    if not isinstance(raw_ranking, (list, tuple)):
        return None
    if len(raw_ranking) != len(methods):
        return None
    reconstructed: Dict[str, int] = {}
    for rank, stmt_num in enumerate(raw_ranking, 1):
        try:
            idx = int(stmt_num) - 1
        except (TypeError, ValueError):
            return None
        if not 0 <= idx < len(methods):
            return None
        reconstructed[methods[idx]] = rank
    if len(reconstructed) != len(methods):
        return None
    return reconstructed
