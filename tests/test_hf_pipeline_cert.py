"""Whole-pipeline cross-certification against HuggingFace ``transformers``.

``tests/test_hf_numerics.py`` certifies raw forwards/decodes; VERDICT r3 #2
asks for the next link: identical weights through BOTH full stacks — a
torch ``Gemma2ForCausalLM`` reference backend and this runtime — driving
the same best_of_n cell greedily, asserting the chosen STATEMENTS are
byte-identical and every evaluation metric column agrees within tolerance.
With this link tested, quality parity reduces to mounting a real
checkpoint: every step above the weight files is exercised.

The torch side implements the backend protocol directly on HF primitives
(greedy ``model.generate``, teacher-forced log-softmax gather, mean-pooled
hidden-state embeddings) while borrowing the SAME tokenizer and prompt
rendering as the production backend, so any disagreement isolates to model
numerics — already certified to <=2e-4 — or to pipeline logic, which is
what this test pins.
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax.numpy as jnp  # noqa: E402

from consensus_tpu.backends.base import (  # noqa: E402
    GenerationRequest,
    GenerationResult,
    ScoreRequest,
    ScoreResult,
    TokenCandidate,
)
from consensus_tpu.backends.tpu import TPUBackend  # noqa: E402
from consensus_tpu.evaluation import StatementEvaluator  # noqa: E402
from consensus_tpu.methods.best_of_n import BestOfNGenerator  # noqa: E402
from consensus_tpu.models.tokenizer import get_tokenizer  # noqa: E402

ISSUE = "Should the library extend its opening hours?"
OPINIONS = {
    "Agent 1": "Students need late-night study space.",
    "Agent 2": "Staff costs must stay within the current budget.",
}


class TorchRefBackend:
    """Backend protocol on HF torch primitives (CPU, float32, eager)."""

    name = "torch-ref"

    def __init__(self, model):
        self.model = model
        self.tokenizer = get_tokenizer(None, family="gemma")

    # Prompt/score rendering is BORROWED from the production backend so the
    # two stacks tokenize byte-identical strings.
    _render_prompt = TPUBackend._render_prompt
    _score_prefix = TPUBackend._score_prefix

    def generate(self, requests):
        results = []
        for request in requests:
            ids = self.tokenizer.encode(self._render_prompt(request), add_bos=True)
            with torch.no_grad():
                out = self.model.generate(
                    torch.tensor([ids]),
                    max_new_tokens=request.max_tokens,
                    do_sample=False,
                    eos_token_id=list(self.tokenizer.eos_ids),
                    pad_token_id=self.tokenizer.pad_id,
                )
            new_ids = out[0, len(ids):].tolist()
            if new_ids and new_ids[-1] in self.tokenizer.eos_ids:
                new_ids = new_ids[:-1]
                finish = "stop"
            else:
                finish = "length"
            text = self.tokenizer.decode(new_ids)
            results.append(
                GenerationResult(
                    text=text, token_ids=tuple(new_ids), finish_reason=finish
                )
            )
        return results

    def score(self, requests):
        results = []
        for request in requests:
            ctx = self.tokenizer.encode(self._score_prefix(request), add_bos=True)
            cont = self.tokenizer.encode(request.continuation)
            ids = torch.tensor([ctx + cont])
            with torch.no_grad():
                logits = self.model(input_ids=ids).logits.float()
            logprobs = torch.log_softmax(logits[0], dim=-1)
            span = []
            for j, token in enumerate(cont):
                span.append(float(logprobs[len(ctx) + j - 1, token]))
            results.append(
                ScoreResult(
                    tokens=tuple(
                        self.tokenizer.decode([t]) for t in cont
                    ),
                    logprobs=tuple(span),
                )
            )
        return results

    def embed(self, texts):
        vectors = []
        for text in texts:
            ids = self.tokenizer.encode(text, add_bos=True)
            with torch.no_grad():
                hidden = self.model.model(
                    input_ids=torch.tensor([ids])
                ).last_hidden_state[0].float()
            pooled = hidden.mean(dim=0).numpy()
            vectors.append(pooled / max(np.linalg.norm(pooled), 1e-12))
        return np.stack(vectors)

    def next_token_logprobs(self, requests):
        """Deterministic top-k proposals, mirroring the production backend's
        semantics for ``mode=="topk"`` or ``temperature<=0`` rows (the only
        rows whose Gumbel term is zeroed there, generate.py:next_token_topk):
        bias added to LOGITS over every token id containing each banned
        string, then top-k of the biased log-softmax."""
        results = []
        for request in requests:
            if request.mode != "topk" and request.temperature > 0:
                raise NotImplementedError(
                    "torch reference implements deterministic proposals only"
                )
            ids = self.tokenizer.encode(
                self._render_prompt(request), add_bos=True
            )
            with torch.no_grad():
                logits = self.model(
                    input_ids=torch.tensor([ids])
                ).logits[0, -1].float()
            for text in request.bias_against_tokens:
                for token_id in self.tokenizer.token_ids_containing(text):
                    logits[token_id] += request.bias_value
            logprobs = torch.log_softmax(logits, dim=-1)
            top = torch.topk(logprobs, min(request.k, logprobs.shape[-1]))
            results.append(
                [
                    TokenCandidate(
                        token=self.tokenizer.decode([int(i)]),
                        token_id=int(i),
                        logprob=float(v),
                    )
                    for v, i in zip(top.values, top.indices)
                ]
            )
        return results


def _hf_tiny_gemma2_long():
    """tiny-gemma2's exact structure, but with a 1024-position window —
    the reference prompt templates alone are ~500 byte-tokens."""
    cfg = transformers.Gemma2Config(
        vocab_size=268,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=4,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        query_pre_attn_scalar=16,
        sliding_window=16,
        attn_logit_softcapping=50.0,
        final_logit_softcapping=30.0,
        rope_theta=10_000.0,
        rms_norm_eps=1e-6,
        hidden_activation="gelu_pytorch_tanh",
        max_position_embeddings=1024,
        tie_word_embeddings=True,
        attention_dropout=0.0,
    )
    cfg._attn_implementation = "eager"
    torch.manual_seed(0)
    model = transformers.Gemma2ForCausalLM(cfg)
    model.eval()
    return model


@pytest.fixture(scope="module")
def stacks(tmp_path_factory):
    from tests.test_hf_numerics import _save_hf_model

    model = _hf_tiny_gemma2_long()
    ckpt = _save_hf_model(model, tmp_path_factory.mktemp("ckpt"))
    torch_backend = TorchRefBackend(model)
    # The checkpoint has the preset's 268 rows, the byte tokenizer's id
    # range: both stacks index the same rows of the same embedding matrix.
    jax_backend = TPUBackend(
        model="tiny-gemma2", checkpoint=ckpt, dtype="float32", max_context=1024
    )
    return torch_backend, jax_backend


def run_cell(backend):
    generator = BestOfNGenerator(
        backend=backend,
        config={"n": 2, "max_tokens": 16, "temperature": 0.0, "seed": 3},
    )
    return generator.generate_statement(ISSUE, OPINIONS)


def test_same_statement_through_both_stacks(stacks):
    torch_backend, jax_backend = stacks
    assert run_cell(torch_backend) == run_cell(jax_backend)


def test_metric_columns_agree(stacks):
    torch_backend, jax_backend = stacks
    statement = run_cell(jax_backend)
    metrics = {}
    for name, backend in (("torch", torch_backend), ("jax", jax_backend)):
        evaluator = StatementEvaluator(backend=backend)
        metrics[name] = evaluator.evaluate_statement(statement, ISSUE, OPINIONS)
    keys_t = {k for k, v in metrics["torch"].items() if isinstance(v, (int, float))}
    keys_j = {k for k, v in metrics["jax"].items() if isinstance(v, (int, float))}
    assert keys_t == keys_j and keys_t
    for key in sorted(keys_t):
        a, b = metrics["torch"][key], metrics["jax"][key]
        assert a == pytest.approx(b, rel=2e-3, abs=2e-3), key


def test_mcts_cell_through_both_stacks(stacks):
    """Session-driven search through both stacks: torch runs MCTS over the
    full-prefix fallback session (next_token_logprobs + score + generate),
    jax over the fused TPU session (persistent KV caches, batched wave
    rollouts) — same weights, same statement.  temperature=0 keeps both
    proposal paths on deterministic top-k, so any divergence isolates to
    session/search logic rather than sampling streams."""
    from consensus_tpu.methods.mcts import MCTSGenerator

    torch_backend, jax_backend = stacks
    cfg = {
        "num_simulations": 2,
        "expansion_sample_width": 2,
        "max_tokens": 3,
        "rollout_depth": 2,
        "temperature": 0.0,
        "seed": 5,
        "mcts_wave_size": 2,
    }
    statements = {}
    for name, backend in (("torch", torch_backend), ("jax", jax_backend)):
        gen = MCTSGenerator(backend, dict(cfg))
        statements[name] = gen.generate_statement(ISSUE, OPINIONS)
        assert gen.search_stats["device_dispatches"] > 0
    assert statements["torch"] == statements["jax"]


def test_greedy_generation_token_identical(stacks):
    """The raw greedy decode paths agree token-for-token for a plain
    request (no search logic in the loop)."""
    torch_backend, jax_backend = stacks
    request = GenerationRequest(
        user_prompt=f"Issue: {ISSUE}", max_tokens=24, temperature=0.0, seed=1
    )
    a = torch_backend.generate([request])[0]
    b = jax_backend.generate([request])[0]
    assert a.token_ids == b.token_ids
    assert a.text == b.text
