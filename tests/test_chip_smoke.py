"""The chip smoke's phases at a tiny size, and the guards it relies on.

``chip_smoke.py`` itself runs only on a TPU.  Here its phase functions run
on the CPU with ``tiny-gemma2`` and a short corpus scenario, and each thing
it counts on to fail loudly is pinned: the command line without a chip, the
compile cache's placement, the peaks table, the platform check, the
vocabulary ban, and the session counter behind ``/healthz``.
"""

import gc
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke
from consensus_tpu.backends.base import GenerationRequest, NextTokenRequest
from consensus_tpu.backends.batching import BatchingBackend
from consensus_tpu.backends.session import SearchSpec, open_token_search
from consensus_tpu.backends.tpu import TPUBackend
from consensus_tpu.models.config import get_model_config
from consensus_tpu.utils import compile_cache, mfu

REPO = pathlib.Path(__file__).resolve().parent.parent

#: Three agents and a 0.9k-token reference prompt: every code path of the
#: paper scenario at a tenth of its attention cost on the CPU.
TINY = chip_smoke.Sizes(
    model="tiny-gemma2",
    scenario="corpus:v2:sybil-0000",
    max_context=1024,
    bon_n=4,
    bon_tokens=16,
    beam_width=2,
    search_tokens=4,
    lookahead_depth=2,
    mcts_simulations=4,
    mcts_rollout_depth=2,
    habermas_tokens=128,  # twice a 64-token segment: still segmented
    habermas_candidates=4,
    request_timeout_s=600.0,
)


def test_phases_pass_on_tiny_model():
    report = chip_smoke.run(TINY, kernel_interpret=True)
    # The one thing a CPU cannot pass: transformer.py would interpret its
    # Pallas calls here.  Everything else is the chip run's own checklist.
    assert report["failures"] == [
        "Pallas kernels would run interpreted on this platform"
    ]
    serving, stream = report["serving"], report["stream"]
    assert serving["model"]["vocab_size"] == 268
    assert serving["sessions_opened"] == {"fused": 3, "prefix": 0}
    assert serving["matrix_stats"]["fallbacks"] == 0
    assert serving["programs"]["generate_shared"]["launches"] >= 2
    assert serving["healthz_backend"]["device"] == {
        "platform": "cpu", "device_kind": "cpu", "count": len(jax.devices()),
    }
    assert stream["programs"]["generate_stream"]["launches"] == 1
    assert stream["engine"]["decode_steps"] == TINY.decode_steps
    assert stream["backend_id"] == serving["backend_id"]
    for step in (*serving["steps"].values(), *stream["steps"].values()):
        for request in step["requests"].values():
            assert request["status"] == 200 and not request["problems"]
    assert all(case["ok"] for case in report["kernels"]["cases"].values())


def test_failures_name_each_hidden_fallback():
    """The report's own checklist: one entry per fallback that would
    otherwise pass for a run on the chip."""
    block = {
        "steps": {"s": {"requests": {"r": {"problems": ["HTTP 500: boom"]}}}},
        "matrix_stats": {"calls": 1, "chunks": 0, "fallbacks": 1},
        "sessions_opened": {"fused": 0, "prefix": 1},
        "truncated_prompts": 1,
        "engine": {"fused_search_sessions": -2},
        "programs": {},
        "backend_id": 1,
        "prompts": {"best_of_n": {"tokens": 5000, "room": 4096}},
        "model": {"embed_rows": 268, "vocab_size": 256_128},
    }
    report = {
        "serving": block,
        "stream": dict(block, backend_id=2),
        "pallas_interpret": True,
        "kernels": {"cases": {"flash": {"ok": False, "error": "Mosaic said no"}}},
        "memory": [{"id": 0, "bytes_limit": 8 * 1024**3}],
    }
    text = "\n".join(chip_smoke.failures(report, 15 * 1024**3))
    for needle in (
        "HTTP 500", "per-call", "full-prefix", "cut to fit", "live search",
        "does not fit", "never ran", "no K-step stream", "backend of its own",
        "268 embedding rows", "interpreted", "Mosaic said no", "bytes_limit",
    ):
        assert needle in text, needle


def test_command_line_needs_a_chip():
    """Without a TPU: exit code 2, no phase, no result line."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=str(REPO),
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 2
    assert proc.stdout == ""
    assert "no phase was run" in proc.stderr


def test_compile_cache_leaves_jax_alone_when_placed_from_outside(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_is_one_fixed_path_in_the_checkout(tmp_path, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expected = str(REPO / ".jax_cache")
    monkeypatch.chdir(tmp_path)
    assert compile_cache.enable_compile_cache() == expected
    assert jax.config.jax_compilation_cache_dir == expected
    # A fresh interpreter started somewhere else resolves the same path.
    elsewhere = tmp_path / "elsewhere"
    elsewhere.mkdir()
    env = dict(os.environ, PYTHONPATH=str(REPO), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "-c",
         "from consensus_tpu.utils.compile_cache import enable_compile_cache;"
         "import jax; print(enable_compile_cache());"
         "print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, timeout=120, cwd=str(elsewhere), env=env,
    )
    assert proc.stdout.split() == [expected, expected], proc.stderr


def test_compile_cache_keys_a_program_by_its_names_and_not_its_lines(monkeypatch):
    """An executable read from the cache carries the names it was compiled
    with, so the key holds them (a profile of a cache hit would otherwise
    show an older build's scopes); it holds no file and no line, so moving
    code compiles nothing again."""
    import jax.numpy as jnp

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    compile_cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key is True

    def program(scope):
        def step(x):
            with jax.named_scope(scope):
                return jnp.tanh(x @ x)
        return jax.jit(step).lower(jnp.ones((8, 8)))

    one, other = program("attention"), program("ffn")
    assert one.as_text() == other.as_text()  # one program,
    keyed = one.as_text(debug_info=True)  # two sets of names
    assert keyed != other.as_text(debug_info=True)
    assert "attention/dot_general" in keyed
    assert ".py" not in keyed


def test_peaks_table_has_no_default():
    assert mfu.device_peaks("TPU v5 lite").bf16_tflops == 197.0
    with pytest.raises(ValueError, match="no published peaks"):
        mfu.device_peaks("cpu")
    with pytest.raises(ValueError, match="no published peaks"):
        mfu.pct_of_peak(1.0, "TPU v9 imaginary")


def test_backend_refuses_a_cpu_it_was_not_asked_for():
    """JAX_PLATFORMS unset and no accelerator: JAX falls back to the CPU with
    a warning.  A ``tpu`` backend must not serve from there."""
    asked = jax.config.jax_platforms
    assert "cpu" in asked  # conftest names it: constructing works
    jax.config.update("jax_platforms", None)
    try:
        with pytest.raises(RuntimeError, match="fell back to the CPU"):
            TPUBackend(model="tiny-gemma2", max_context=64)
    finally:
        jax.config.update("jax_platforms", asked)


# -- the vocabulary is a width ---------------------------------------------------


@pytest.fixture(scope="module")
def wide_vocab_backend():
    """A model with more rows than the byte tokenizer has ids.  With random
    weights half of all unbanned samples would be undecodable."""
    config = get_model_config("tiny-gemma2", vocab_size=512)
    return TPUBackend(config=config, max_context=128, dtype="float32")


def test_wide_vocabulary_is_served_whole(wide_vocab_backend):
    backend = wide_vocab_backend
    assert backend.config.vocab_size == 512
    assert backend.params["embed"].shape[0] == 512
    assert backend.config.sample_vocab == backend.tokenizer.vocab_size == 268


def test_generate_never_emits_an_undecodable_id(wide_vocab_backend):
    backend = wide_vocab_backend
    decodable = backend.tokenizer.vocab_size
    shared = [
        GenerationRequest(user_prompt="same prompt", max_tokens=24, seed=i)
        for i in range(4)
    ]  # four identical prompts: the shared-trunk path
    classic = [
        GenerationRequest(user_prompt=f"prompt {i}", max_tokens=24, seed=i,
                          temperature=0.0 if i == 0 else 1.0)
        for i in range(3)
    ]
    ids = [
        t for batch in (shared, classic)
        for result in backend.generate(batch) for t in result.token_ids
    ]
    stream = backend.generate_stream(classic, decode_steps=4)
    while not stream.finished:
        stream.dispatch()
        stream.collect()
    ids += [t for result in stream.results() for t in result.token_ids]
    stream.close()
    assert len(ids) > 100
    assert max(ids) < decodable


def test_search_never_proposes_an_undecodable_id(wide_vocab_backend):
    backend = wide_vocab_backend
    decodable = backend.tokenizer.vocab_size
    proposals = backend.next_token_logprobs([
        NextTokenRequest(user_prompt="a prompt", k=16, temperature=1.0,
                         seed=3, mode="sample"),
        NextTokenRequest(user_prompt="a prompt", k=16, mode="topk"),
    ])
    ids = [c.token_id for row in proposals for c in row]
    assert all(np.isfinite(c.logprob) for row in proposals for c in row)

    spec = SearchSpec(
        ref_system=None, ref_user="reference prompt",
        agent_prompts=((None, "agent one"), (None, "agent two")),
        n_slots=1, k=8, seed=5, max_steps=8,
    )
    session = backend.open_fused_token_search(spec)
    try:
        slots = session.propose()
        ids += [c.token_id for c in slots[0]]
        slots = session.advance_and_propose([0], [slots[0][0]])
        ids += [c.token_id for c in slots[0]]
        paths = [[c] for c in slots[0][:4]]
        for row in session.propose_suffixes(paths, salt=1):
            ids += [c.token_id for c in row]
        rolled, _, totals, _ = session.rollout_from(paths[0], depth=6, salt=2)
        ids += rolled
        assert all(np.isfinite(t) for t in totals)
        for rolled, _, _, _ in session.rollout_many(
            paths, depth=6, salts=[3, 4, 5, 6]
        ):
            ids += rolled
    finally:
        session.close()
    assert len(ids) > 50
    assert max(ids) < decodable


# -- /healthz session counter -----------------------------------------------------


def test_tracked_session_leaves_the_pressure_surface_once():
    """``TPUTokenSearchSession.__del__`` closes again after the explicit
    close; the engine's counters must come back to zero, not go below."""
    inner = TPUBackend(model="tiny-gemma2", max_context=128)
    batching = BatchingBackend(inner, engine=True)
    try:
        spec = SearchSpec(
            ref_system=None, ref_user="reference prompt",
            agent_prompts=((None, "agent one"),), n_slots=3, k=2, seed=1,
            max_steps=4,
        )
        session = open_token_search(batching, spec)
        stats = batching.engine.stats()
        assert stats["fused_search_sessions"] == 1
        assert stats["fused_search_slots"] == 3
        session.propose()
        session.close()
        session.close()
        del session
        gc.collect()
        stats = batching.engine.stats()
        assert stats["fused_search_sessions"] == 0
        assert stats["fused_search_slots"] == 0
    finally:
        batching.close()
