"""The Falcon-H1 architecture as files of the benchmark: its reference held
to the plain one of ``tests/``, its weights' draws to the program's, its work
count's terms on the published ``model`` block, and a tiny fixture through
the harness on the CPU (correct; the float8 control not correct; a served
path that drops the recurrent state at the fork not correct).  The runs
through the harness are slow (minutes): run by hand, ``pytest benchmark/tests``.
"""

import importlib.util
import json
import pathlib
import sys

import numpy as np
import pytest

from benchmark.lib import check, harness
from benchmark.lib import reference as ref
from benchmark.tests.test_harness_cpu import FIXTURE, _run_in_process, run_cell

TESTS = pathlib.Path(__file__).resolve().parent
BENCH = TESTS.parent
FALCON = TESTS / "fixture_falcon_h1"
DIRS = [FALCON, FIXTURE, BENCH]
PUBLISHED = json.loads((BENCH / "configs" / "falcon-h1-34b-l4.json").read_text())
TINY = json.loads((FALCON / "configs" / "tiny-falcon-h1.json").read_text())


def _reference():
    return harness.load_module([BENCH], "references", "falcon_h1",
                               harness.REFERENCE_GIVES)


# -- the configuration file ---------------------------------------------------------


def test_the_configuration_file_keeps_every_published_key():
    catalog = PUBLISHED["published"]
    assert catalog["model_type"] == "falcon_h1" and len(catalog) == 42
    for key, value in catalog.items():  # at the top level too, unchanged
        assert PUBLISHED[key] == value, key
    model = PUBLISHED["model"]
    assert PUBLISHED["reduced"] == ["n_layers"] and model["n_layers"] == 4
    assert catalog["num_hidden_layers"] == 72
    same = {"vocab_size": "vocab_size", "d_model": "hidden_size",
            "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
            "head_dim": "head_dim", "ffn_hidden": "intermediate_size",
            "ssm_heads": "mamba_n_heads", "ssm_head_dim": "mamba_d_head",
            "ssm_state": "mamba_d_state", "ssm_groups": "mamba_n_groups",
            "ssm_conv": "mamba_d_conv", "ssm_chunk": "mamba_chunk_size",
            "ssm_inner": "mamba_d_ssm", "rms_eps": "rms_norm_eps",
            "rope_theta": "rope_theta", "ssm_slice_multipliers": "ssm_multipliers",
            "tie_lm_head": "tie_word_embeddings",
            "ssm_norm_before_gate": "mamba_norm_before_gate"}
    same.update({key: key for key in catalog if key.endswith("_multiplier")
                 or key == "mlp_multipliers"})
    for ours, theirs in same.items():
        assert model[ours] == catalog[theirs], ours
    cell = harness.load_cell([BENCH], "falcon-h1-34b-l4.bon_sweep")
    assert cell.reference.ref_config(cell.model).ssm_state == 256
    harness.model_config(cell)  # every key of the block is the program's


def test_the_reference_refuses_what_it_does_not_compute():
    falcon = _reference()
    model = PUBLISHED["model"]
    with pytest.raises(ValueError, match="mamba_d_state"):
        falcon.ref_config({**model, "mamba_d_state": 256})
    with pytest.raises(ValueError, match="use_post_norms"):
        falcon.ref_config({**model, "use_post_norms": True})
    with pytest.raises(ValueError, match="tie_lm_head"):
        falcon.ref_config({**model, "tie_lm_head": True})
    lacking = {k: v for k, v in model.items() if k != "key_multiplier"}
    with pytest.raises(ValueError, match="key_multiplier"):
        falcon.ref_config(lacking)
    dense = harness.load_module([BENCH], "references", "dense",
                                harness.REFERENCE_GIVES)
    with pytest.raises(ValueError, match="ssm_heads"):
        dense.ref_config(model)


# -- the work count on the published block ------------------------------------------


def test_the_work_files_terms_on_the_published_block():
    model = PUBLISHED["model"]
    work = harness.load_work([BENCH], "falcon_h1", model)  # terms sum to the whole
    assert tuple(work.TERMS) == ("attention", "head", "matrix", "ssm")
    assert work.param_count(model) == 4_394_354_048
    assert work.layer_matmul_params(model) == 31_457_280 + 47_349_760 \
        + 20_971_520 + 330_301_440
    assert work.in_dim(model) == 9248 and work.conv_dim(model) == 5120
    # 2 bytes a parameter, 4 for the three vectors a head.
    assert work.weight_bytes(model) == 2 * 4_394_354_048 + 4 * 3 * 32 * 2
    assert round(work.weight_bytes(model) / 1e9, 2) == 8.79
    assert work.weight_bytes(model, "head") == 261120 * 5120 * 2
    row = work.state_bytes_per_row(model)
    assert row == 4 * (4 * 32 * 128 * 256 + 2 * 3 * 5120)
    assert round(row / 4 / 2**20, 2) == 4.03
    assert work.kv_bytes_per_token(model) == 4 * 2048
    forms = work.recurrence_flops(model)
    assert forms["sequential"] == 32 * 164_224 and forms["chunked"] == 32 * 150_656
    assert work.ssm_position_flops(model) == 32 * 150_656 + 2 * 4 * 5120
    # The mixer's two products are 16% of a layer's matrix work; the
    # recurrence itself half a percent of a position's.
    mixer = 5120 * 9248 + 4096 * 5120
    assert round(mixer / work.layer_matmul_params(model), 2) == 0.16
    one = work.span_flops(model, 0, 1)
    assert round(work.span_flops(model, 0, 1, term="matrix") / 1e9, 2) == 3.44
    assert 0.004 < work.span_flops(model, 0, 1, term="ssm") / one < 0.007
    assert round(work.span_flops(model, 0, 1, 1, term="head") / 1e9, 2) == 2.67
    # A decode step of 32 rows: the state in and out, 1.08 GB, beside 8.79 GB
    # of weights; a dense work file reads the same bytes whatever the rows.
    assert work.step_bytes(model, 3000, 32, term="ssm") - work.step_bytes(
        model, 3000, 0, term="ssm") == 2 * 32 * row
    assert round(2 * 32 * row / 1e9, 2) == 1.08
    assert work.step_bytes(model, 3000, 32, term="attention") == 3000 * 8192
    tiny = harness.load_work(DIRS, "falcon_h1", TINY["model"])
    assert tiny.state_bytes_per_row(TINY["model"]) == 2 * (4 * 4 * 16 * 16 + 2 * 3 * 128)


# -- the reference against the plain one of tests/, and the weights' draws -----------


def _plain_reference():
    path = BENCH.parent / "tests" / "reference_falcon_h1.py"
    spec = importlib.util.spec_from_file_location("reference_falcon_h1", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("norm_before_gate", [False, True])
def test_the_benchmarks_reference_is_the_plain_one(norm_before_gate):
    import jax.numpy as jnp

    falcon, plain = _reference(), _plain_reference()
    model = {**TINY["model"], "ssm_norm_before_gate": norm_before_gate}
    cfg = falcon.ref_config(model)
    weights = falcon.make_weights(cfg, 2 ** 31 + 27)
    rng = np.random.default_rng(27)
    rows = [(list(rng.integers(12, 268, size=n)), scored)
            for n, scored in ((40, 11), (130, 64), (19, 5), (700, 70))]
    got = falcon.score_rows(cfg, weights, rows)
    for (ids, scored), mine in zip(rows, got):
        want = np.asarray(plain.token_logprobs(cfg, weights, jnp.asarray(ids)))
        np.testing.assert_allclose(mine.logprob, want[-scored:], atol=2e-4, rtol=0)
        logits = np.asarray(plain.forward(cfg, weights, jnp.asarray(ids)))
        at = np.arange(len(ids) - scored - 1, len(ids) - 1)
        np.testing.assert_allclose(
            mine.best_logit, logits[at, :268].max(axis=1), atol=2e-4, rtol=0)
        assert list(mine.best_id) == list(logits[at, :268].argmax(axis=1))
    low = falcon.score_rows(cfg, weights, rows[:1], precision="fp8")[0]
    assert np.abs(low.logprob - got[0].logprob).max() > 0.01


def test_the_reference_draws_the_weights_the_program_serves():
    falcon = _reference()
    cell = harness.Cell(name="tiny-falcon-h1", workload={}, config=TINY, traffic={},
                        bench_dir=FALCON, reference=falcon)
    seed = 2 ** 31 + 27
    params = harness.make_params(harness.model_config(cell), seed)
    served = ref.weights_checksum(params)
    own = ref.weights_checksum(
        falcon.make_weights(falcon.ref_config(cell.model), seed))
    # 17 leaves a layer stack (9 dense, 8 of the mixer) and 3 beside; the
    # float32 leaves (A_log, dt_bias, D) are summed as two 16-bit halves a
    # value, so a difference in either half shows.
    assert check.differing_leaves(served, own) == (0, 20)
    assert str(params["layers"]["ssm_a_log"].dtype) == "float32"
    fewer = {path: s for path, s in own.items() if "ssm_dt_bias" not in path}
    assert check.differing_leaves(served, fewer) == (1, 20)


# -- the tiny fixture through the harness ---------------------------------------------


def test_the_fixture_cell_is_correct_and_its_control_is_not():
    line, _ = run_cell("--workload", "tiny-falcon-h1.bon_small", "--seed",
                       "2700000123", "--seconds", "2", "--trace", "1", "--control",
                       bench_dirs=(FALCON, FIXTURE))
    assert line["correct"] is True and line["failed"] == 0
    compared = line["compared"]
    for name in ("matrix_gap", "greedy_gap", "generated", "selection",
                 "truncated", "weights"):
        assert compared[name]["value"] <= compared[name]["limit"]
    assert compared["weights"]["compared"] == 20
    assert line["control_correct"] is False
    control = line["control"]
    assert control["matrix_gap"]["value"] > control["matrix_gap"]["limit"]
    assert control["greedy_gap"]["value"] > control["greedy_gap"]["limit"]
    assert control["matrix_gap"]["value"] > 3 * compared["matrix_gap"]["value"]
    # No peak on a CPU and no device plane: the shares are left out, not 0.
    for name in ("ssm_scan_roofline", "ssm_device_pct", "window_mfu_pct"):
        assert name not in line["metrics"]
    assert "engine_wait_ms" in line["metrics"]


def test_a_state_dropped_at_the_fork_is_not_correct(monkeypatch):
    """The served path with every forked row starting from a zero state:
    the decode rows forget the prompt, the score rows their context."""
    import jax
    import jax.numpy as jnp

    from consensus_tpu.models import generate, stepper, transformer

    real = transformer.fork_ssm

    def dropped(state, rows):
        return jax.tree.map(jnp.zeros_like, real(state, rows))

    for module in (generate, stepper, transformer):
        monkeypatch.setattr(module, "fork_ssm", dropped)
    jax.clear_caches()
    try:
        line = _run_in_process("tiny-falcon-h1.bon_small", seed=2700000123,
                               bench_dirs=(FALCON, FIXTURE))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert line["correct"] is False
    compared = line["compared"]
    assert compared["matrix_gap"]["value"] > compared["matrix_gap"]["limit"]
    assert compared["weights"]["value"] == 0
