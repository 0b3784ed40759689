"""The MiMo-V2-Flash configuration as files of the benchmark: the file's
published keys and its cut, its reference held to the plain one of
``tests/``, its weights' draws to the program's, its work count's terms on the
published ``model`` block (hand-worked numbers), and a tiny fixture through
the harness on the CPU (correct; the float8 control not correct).  The run
through the harness is slow (minutes): run by hand, ``pytest benchmark/tests``
(``pytest tests/`` does not collect this directory).
"""

import importlib.util
import json
import pathlib

import numpy as np
import pytest

from benchmark.lib import check, harness
from benchmark.lib import reference as ref
from benchmark.tests.test_harness_cpu import FIXTURE, run_cell

TESTS = pathlib.Path(__file__).resolve().parent
BENCH = TESTS.parent
MIMO = TESTS / "fixture_mimo_v2"
DIRS = [MIMO, FIXTURE, BENCH]
PUBLISHED = json.loads((BENCH / "configs" / "mimo-v2-flash-l7e16.json").read_text())
TINY = json.loads((MIMO / "configs" / "tiny-mimo-v2.json").read_text())
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")


def _reference():
    return harness.load_module([BENCH], "references", "mimo_v2",
                               harness.REFERENCE_GIVES)


# -- the configuration file ---------------------------------------------------------


def test_the_configuration_file_keeps_every_published_key_but_the_three_it_cuts():
    catalog = PUBLISHED["published"]
    assert catalog["model_type"] == "mimo_v2_flash" and len(catalog) == 39
    reduced = PUBLISHED["reduced"]
    assert reduced == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    for key, value in catalog.items():  # at the top level too
        if key not in reduced:
            assert PUBLISHED[key] == value, key
    # The published counts beside the held ones.
    assert (catalog["num_hidden_layers"], PUBLISHED["num_hidden_layers"]) == (48, 7)
    assert (catalog["n_routed_experts"], PUBLISHED["n_routed_experts"]) == (256, 16)
    assert (catalog["vocab_size"], PUBLISHED["vocab_size"]) == (152576, 19072)
    assert set(PUBLISHED["held"]) == set(reduced)
    assert "16 chips" in PUBLISHED["stands_for"]
    model = PUBLISHED["model"]
    same = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
            "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
            "ffn_hidden": "intermediate_size", "rope_theta": "rope_theta",
            "rms_eps": "layernorm_epsilon", "sliding_window": "sliding_window",
            "swa_kv_heads": "swa_num_key_value_heads",
            "swa_rope_theta": "swa_rope_theta", "v_head_dim": "v_head_dim",
            "swa_sink": "add_swa_attention_sink_bias",
            "value_scale": "attention_value_scale", "n_experts": "n_routed_experts",
            "experts_per_token": "num_experts_per_tok",
            "expert_hidden": "moe_intermediate_size",
            "tie_lm_head": "tie_word_embeddings"}
    for ours, theirs in same.items():  # no width is cut
        assert model[ours] == catalog[theirs], ours
    assert model["rotary_dim"] == int(catalog["head_dim"] * catalog["partial_rotary_factor"])
    assert model["hybrid_layer_pattern"] == catalog["hybrid_layer_pattern"][:7]
    assert model["moe_layer_freq"] == catalog["moe_layer_freq"][:7]
    assert model["experts_held"] == [0, PUBLISHED["n_routed_experts"]]
    assert model["vocab_size"] == PUBLISHED["vocab_size"]
    assert model["n_layers"] == PUBLISHED["num_hidden_layers"]
    # The floors of a cut: a whole period after the leading dense layer, at
    # least 8 experts, at least an eighth of the vocabulary.
    assert model["moe_layer_freq"][0] == 0 and sum(model["hybrid_layer_pattern"][1:]) == 5
    assert model["experts_held"][1] >= 8 and 8 * model["vocab_size"] >= catalog["vocab_size"]
    cell = harness.load_cell([BENCH], "mimo-v2-flash-l7e16.bon_sweep")
    assert cell.reference.ref_config(cell.model).experts_held == (0, 16)
    config = harness.model_config(cell)  # every key of the block is the program's
    assert [run.count for run in config.layer_runs] == [1, 4, 1, 1]
    listed = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = [c for c in listed["configs"] if c["name"] == PUBLISHED["name"]][0]
    assert entry["reduced"] == reduced and entry["source"] == PUBLISHED["source"]


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog on this machine")
def test_the_published_block_is_the_catalogs_row():
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines()]
    row = [r for r in rows if r["name"] == "MiMo-V2-Flash"][0]
    assert PUBLISHED["published"] == row["config"]
    assert PUBLISHED["source"] == row["source_url"]


def test_the_reference_refuses_what_it_does_not_compute():
    mimo = _reference()
    model = PUBLISHED["model"]
    with pytest.raises(ValueError, match="n_shared_experts"):
        mimo.ref_config({**model, "n_shared_experts": 1})
    with pytest.raises(ValueError, match="ssm_heads"):
        mimo.ref_config({**model, "ssm_heads": 4})
    with pytest.raises(ValueError, match="tie_lm_head"):
        mimo.ref_config({**model, "tie_lm_head": True})
    with pytest.raises(ValueError, match="value_scale"):
        mimo.ref_config({k: v for k, v in model.items() if k != "value_scale"})
    with pytest.raises(ValueError, match="experts_held"):
        mimo.ref_config({**model, "experts_held": [250, 16]})
    dense = harness.load_module([BENCH], "references", "dense",
                                harness.REFERENCE_GIVES)
    with pytest.raises(ValueError, match="hybrid_layer_pattern"):
        dense.ref_config(model)


# -- the work count on the published block ------------------------------------------


def test_the_work_files_terms_on_the_published_block():
    model = PUBLISHED["model"]
    work = harness.load_work([BENCH], "mimo_v2", model)  # terms sum to the whole
    assert tuple(work.TERMS) == ("attention", "attention_window", "experts",
                                 "head", "matrix")
    assert work.layers_of(model) == {"full": 2, "window": 5, "dense": 1, "routed": 6}
    # Attention matrices: Wq 4,096 x 12,288, Wk x 768 (1,536), Wv x 512
    # (1,024), Wo 8,192 x 4,096.
    assert work.attention_params(model, False) == 89_128_960
    assert work.attention_params(model, True) == 94_371_840
    assert work.expert_params(model) == 25_165_824
    # Layer 0 290.5 M; a window expert layer 498.1 M; the full one 492.8 M;
    # embedding and head 156.2 M.
    d = 4096
    layer0 = 89_128_960 + 3 * d * 16384 + 2 * d
    window = 94_371_840 + 64 + d * 256 + 256 + 16 * 25_165_824 + 2 * d
    full = 89_128_960 + d * 256 + 256 + 16 * 25_165_824 + 2 * d
    assert work.param_count(model) == layer0 + 5 * window + full + 2 * 19072 * d + d
    assert work.param_count(model) == 3_429_955_392
    assert round(work.weight_bytes(model) / 1e9, 2) == 6.87  # the issue's 6.86 + float32
    assert work.weight_bytes(model) == 2 * 3_429_955_392 + 2 * (6 * (d * 256 + 256) + 5 * 64)
    # 2,560 B a full layer, 5,120 B a window layer: 30 KiB a token.
    assert work.kv_bytes_per_token(model, "full") == 2 * 2560
    assert work.kv_bytes_per_token(model, "window") == 5 * 5120
    assert work.kv_bytes_per_token(model) == 30 * 1024
    # 4.83 GB of experts a decode step that reads all 16, beside the routers.
    experts = 6 * 16 * 25_165_824 * 2
    assert round(experts / 1e9, 2) == 4.83
    assert work.weight_bytes(model, "experts") == experts + 6 * 4 * (d * 256 + 256)
    # 32 rows are expected to reach 16 x (1 - (31/32)^32) = 10.2 of them.
    assert round(work.experts_hit(model, 32), 1) == 10.2
    assert work.experts_hit(model, 0) == 0 and work.experts_hit(model, 10 ** 4) > 15.99
    step = work.step_bytes(model, 3000, 32, term="experts")
    assert experts * 10.2 / 16 < step < experts * 10.3 / 16 + 3e7
    assert work.step_bytes(model, 3000, 32, term="attention") == 3000 * 5120
    assert work.step_bytes(model, 3000, 32, term="attention_window") == 128 * 25600
    # A token meets 0.5 held experts.
    assert work.held_assignments_per_position(model) == 0.5
    one = work.span_flops(model, 0, 1, term="experts")
    assert one == 6 * (2 * d * 256 + 2 * 0.5 * 25_165_824)
    # The window's least work: position p sees min(p + 1, 128) keys.
    assert work.window_context(model, 0, 3) == 1 + 2 + 3
    assert work.window_context(model, 126, 4) == 127 + 128 + 128 + 128
    assert work.window_context(model, 1000, 50) == 50 * 128
    per_key = 2 * 64 * (192 + 128)
    assert work.span_flops(model, 1000, 50, term="attention_window") == \
        per_key * 50 * 128 * 5
    assert work.span_flops(model, 1000, 50, term="attention") == \
        per_key * (50 * 1000 + 50 * 51 // 2) * 2
    tiny = harness.load_work(DIRS, "mimo_v2", TINY["model"])
    assert tiny.kv_bytes_per_token(TINY["model"]) == 2 * 40 * (2 * 2 + 5 * 4)


# -- the reference against the plain one of tests/, and the weights' draws -----------


def _plain_reference():
    path = BENCH.parent / "tests" / "reference_mimo_v2.py"
    spec = importlib.util.spec_from_file_location("reference_mimo_v2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_benchmarks_reference_is_the_plain_one():
    import jax.numpy as jnp

    mimo, plain = _reference(), _plain_reference()
    cfg = mimo.ref_config(TINY["model"])
    weights = mimo.make_weights(cfg, 2 ** 31 + 27)
    rng = np.random.default_rng(27)
    rows = [(list(rng.integers(12, 268, size=n)), scored)
            for n, scored in ((40, 11), (130, 64), (19, 5), (700, 70))]
    got = mimo.score_rows(cfg, weights, rows)
    for (ids, scored), mine in zip(rows, got):
        want = np.asarray(plain.token_logprobs(cfg, weights, jnp.asarray(ids)))
        np.testing.assert_allclose(mine.logprob, want[-scored:], atol=2e-4, rtol=0)
        logits = np.asarray(plain.forward(cfg, weights, jnp.asarray(ids)))
        at = np.arange(len(ids) - scored - 1, len(ids) - 1)
        np.testing.assert_allclose(
            mine.best_logit, logits[at, :268].max(axis=1), atol=2e-4, rtol=0)
        assert list(mine.best_id) == list(logits[at, :268].argmax(axis=1))
    low = mimo.score_rows(cfg, weights, rows[:1], precision="fp8")[0]
    assert np.abs(low.logprob - got[0].logprob).max() > 0.01


def test_the_reference_draws_the_weights_the_program_serves():
    mimo = _reference()
    cell = harness.Cell(name="tiny-mimo-v2", workload={}, config=TINY, traffic={},
                        bench_dir=MIMO, reference=mimo)
    seed = 2 ** 31 + 27
    params = harness.make_params(harness.model_config(cell), seed)
    served = ref.weights_checksum(params)
    own = ref.weights_checksum(mimo.make_weights(mimo.ref_config(cell.model), seed))
    # Three kinds: 9 leaves of the dense one, 12 of the window one (sinks,
    # router, bias, three expert stacks), 11 of the full routed one, and 3
    # beside; the float32 leaves are summed as two 16-bit halves a value.
    assert check.differing_leaves(served, own) == (0, 9 + 12 + 11 + 3)
    assert str(params["layers"]["window_moe"]["router"].dtype) == "float32"
    fewer = {path: s for path, s in own.items() if "attn_sink" not in path}
    assert check.differing_leaves(served, fewer) == (1, 35)


# -- the tiny fixture through the harness ---------------------------------------------


def test_the_fixture_cell_is_correct_and_its_control_is_not():
    line, _ = run_cell("--workload", "tiny-mimo-v2.bon_small", "--seed",
                       "2700000123", "--seconds", "2", "--trace", "1", "--control",
                       bench_dirs=(MIMO, FIXTURE))
    assert line["correct"] is True and line["failed"] == 0
    compared = line["compared"]
    for name in ("matrix_gap", "greedy_gap", "generated", "selection",
                 "truncated", "weights"):
        assert compared[name]["value"] <= compared[name]["limit"]
    assert compared["weights"]["compared"] == 35
    assert line["control_correct"] is False
    control = line["control"]
    assert control["matrix_gap"]["value"] > control["matrix_gap"]["limit"]
    assert control["matrix_gap"]["value"] > 3 * compared["matrix_gap"]["value"]
    # No peak on a CPU and no device plane: the shares are left out, not 0
    # (and BENCHMARK.json lists the four new metrics for the chip's cell).
    for name in ("moe_experts_roofline", "window_attention_roofline",
                 "moe_device_pct", "moe_rows_per_expert_call", "window_mfu_pct"):
        assert name not in line["metrics"]
    assert "engine_wait_ms" in line["metrics"]


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """The parent's program has no such counters and no such scopes: the
    readers return None and do not raise; and what they read where there is
    something."""
    metrics = harness.load_metrics([BENCH])
    rows = metrics["moe_rows_per_expert_call"]
    assert rows["read"]({"deltas": {}}, rows) is None
    key = lambda family, **labels: (family, tuple(sorted(labels.items())))
    deltas = {
        key("backend_moe_assignments_total", backend="tpu", held="held"): {"value": 600.0},
        key("backend_moe_assignments_total", backend="tpu", held="absent"): {"value": 9000.0},
        key("backend_moe_expert_calls_total", backend="tpu"): {"value": 96.0},
    }
    assert rows["read"]({"deltas": deltas}, rows) == {
        "value": 6.25, "held": 600.0, "absent": 9000.0, "expert_calls": 96.0}
    share = metrics["moe_device_pct"]
    assert share["reader"] == "ssm_device"  # it takes its scopes from the file
    no_trace = {"trace": None, "traced": None}
    assert share["read"](no_trace, share) is None
    for name in ("moe_experts_roofline", "window_attention_roofline"):
        metric = metrics[name]
        assert metric["reader"] == "scope_roofline"
        # A cell whose work file has no such term (the two accepted cells).
        dense = harness.load_cell([BENCH], "smollm2-1.7b.bon_sweep")
        assert metric["read"]({"peak": None, "traced": None, "cell": dense}, metric) is None
