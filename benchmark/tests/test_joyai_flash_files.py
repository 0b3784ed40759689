"""The JoyAI-LLM-Flash configuration as files of the benchmark: the file's
published keys and its cut, its reference held to the plain one of ``tests/``,
its weights' draws to the program's, its work count's terms on the published
``model`` block (hand-worked numbers), the new reader, and a tiny fixture
through the harness on the CPU (correct; the float8 control not correct).
The run through the harness is slow (minutes): run by hand, ``pytest
benchmark/tests`` (``pytest tests/`` does not collect this directory).
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
JOYAI = TESTS / "fixture_joyai_flash"
DIRS = [JOYAI, FIXTURE, BENCH]
PUBLISHED = json.loads((BENCH / "configs" / "joyai-llm-flash-l5.json").read_text())
TINY = json.loads((JOYAI / "configs" / "tiny-joyai-flash.json").read_text())
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
CELL = "joyai-llm-flash-l5.bon_sweep"


def _reference():
    return harness.load_module([BENCH], "references", "joyai_flash",
                               harness.REFERENCE_GIVES)


# -- the configuration file ---------------------------------------------------------


def test_the_configuration_file_keeps_every_published_key_but_the_depth():
    catalog = PUBLISHED["published"]
    assert catalog["model_type"] == "joyai_llm_flash" and len(catalog) == 36
    assert PUBLISHED["reduced"] == ["num_hidden_layers"]
    for key, value in catalog.items():  # at the top level too
        if key != "num_hidden_layers":
            assert PUBLISHED[key] == value, key
    assert (catalog["num_hidden_layers"], PUBLISHED["num_hidden_layers"]) == (40, 5)
    assert set(PUBLISHED["held"]) == {"num_hidden_layers"}
    assert "first pipeline stage" in PUBLISHED["stands_for"]
    for key in ("mtp", "weights", "rotary", "shared_expert", "routed_scaling_factor"):
        assert key in PUBLISHED["assumed"], key
    assert "bfloat16" in PUBLISHED["precision"]["cache"]
    model = PUBLISHED["model"]
    same = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
            "n_kv_heads": "num_key_value_heads", "head_dim": "qk_head_dim",
            "ffn_hidden": "intermediate_size", "rope_theta": "rope_theta",
            "rms_eps": "rms_norm_eps", "v_head_dim": "v_head_dim",
            "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
            "qk_nope_dim": "qk_nope_head_dim", "qk_rope_dim": "qk_rope_head_dim",
            "rope_interleave": "rope_interleave", "n_experts": "n_routed_experts",
            "experts_per_token": "num_experts_per_tok",
            "expert_hidden": "moe_intermediate_size",
            "n_shared_experts": "n_shared_experts",
            "routed_scaling_factor": "routed_scaling_factor",
            "vocab_size": "vocab_size", "tie_lm_head": "tie_word_embeddings"}
    for ours, theirs in same.items():  # no width is cut, nor the vocabulary
        assert model[ours] == catalog[theirs], ours
    assert model["experts_held"] == [0, catalog["n_routed_experts"]]  # ep_size 1
    assert model["n_layers"] == PUBLISHED["num_hidden_layers"]
    # The floors of a cut: the leading dense layer once and four of the
    # layers that follow it (the pattern's period is one layer).
    assert model["moe_layer_freq"] == [0] * catalog["first_k_dense_replace"] + [1] * 4
    assert model["hybrid_layer_pattern"] == [0] * 5
    cell = harness.load_cell([BENCH], CELL)
    assert cell.reference.ref_config(cell.model).experts_held == (0, 256)
    config = harness.model_config(cell)  # every key of the block is the program's
    assert [(run.kind.name, run.count) for run in config.layer_runs] == [
        ("latent_dense", 1), ("latent_moe", 4)]
    assert config.kv_bytes_per_token(2) == 5760
    listed = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = [c for c in listed["configs"] if c["name"] == PUBLISHED["name"]][0]
    assert entry["reduced"] == PUBLISHED["reduced"]
    assert entry["source"] == PUBLISHED["source"]
    mine = [m["name"] for m in listed["per_layer"] if m.get("workloads") == [CELL]]
    assert mine == ["latent_attention_roofline", "latent_device_pct",
                    "moe_layer_roofline", "latent_keys_expanded_per_query"]


@pytest.mark.skipif(not CATALOG.exists(), reason="no catalog on this machine")
def test_the_published_block_is_the_catalogs_row():
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines()]
    row = [r for r in rows if r["name"] == "JoyAI-LLM-Flash"][0]
    assert PUBLISHED["published"] == row["config"]
    assert PUBLISHED["source"] == row["source_url"]


def test_the_reference_refuses_what_it_does_not_compute():
    joyai = _reference()
    model = PUBLISHED["model"]
    with pytest.raises(ValueError, match="swa_sink"):
        joyai.ref_config({**model, "swa_sink": True})
    with pytest.raises(ValueError, match="ssm_heads"):
        joyai.ref_config({**model, "ssm_heads": 4})
    with pytest.raises(ValueError, match="tie_lm_head"):
        joyai.ref_config({**model, "tie_lm_head": True})
    with pytest.raises(ValueError, match="rope_interleave"):
        joyai.ref_config({**model, "rope_interleave": False})
    with pytest.raises(ValueError, match="routed_scaling_factor"):
        joyai.ref_config(
            {k: v for k, v in model.items() if k != "routed_scaling_factor"})
    with pytest.raises(ValueError, match="experts_held"):
        joyai.ref_config({**model, "experts_held": [250, 16]})
    with pytest.raises(ValueError, match="zeros"):
        joyai.ref_config({**model, "hybrid_layer_pattern": [0, 1, 0, 0, 0]})
    for name in ("dense", "mimo_v2"):  # and the others refuse its keys
        other = harness.load_module([BENCH], "references", name,
                                    harness.REFERENCE_GIVES)
        with pytest.raises(ValueError, match="kv_lora_rank"):
            other.ref_config(model)


# -- the work count on the published block ------------------------------------------


def test_the_work_files_terms_on_the_published_block():
    model = PUBLISHED["model"]
    work = harness.load_work([BENCH], "joyai_flash", model)  # terms sum to the whole
    assert tuple(work.TERMS) == ("attention_latent", "experts", "head", "matrix")
    assert work.layers_of(model) == {"latent": 5, "dense": 1, "routed": 4}
    d = 2048
    # Attention of any layer: q_a 2,048 x 1,536, q_b 1,536 x 6,144, kv_a
    # 2,048 x 576, o 4,096 x 2,048 (22.15 M), and kv_b 512 x 8,192 (4.19 M).
    assert work.attention_params(model) == 3_145_728 + 9_437_184 + 1_179_648 + 8_388_608
    assert work.kvb_params(model) == 4_194_304
    assert work.expert_params(model) == 3 * d * 768 == 4_718_592
    norms = 2 * d + 1536 + 512
    layer0 = 22_151_168 + 4_194_304 + 3 * d * 7168 + norms
    routed = (22_151_168 + 4_194_304 + d * 256 + 256 + 4_718_592
              + 256 * 4_718_592 + norms)
    assert layer0 == 70_391_808 and routed == 1_239_554_304  # 70.39 M, 1,239.55 M
    assert work.param_count(model) == layer0 + 4 * routed + 2 * 129280 * d + d
    assert work.param_count(model) == 5_558_141_952  # 5,558.1 M
    assert round(work.weight_bytes(model) / 1e9, 2) == 11.12
    assert work.weight_bytes(model) == 2 * 5_558_141_952 + 2 * 4 * (d * 256 + 256)
    # One buffer: 1,152 B a token a layer, 5,760 B over the five.
    assert work.kv_bytes_per_token(model) == work.kv_bytes_per_token(model, "latent")
    assert work.kv_bytes_per_token(model) == 5 * 1152 == 5760
    # One position: 1.23 GFLOP of which the head is 0.53 (43%).
    one = {term: work.span_flops(model, 0, 1, 1, term=term) for term in work.TERMS}
    assert one["head"] == 2 * 129280 * d == 529_530_880
    assert one["matrix"] == 2 * (5 * 22_151_168 + 3 * d * 7168)
    assert one["experts"] == 4 * (2 * d * 256 + 2 * 4_718_592 + 2 * 8 * 4_718_592)
    whole = work.span_flops(model, 0, 1, 1)
    assert round(whole / 1e9, 2) == 1.23 and round(one["head"] / whole, 2) == 0.43
    # Latent attention a layer.  One query over 2,048 cached positions: the
    # absorbed form, 0.15 GFLOP, against 17.2 to expand them first.  A span of
    # 256 after 1,792: the expanded form, 27 GFLOP, against the absorbed 36.
    step = work.latent_attention_flops(model, 2048, 1)
    assert step["absorbed"] == 2 * 512 * 32 * 256 + 2049 * 32 * 2176
    assert step["expanded"] == 2049 * 8_388_608 + 2049 * 32 * 640
    assert round(step["absorbed"] / 1e9, 2) == 0.15
    assert work.span_flops(model, 2048, 1, term="attention_latent") == 5 * step["absorbed"]
    span = work.latent_attention_flops(model, 1792, 256)
    pairs = 256 * 1792 + 256 * 257 // 2
    assert span["expanded"] == 2048 * 8_388_608 + pairs * 32 * 640
    assert span["absorbed"] == 256 * 8_388_608 + pairs * 32 * 2176
    assert (round(span["expanded"] / 1e9), round(span["absorbed"] / 1e9)) == (27, 36)
    assert work.span_flops(model, 1792, 256, term="attention_latent") == 5 * span["expanded"]
    # A decode step of 32 rows over 2,048 cached positions: 256 assignments
    # reach 163 of 256 experts in expectation; 1,152 B a position a layer and
    # W_kvb once a layer; the head and the matrices once.
    assert round(work.experts_hit(model, 32), 1) == 163.3
    assert work.experts_hit(model, 0) == 0 and work.experts_hit(model, 10 ** 4) > 255.99
    every = 4 * 256 * 9_437_184
    assert round(every / 1e9, 2) == 9.66  # what the masked product reads
    assert work.weight_bytes(model, "experts") == every + 4 * (
        9_437_184 + 4 * (d * 256 + 256))
    bytes_ = {term: work.step_bytes(model, 2048, 32, term=term) for term in work.TERMS}
    assert bytes_["attention_latent"] == 2048 * 5760 + 5 * 8_388_608
    reached = 4 * work.experts_hit(model, 32) * 9_437_184
    assert bytes_["experts"] == pytest.approx(
        reached + 4 * 9_437_184 + 4 * 4 * (d * 256 + 256))
    assert round(reached / 1e9, 1) == 6.2
    assert bytes_["head"] == 529_530_880
    # (the rest of the weights: the matrices, the norms, the embedding's table)
    assert bytes_["matrix"] == (2 * (5 * 22_151_168 + 3 * d * 7168)
                                + 2 * (5 * norms + d) + 529_530_880)
    assert sum(bytes_.values()) == pytest.approx(work.step_bytes(model, 2048, 32))
    tiny = harness.load_work(DIRS, "joyai_flash", TINY["model"])
    assert tiny.kv_bytes_per_token(TINY["model"]) == 3 * 40 * 2


# -- the reference against the plain one of tests/, and the weights' draws -----------


def _plain_reference():
    path = BENCH.parent / "tests" / "reference_joyai_flash.py"
    spec = importlib.util.spec_from_file_location("reference_joyai_flash", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_benchmarks_reference_is_the_plain_one():
    import jax.numpy as jnp

    joyai, plain = _reference(), _plain_reference()
    cfg = joyai.ref_config(TINY["model"])
    weights = joyai.make_weights(cfg, 2 ** 31 + 35)
    rng = np.random.default_rng(35)
    rows = [(list(rng.integers(12, 268, size=n)), scored)
            for n, scored in ((40, 11), (130, 64), (19, 5), (700, 70))]
    got = joyai.score_rows(cfg, weights, rows)
    for (ids, scored), mine in zip(rows, got):
        want = np.asarray(plain.token_logprobs(cfg, weights, jnp.asarray(ids)))
        np.testing.assert_allclose(mine.logprob, want[-scored:], atol=2e-4, rtol=0)
        logits = np.asarray(plain.forward(cfg, weights, jnp.asarray(ids)))
        at = np.arange(len(ids) - scored - 1, len(ids) - 1)
        np.testing.assert_allclose(
            mine.best_logit, logits[at, :268].max(axis=1), atol=2e-4, rtol=0)
        assert list(mine.best_id) == list(logits[at, :268].argmax(axis=1))
    low = joyai.score_rows(cfg, weights, rows[:1], precision="fp8")[0]
    assert np.abs(low.logprob - got[0].logprob).max() > 0.01


def test_an_expert_is_sent_its_own_rows_whatever_their_number(monkeypatch):
    """Blocks of 4 rows where an expert has 30: the blocks follow its rows,
    and the result is the one that blocks of 256 give."""
    joyai = _reference()
    cfg = joyai.ref_config(TINY["model"])
    weights = joyai.make_weights(cfg, 5)
    rows = [(list(range(20, 140)), 30)]
    want = joyai.score_rows(cfg, weights, rows)[0]
    monkeypatch.setattr(joyai, "_EXPERT_ROWS", 4)
    joyai._forward.clear_cache()
    got = joyai.score_rows(cfg, weights, rows)[0]
    joyai._forward.clear_cache()
    np.testing.assert_allclose(got.logprob, want.logprob, atol=2e-5, rtol=0)


def test_the_reference_draws_the_weights_the_program_serves():
    joyai = _reference()
    cell = harness.Cell(name="tiny-joyai-flash", workload={}, config=TINY,
                        traffic={}, bench_dir=JOYAI, reference=joyai)
    seed = 2 ** 31 + 35
    params = harness.make_params(harness.model_config(cell), seed)
    served = ref.weights_checksum(params)
    own = ref.weights_checksum(joyai.make_weights(joyai.ref_config(cell.model), seed))
    # Two kinds: 12 leaves of the dense one (9 of attention and norms, 3 of
    # the feed-forward), 17 of the routed one (router, bias, three expert
    # stacks, three of the shared expert), and 3 beside.
    assert check.differing_leaves(served, own) == (0, 12 + 17 + 3)
    assert str(params["layers"]["latent_moe"]["router"].dtype) == "float32"
    fewer = {path: s for path, s in own.items() if "shared_up" not in path}
    assert check.differing_leaves(served, fewer) == (1, 32)


# -- the tiny fixture through the harness ---------------------------------------------


def test_the_fixture_cell_is_correct_and_its_control_is_not():
    line, _ = run_cell("--workload", "tiny-joyai-flash.bon_small", "--seed",
                       "3500000123", "--seconds", "2", "--trace", "1", "--control",
                       bench_dirs=(JOYAI, FIXTURE))
    assert line["correct"] is True and line["failed"] == 0
    compared = line["compared"]
    for name in ("matrix_gap", "greedy_gap", "generated", "selection",
                 "truncated", "weights"):
        assert compared[name]["value"] <= compared[name]["limit"]
    assert compared["weights"]["compared"] == 32
    assert line["control_correct"] is False
    control = line["control"]
    assert control["matrix_gap"]["value"] > control["matrix_gap"]["limit"]
    assert control["matrix_gap"]["value"] > 3 * compared["matrix_gap"]["value"]
    # No peak on a CPU and no device plane: the shares are left out, not 0,
    # and BENCHMARK.json lists the four new metrics for the chip's cell alone.
    for name in ("latent_attention_roofline", "latent_device_pct",
                 "moe_layer_roofline", "latent_keys_expanded_per_query",
                 "window_mfu_pct"):
        assert name not in line["metrics"]
    assert "engine_wait_ms" in line["metrics"]


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """The parent's program has no such counters and no such scopes: the
    readers return None and do not raise; and what they read where there is
    something."""
    metrics = harness.load_metrics([BENCH])
    keys = metrics["latent_keys_expanded_per_query"]
    assert keys["read"]({"deltas": {}}, keys) is None
    key = lambda family, **labels: (family, tuple(sorted(labels.items())))
    deltas = {
        key("backend_mla_queries_total", backend="tpu", form="expanded"): {"value": 400.0},
        key("backend_mla_queries_total", backend="tpu", form="absorbed"): {"value": 90.0},
        key("backend_mla_keys_expanded_total", backend="tpu"): {"value": 3000.0},
    }
    assert keys["read"]({"deltas": deltas}, keys) == {
        "value": 7.5, "keys_expanded": 3000.0, "expanded_queries": 400.0,
        "absorbed_queries": 90.0}
    only_absorbed = {k: v for k, v in deltas.items() if "absorbed" in str(k)}
    assert keys["read"]({"deltas": only_absorbed}, keys) is None
    share = metrics["latent_device_pct"]
    assert share["reader"] == "ssm_device"  # it takes its scopes from the file
    assert share["read"]({"trace": None, "traced": None}, share) is None
    for name, term in (("latent_attention_roofline", "attention_latent"),
                       ("moe_layer_roofline", "experts")):
        metric = metrics[name]
        assert metric["reader"] == "scope_roofline" and metric["term"] == term
        # A cell whose work file has no such term: SmolLM2's has neither.
        dense = harness.load_cell([BENCH], "smollm2-1.7b.bon_sweep")
        assert metric["read"]({"peak": None, "traced": None, "cell": dense}, metric) is None
    assert "moe_shared" in metrics["moe_layer_roofline"]["scopes"]
    assert "moe_shared" not in metrics["moe_experts_roofline"]["scopes"]
