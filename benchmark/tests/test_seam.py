"""The seam between the harness and what is an architecture's: a
configuration file names its reference and its work count, the harness finds
them as it finds readers, and holds each to what it has to give.  Fast, on
the CPU, no server.

The dense work file is held, bit for bit, to the counts as ``lib/work.py``
and ``lib/scope_work.py`` gave them before the seam (PR 26), written out
again here.
"""

import json
import pathlib
import types

import pytest

from benchmark.lib import check, harness, useful
from benchmark.lib import reference as ref
from benchmark.lib.peaks import peaks

TESTS = pathlib.Path(__file__).resolve().parent
BENCH = TESTS.parent
POST_NORMS = TESTS / "fixture_post_norms"
DIRS = [BENCH]
MODELS = {name: json.loads((BENCH / "configs" / f"{name}.json").read_text())["model"]
          for name in ("smollm2-1.7b", "mistral-7b-v0.3-h16")}
TINY = json.loads((TESTS / "fixture" / "configs" / "tiny-dense.json").read_text())


# -- the counts before the seam ---------------------------------------------------


def old_layer_matmul_params(model):
    d, hd = model["d_model"], model["head_dim"]
    attn = 2 * d * model["n_heads"] * hd + 2 * d * model["n_kv_heads"] * hd
    return attn + 3 * d * model["ffn_hidden"]


def old_weight_bytes(model):
    d = model["d_model"]
    total = (model["n_layers"] * (old_layer_matmul_params(model) + 2 * d)
             + model["vocab_size"] * d + d)
    if not model["tie_lm_head"]:
        total += model["vocab_size"] * d
    return total * 2


def old_span_flops(model, start, count, with_head=0):
    layers = model["n_layers"] * old_layer_matmul_params(model)
    context = count * start + count * (count + 1) // 2
    attention = (4 * context * model["n_heads"] * model["head_dim"]
                 * model["n_layers"])
    head = 2 * with_head * model["vocab_size"] * model["d_model"]
    return 2.0 * layers * count + attention + head


def old_step_bytes(model, cached_positions):
    kv = 2 * model["n_layers"] * model["n_kv_heads"] * model["head_dim"] * 2
    return float(old_weight_bytes(model) + cached_positions * kv)


def old_terms(model, start, count, with_head, cached):
    """(FLOPs, bytes of a decode step) by term, as ``scope_work`` cut them:
    the same functions over a model without widths, or without layers."""
    attention = {**model, "d_model": 0, "ffn_hidden": 0}
    head = {**model, "n_layers": 0}
    flops = {"attention": old_span_flops(attention, start, count),
             "head": old_span_flops(head, 0, 0, with_head)}
    flops["matrix"] = (old_span_flops(model, 0, count)
                       - old_span_flops(attention, 0, count))
    bytes_ = {"attention": old_step_bytes(attention, cached),
              "head": float(model["vocab_size"] * model["d_model"] * 2)}
    bytes_["matrix"] = old_step_bytes(model, 0) - bytes_["head"]
    return flops, bytes_


TABLE = [(start, count, rows) for start in (0, 1, 9, 700, 2000, 3071)
         for count in (1, 3, 50, 2000) for rows in (1, 8, 32, 160)]


@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_dense_work_file_gives_the_counts_it_gave_before(name):
    m = MODELS[name]
    work = harness.load_work(DIRS, "dense", m)
    assert tuple(work.TERMS) == ("attention", "head", "matrix")
    assert work.weight_bytes(m) == old_weight_bytes(m)
    assert work.param_count(m) * 2 == old_weight_bytes(m)
    for start, count, rows in TABLE:
        for with_head in (0, 1, count):
            assert work.span_flops(m, start, count, with_head) == \
                old_span_flops(m, start, count, with_head)
        # ``rows`` is new: positions that rows share are read once, so a
        # dense model's step reads the same bytes whatever decodes in it.
        assert work.step_bytes(m, start, rows) == old_step_bytes(m, start)
        flops, bytes_ = old_terms(m, start, count, count, start)
        for term in work.TERMS:
            assert work.span_flops(m, start, count, count, term=term) == flops[term]
            assert work.step_bytes(m, start, rows, term=term) == bytes_[term]


def test_a_configuration_without_the_keys_gets_the_dense_files():
    for bench_dir, name in ((BENCH, "smollm2-1.7b"), (BENCH, "mistral-7b-v0.3-h16"),
                            (TESTS / "fixture", "tiny-dense")):
        config = harness.load_json(bench_dir / "configs" / f"{name}.json")
        assert "reference" not in config and "work" not in config
    cell = harness.load_cell([TESTS / "fixture", BENCH], "tiny-dense.bon_small")
    assert cell.reference.__file__ == str(BENCH / "references" / "dense.py")
    assert cell.work.__file__ == str(BENCH / "work" / "dense.py")
    for model in MODELS.values():  # both dense files still load
        assert cell.reference.ref_config(model).d_model == model["d_model"]


# -- what is refused when it is loaded ----------------------------------------------


WORK_FILE = '''
TERMS = ("a", "b")
def param_count(model): return 10
def weight_bytes(model, term=None): return {None: 20, "a": 5, "b": %(b)s}[term]
def span_flops(model, start, count, with_head=0, term=None):
    return {None: 3.0, "a": 1.0, "b": 2.0}[term] * count
def step_bytes(model, cached_positions, rows, term=None):
    return {None: 20.0 + rows, "a": 5.0 + rows, "b": 15.0}[term]
'''


def test_a_work_file_whose_terms_do_not_sum_is_refused(tmp_path):
    (tmp_path / "work").mkdir()
    (tmp_path / "work" / "sums.py").write_text(WORK_FILE % {"b": 15})
    (tmp_path / "work" / "short.py").write_text(WORK_FILE % {"b": 14})
    (tmp_path / "work" / "unnamed.py").write_text(
        (WORK_FILE % {"b": 15}).replace('TERMS = ("a", "b")', "TERMS = ()"))
    (tmp_path / "work" / "lacking.py").write_text(
        (WORK_FILE % {"b": 15}).replace("def step_bytes", "def step_bites"))
    assert harness.load_work([tmp_path], "sums", {}).TERMS == ("a", "b")
    with pytest.raises(ValueError, match="weight_bytes sum to 19"):
        harness.load_work([tmp_path], "short", {})
    with pytest.raises(ValueError, match="names no TERMS"):
        harness.load_work([tmp_path], "unnamed", {})
    with pytest.raises(ValueError, match="gives no step_bytes"):
        harness.load_work([tmp_path], "lacking", {})
    with pytest.raises(FileNotFoundError, match="work/absent.py"):
        harness.load_work([tmp_path], "absent", {})


@pytest.mark.parametrize("lacking", harness.REFERENCE_GIVES)
def test_a_reference_file_that_lacks_a_function_is_refused(tmp_path, lacking):
    for kind in ("configs", "workloads", "traffic", "references"):
        (tmp_path / kind).mkdir()
    (tmp_path / "references" / "partial.py").write_text("".join(
        f"def {name}(*args, **kwargs): return None\n"
        for name in harness.REFERENCE_GIVES if name != lacking))
    (tmp_path / "configs" / "tiny-partial.json").write_text(
        json.dumps({**TINY, "name": "tiny-partial", "reference": "partial"}))
    (tmp_path / "workloads" / "tiny-partial.w.json").write_text(
        json.dumps({"name": "tiny-partial.w", "config": "tiny-partial",
                    "traffic": "bon_small", "chips": 1}))
    with pytest.raises(ValueError, match=f"partial.py gives no {lacking}"):
        harness.load_cell([tmp_path, TESTS / "fixture", BENCH], "tiny-partial.w")


@pytest.mark.parametrize("key, value", [
    ("ssm_multipliers", [0.25, 0.5]), ("mamba_d_state", 256),
    ("sample_vocab", 268), ("use_flash_attention", True)])
def test_the_dense_reference_refuses_a_key_it_does_not_know(key, value):
    dense = harness.load_module(DIRS, "references", "dense", harness.REFERENCE_GIVES)
    with pytest.raises(ValueError, match=key):
        dense.ref_config({**MODELS["smollm2-1.7b"], key: value})


@pytest.mark.parametrize("key, value", [
    ("use_post_norms", True), ("attn_softcap", 50.0), ("sliding_window", 4096),
    ("local_layer_pattern", [True, False]), ("rope_scaling", [8.0, 1.0, 4.0, 8192])])
def test_the_dense_reference_refuses_what_it_knows_and_does_not_compute(key, value):
    dense = harness.load_module(DIRS, "references", "dense", harness.REFERENCE_GIVES)
    with pytest.raises(ValueError, match=key):
        dense.ref_config({**MODELS["smollm2-1.7b"], key: value})


# -- the served tree against the reference's -----------------------------------------


def test_weights_counts_leaves_that_either_tree_lacks():
    own = {"['embed']": 7, "['layers']['wq']": 9}
    assert check.differing_leaves(dict(own), own) == (0, 2)
    assert check.differing_leaves({**own, "['layers']['wq']": 8}, own) == (1, 2)
    # A leaf the program serves and the reference never drew, and the other
    # way round: each differs.
    assert check.differing_leaves({**own, "['layers']['a_log']": 3}, own) == (1, 3)
    assert check.differing_leaves({"['embed']": 7}, own) == (1, 2)


@pytest.mark.parametrize("bench_dir, name, leaves", [
    (TESTS / "fixture", "tiny-dense", 12), (POST_NORMS, "tiny-post-norms", 14)])
def test_the_reference_draws_the_weights_the_program_serves(bench_dir, name, leaves):
    """The program's ``init_params`` through ``harness.make_params`` against
    the configuration's reference file, leaf by leaf; and the same served
    tree against a reference that lacks or adds a leaf."""
    dirs = [bench_dir, BENCH]
    config = harness.load_json(bench_dir / "configs" / f"{name}.json")
    cell = harness.Cell(
        name=name, workload={}, config=config, traffic={}, bench_dir=bench_dir,
        reference=harness.load_module(dirs, "references",
                                      config.get("reference", "dense"),
                                      harness.REFERENCE_GIVES))
    seed = 2 ** 31 + 26
    served = ref.weights_checksum(
        harness.make_params(harness.model_config(cell), seed))
    weights = cell.reference.make_weights(
        cell.reference.ref_config(cell.model), seed)
    own = ref.weights_checksum(weights)
    assert check.differing_leaves(served, own) == (0, leaves)
    fewer = {path: sums for path, sums in own.items() if "ffn_norm" not in path}
    assert check.differing_leaves(served, fewer)[0] == len(own) - len(fewer) >= 1


# -- the program's configuration -----------------------------------------------------


def test_every_list_of_a_model_block_reaches_the_program_as_a_tuple():
    cell = types.SimpleNamespace(config={"name": "tiny"}, model={
        **TINY["model"], "local_layer_pattern": [True, False],
        "rope_scaling": [8.0, 1.0, 4.0, 8192]})
    config = harness.model_config(cell)
    assert config.local_layer_pattern == (True, False)
    assert config.rope_scaling == (8.0, 1.0, 4.0, 8192)
    hash(config)
    assert harness._hashable([[1, [2.0, 3.0]], "a"]) == ((1, (2.0, 3.0)), "a")
    # A key the program lacks is an error at set-up, not a default.
    cell.model["ssm_multipliers"] = [0.25, 0.5]
    with pytest.raises(TypeError, match="ssm_multipliers"):
        harness.model_config(cell)


# -- a metric on a term ----------------------------------------------------------------


def test_a_metric_on_a_term_the_cells_work_file_lacks_reads_nothing():
    """Before anything of the trace is looked at: the dense cell has no
    ``post_norm`` term, the post-norm cell has the dense three and its own."""
    metric = json.loads(
        (POST_NORMS / "metrics" / "post_norm_roofline.json").read_text())
    read = harness.load_module(DIRS, "readers", metric["reader"], ("read",)).read
    model = json.loads(
        (POST_NORMS / "configs" / "tiny-post-norms.json").read_text())["model"]
    own = harness.load_work([POST_NORMS, BENCH], "post_norms", model)
    dense = harness.load_work(DIRS, "dense", model)
    assert tuple(own.TERMS) == tuple(dense.TERMS) + ("post_norm",)
    context = {"peak": peaks("TPU v5 lite"), "traced": [1.0, 2.0], "calls": [],
               "cell": types.SimpleNamespace(name="c", work=dense)}
    assert read(context, metric) is None  # and no key of the trace was asked for

    request = types.SimpleNamespace(chat=False, user_prompt="p" * 10,
                                    system_prompt=None, seed=1)
    calls = [{"kind": "generate", "start": 0.0, "end": 1.0, "requests": [request],
              "results": [types.SimpleNamespace(token_ids=(5, 5, 5))]}]
    whole = useful.tally(own, model, calls, 0.0, 1.0)["generate"]
    parts = [useful.tally(own, model, calls, 0.0, 1.0, term=term)["generate"]
             for term in own.TERMS]
    for key in ("flops", "bytes"):
        assert sum(part[key] for part in parts) == pytest.approx(whole[key], rel=1e-12)
    # 2 norms x 2 layers x 64 values: 3 FLOPs a value at each of the prompt's
    # 11 and the 3 generated positions, 512 bytes at the prefill and 3 steps.
    assert parts[-1]["flops"] == 3.0 * 256 * (11 + 3)
    assert parts[-1]["bytes"] == 512.0 * 4
    assert whole["flops"] == useful.tally(dense, model, calls, 0.0, 1.0)[
        "generate"]["flops"] + parts[-1]["flops"]


# -- BENCHMARK.json against the files it names -----------------------------------------


def test_every_listed_metric_has_a_file_that_says_the_same():
    listed = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    end_to_end = {m["name"] for m in listed["end_to_end"]}
    assert end_to_end == {"statements_per_s", "setup_s"}
    for entry in listed["per_layer"]:
        own = json.loads((BENCH / "metrics" / f"{entry['name']}.json").read_text())
        for key in ("unit", "better", "source", "layer", "moves"):
            assert own[key] == entry[key], (entry["name"], key)
        assert entry["moves"] in end_to_end
        assert (BENCH / "readers" / f"{own['reader']}.py").exists()
    assert len(listed["per_layer"]) == len(list((BENCH / "metrics").glob("*.json")))


def test_the_median_time_to_a_statement_is_read_from_the_requests():
    """A per-layer metric since PR 26: the same number ``end_to_end`` gives,
    over the mix's own requests alone."""
    metric = json.loads(
        (BENCH / "metrics" / "time_to_statement_p50_s.json").read_text())
    read = harness.load_module(DIRS, "readers", metric["reader"], ("read",)).read
    mix = {"request": {"params": {"n": 32}}}
    own, greedy = {"params": {"n": 32}}, {"params": {"n": 32, "temperature": 0.0}}
    body = {"statement": "s", "utilities": {"a": 1}, "welfare": {"w": 1}}

    def sent(payload, start, seconds):
        return types.SimpleNamespace(
            payload=payload, sent=start, done=start + seconds, seconds=seconds,
            status=200, body=body, error=None)

    requests = [sent(own, 0.0, 10.0), sent(greedy, 0.0, 2.0), sent(own, 1.0, 14.0),
                sent(own, 2.0, 18.0)]
    context = {"cell": types.SimpleNamespace(traffic=mix), "sent": requests}
    reading = read(context, metric)
    assert reading["value"] == 14.0 and reading["requests"] == 3
    assert reading["rate_x_median"] == pytest.approx(3 / 20.0 * 14.0)
    assert read({**context, "sent": requests[1:2]}, metric) is None
