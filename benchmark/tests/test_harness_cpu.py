"""The harness end to end on the CPU at a tiny configuration: the shape of
the last line, a configuration, a mix, a cell and a metric picked up as new
files with no edit to a file that is there, an architecture the dense
reference refuses brought the same way with a reference and a work count of
its own, the float8 control coming out not correct, and ``correct`` coming
out false when the timed path is broken underneath.  Slow (minutes): run by
hand, ``pytest benchmark/tests``.
"""

import argparse
import dataclasses
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

TESTS = pathlib.Path(__file__).resolve().parent
ROOT = TESTS.parents[1]
FIXTURE = TESTS / "fixture"
POST_NORMS = TESTS / "fixture_post_norms"


def launch(*args, bench_dirs=(FIXTURE,)):
    command = [sys.executable, str(ROOT / "benchmark" / "run.py"),
               "--platform", "cpu"]
    for d in bench_dirs:
        command += ["--bench-dir", str(d)]
    return subprocess.run(command + list(args), capture_output=True, text=True,
                          cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          timeout=1500)


def run_cell(*args, bench_dirs=(FIXTURE,)):
    done = launch(*args, bench_dirs=bench_dirs)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stderr


def test_refuses_without_a_tpu():
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         "smollm2-1.7b.bon_sweep", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert done.returncode == 2
    assert done.stdout.strip() == ""


def test_new_files_are_picked_up_and_control_fails(tmp_path):
    """A later PR's configuration, mix, cell and metric, dropped in as files
    of a directory of their own, run with no edit anywhere."""
    for kind in ("configs", "traffic", "workloads", "metrics", "readers"):
        (tmp_path / kind).mkdir()
    config = json.loads((FIXTURE / "configs" / "tiny-dense.json").read_text())
    config["name"] = "tiny-wide"
    config["model"]["vocab_size"] = 384
    (tmp_path / "configs" / "tiny-wide.json").write_text(json.dumps(config))
    mix = json.loads((FIXTURE / "traffic" / "bon_small.json").read_text())
    mix["name"] = "bon_three"
    mix["request"]["params"]["n"] = 3
    (tmp_path / "traffic" / "bon_three.json").write_text(json.dumps(mix))
    cell = json.loads(
        (FIXTURE / "workloads" / "tiny-dense.bon_small.json").read_text())
    cell.update(name="tiny-wide.bon_three", config="tiny-wide", traffic="bon_three")
    (tmp_path / "workloads" / "tiny-wide.bon_three.json").write_text(json.dumps(cell))
    (tmp_path / "metrics" / "requests_answered.json").write_text(json.dumps({
        "name": "requests_answered", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "serve",
        "moves": "statements_per_s", "reader": "answered"}))
    (tmp_path / "readers" / "answered.py").write_text(
        "def read(context, metric):\n    return context['answered']\n")

    line, _ = run_cell("--workload", "tiny-wide.bon_three", "--seed",
                       str(2 ** 31 + 12345), "--seconds", "2", "--trace", "1",
                       "--control", bench_dirs=(tmp_path, FIXTURE))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1
    assert line["metrics"]["requests_answered"]["value"] == line["attempted"]
    for name in ("dispatches_per_statement", "padding_efficiency_pct",
                 "compiles_in_window", "serve_overhead_ms"):
        assert set(line["metrics"][name]) == {"value", "unit"}
    # No peak for a CPU and no device plane in its trace: the shares of a
    # peak and of a roofline are left out, never printed as 0.
    for name in ("window_mfu_pct", "score_matrix_roofline", "device_idle_pct"):
        assert name not in line["metrics"]
    compared = line["compared"]
    for name in ("matrix_gap", "greedy_gap", "generated", "selection",
                 "truncated", "weights"):
        assert compared[name]["value"] <= compared[name]["limit"]
    # Every row of the greedy request is held to the reference, token by token.
    assert compared["greedy_gap"]["compared"] == 3 * 6
    # The control: the reference in float8 in the program's place, through
    # the same comparison, comes out not correct.
    assert line["control_correct"] is False
    control = line["control"]
    assert control["matrix_gap"]["value"] > 3 * compared["matrix_gap"]["value"]
    assert control["matrix_gap"]["value"] > control["matrix_gap"]["limit"]
    assert control["selection"]["value"] == 0


def _tracked_files_changed():
    done = subprocess.run(["git", "status", "--porcelain", "--untracked-files=no"],
                          capture_output=True, text=True, cwd=ROOT)
    return done.stdout if done.returncode == 0 else None  # None: no git here


def test_a_new_architecture_comes_as_files(tmp_path):
    """A later PR's architecture: a configuration the program runs and the
    dense reference refuses, with a reference and a work count of its own, a
    metric on a term of that count, its cell and its mix, all files of a
    directory of their own and no file of the repository touched."""
    before = _tracked_files_changed()
    shutil.copytree(POST_NORMS, tmp_path, dirs_exist_ok=True)
    for kind in ("traffic", "workloads"):
        (tmp_path / kind).mkdir()
    mix = json.loads((FIXTURE / "traffic" / "bon_small.json").read_text())
    mix["name"] = "bon_three"
    mix["request"]["params"]["n"] = 3
    (tmp_path / "traffic" / "bon_three.json").write_text(json.dumps(mix))
    cell = json.loads(
        (FIXTURE / "workloads" / "tiny-dense.bon_small.json").read_text())
    cell.update(name="tiny-post-norms.bon_three", config="tiny-post-norms",
                traffic="bon_three")
    (tmp_path / "workloads" / "tiny-post-norms.bon_three.json").write_text(
        json.dumps(cell))
    run = ("--workload", "tiny-post-norms.bon_three", "--seed", "2600000123",
           "--seconds", "2", "--trace", "1", "--control")

    line, _ = run_cell(*run, bench_dirs=(tmp_path, FIXTURE))
    assert line["correct"] is True and line["failed"] == 0
    compared = line["compared"]
    for name in ("matrix_gap", "greedy_gap", "generated", "selection",
                 "truncated", "weights"):
        assert compared[name]["value"] <= compared[name]["limit"]
    # Both trees hold the post-norms: 11 leaves a layer stack and 3 beside.
    assert compared["weights"]["compared"] == 14
    assert compared["greedy_gap"]["compared"] == 3 * 6
    assert line["control_correct"] is False
    assert (line["control"]["matrix_gap"]["value"]
            > line["control"]["matrix_gap"]["limit"])
    # The metric on the new term is found and read; like the dense rooflines
    # it has no peak to be a share of on a CPU, and is left out, not 0.
    for name in ("post_norm_roofline", "attention_roofline", "window_mfu_pct"):
        assert name not in line["metrics"]
    assert "engine_wait_ms" in line["metrics"]
    assert line["metrics"]["time_to_statement_p50_s"]["value"] > 0
    assert _tracked_files_changed() == before

    # The same configuration held to the dense reference: refused in set-up,
    # the key named, no result line.
    path = tmp_path / "configs" / "tiny-post-norms.json"
    config = json.loads(path.read_text())
    del config["reference"]
    path.write_text(json.dumps(config))
    done = launch(*run, bench_dirs=(tmp_path, FIXTURE))
    assert done.returncode not in (0, 2)
    assert done.stdout.strip() == ""
    assert "use_post_norms" in done.stderr.strip().splitlines()[-1]
    assert "window of" not in done.stderr  # nothing was measured


def test_untraced_line_has_the_end_to_end_metrics():
    line, stderr = run_cell("--workload", "tiny-dense.bon_small", "--seed", "5",
                            "--seconds", "2", "--trace", "0")
    assert set(line["metrics"]) == {"statements_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["correct"] is True
    assert "breakdown" not in line and "control" not in line
    # Every other request of this mix is greedy: none of them is a statement
    # of the rate.
    span = line["phases"]["window_s"]
    own = line["attempted"] // 2 + line["attempted"] % 2
    assert line["metrics"]["statements_per_s"]["value"] == pytest.approx(own / span)
    last = stderr.strip().splitlines()[-1]
    assert last.startswith("correct: True")
    assert "compared greedy_gap" in stderr
    assert "window closed" in stderr


# -- the timed path broken underneath ------------------------------------------


def _run_in_process(workload, seed=11, bench_dirs=(FIXTURE,)):
    sys.path.insert(0, str(ROOT))
    from benchmark import run as bench_run

    args = argparse.Namespace(
        workload=workload, seed=seed, seconds=2.0, trace=0, platform="cpu",
        bench_dir=[str(d) for d in bench_dirs], control=False)
    return bench_run.run(args)


def test_altered_answer_is_not_correct(monkeypatch):
    """Every utility the score matrix returns moved by 0.3."""
    from consensus_tpu.backends.tpu import TPUBackend

    original = TPUBackend.score_matrix

    def altered(self, requests):
        return [dataclasses.replace(r, utilities=r.utilities + 0.3)
                for r in original(self, requests)]

    monkeypatch.setattr(TPUBackend, "score_matrix", altered)
    line = _run_in_process("tiny-dense.bon_small")
    assert line["correct"] is False
    assert line["compared"]["matrix_gap"]["value"] > line["compared"]["matrix_gap"]["limit"]


def test_altered_greedy_token_is_not_correct(monkeypatch):
    """Every generated token id moved up by one where it is produced."""
    from consensus_tpu.backends.tpu import TPUBackend

    original = TPUBackend.generate

    def altered(self, requests):
        out = []
        for result in original(self, requests):
            ids = tuple((t + 1) % 268 for t in result.token_ids)
            out.append(dataclasses.replace(
                result, token_ids=ids, text=self.tokenizer.decode(ids)))
        return out

    monkeypatch.setattr(TPUBackend, "generate", altered)
    line = _run_in_process("tiny-dense.bon_small")
    assert line["correct"] is False
    assert line["compared"]["greedy_gap"]["value"] > line["compared"]["greedy_gap"]["limit"]


def test_altered_sampled_token_is_not_correct(monkeypatch):
    """A sampled row that comes back one token short, and one with an id
    that cannot be decoded."""
    from consensus_tpu.backends.tpu import TPUBackend

    original = TPUBackend.generate

    def altered(self, requests):
        out = []
        for request, result in zip(requests, original(self, requests)):
            if request.temperature != 0.0:
                ids = tuple(result.token_ids[:-1])
                result = dataclasses.replace(result, token_ids=ids)
            out.append(result)
        return out

    monkeypatch.setattr(TPUBackend, "generate", altered)
    line = _run_in_process("tiny-dense.bon_small")
    assert line["correct"] is False
    assert line["compared"]["generated"]["value"] > 0


def _blank_greedy(monkeypatch, where):
    """Greedy decoding that runs into white space alone, on the prompts
    that ``where`` picks: an error to the method."""
    from consensus_tpu.backends.tpu import TPUBackend

    original = TPUBackend.generate

    def blank_when_greedy(self, requests):
        return [dataclasses.replace(result, text="   ")
                if request.temperature == 0.0 and where(request) else result
                for request, result in zip(requests, original(self, requests))]

    monkeypatch.setattr(TPUBackend, "generate", blank_when_greedy)


def test_greedy_request_goes_to_a_scenario_that_answers_it(monkeypatch, tmp_path):
    """Set-up tries the scenarios in the seed's order and the window's greedy
    requests go to the first that answers; the generated tokens are still
    held to the reference."""
    (tmp_path / "traffic").mkdir()
    mix = json.loads((FIXTURE / "traffic" / "bon_small.json").read_text())
    mix["scenarios"]["ids"] = [1, 2]
    (tmp_path / "traffic" / "bon_small.json").write_text(json.dumps(mix))
    sys.path.insert(0, str(ROOT))
    from benchmark.lib import traffic

    first, second = traffic.greedy_scenarios(mix, 11)
    refused = []

    def on_first(request):
        # The first greedy prompt seen is the first scenario's.
        refused.append(request.user_prompt)
        return request.user_prompt == refused[0]

    _blank_greedy(monkeypatch, on_first)
    line = _run_in_process("tiny-dense.bon_small", bench_dirs=(tmp_path, FIXTURE))
    warm = line["setup"]["warm_up"]
    assert warm["greedy_scenario"] == second
    assert warm["requests"]["greedy_refused"][0].startswith(first)
    assert line["failed"] == 0 and line["correct"] is True
    assert line["compared"]["greedy_gap"]["compared"] == 3 * 6


def test_no_scenario_answers_a_greedy_request(monkeypatch):
    """The run stops in set-up and prints no result."""
    _blank_greedy(monkeypatch, lambda request: True)
    with pytest.raises(RuntimeError, match="no scenario answers a greedy"):
        _run_in_process("tiny-dense.bon_small")


def test_no_greedy_token_compared_is_not_correct():
    """A limit whose number the run did not read: a window that finished no
    greedy request holds no generated token to the reference."""
    sys.path.insert(0, str(ROOT))
    from benchmark.lib import check

    limits = {"matrix_gap": 0.05, "greedy_gap": 0.2, "selection": 0}
    numbers = check.Numbers()
    numbers.add("matrix_gap", 0.01)
    numbers.add("selection", 0)
    ok, block = check.verdict(numbers, limits)
    assert ok is False and block["greedy_gap"]["value"] is None
    numbers.add("greedy_gap", 0.0, 48)
    assert check.verdict(numbers, limits)[0] is True
