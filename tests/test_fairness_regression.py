"""Fairness regression suite (PR 18): welfare-gap tables pinned per
adversarial corpus family, through the PR 10 score-matrix path.

Goldens live under tests/golden/fairness/ and are regenerated with
``scripts/gen_fairness_goldens.py``.  The fake-backend scores are exact
(blake2b-deterministic), but the welfare rules sum them in float32 on the
device, so a table's sixth significant digit moves with the toolchain:
every table is held with ``_assert_close``.  The tiny-gemma2 tables come
from PRNGKey(0) random weights, which are other weights under another
default PRNG (``jax_threefry_partitionable`` went on by default in JAX
0.5): regenerate them when the toolchain changes what ``init_params``
draws.  The adversarial families make the rules disagree for a
*structural* reason: blocs/sybils repeat opinion text verbatim, so
candidate utilities repeat per clone — multiplicity moves the
utilitarian sum but never the egalitarian min.
"""

import json
import pathlib
import urllib.error
import urllib.request

import pytest

from consensus_tpu.backends.fake import FakeBackend
from consensus_tpu.data.scenarios.fairness import (
    BIG_SLATE,
    RULES,
    separated_families,
    welfare_gap_table,
)
from consensus_tpu.data.scenarios.registry import resolve_scenario_ref

GOLDEN_DIR = pathlib.Path(__file__).resolve().parent / "golden" / "fairness"

#: Pinned fake-backend scenarios (mirrors scripts/gen_fairness_goldens.py).
FAKE_SCENARIOS = (
    "polarized-0004",
    "sybil-0006",
    "holdout-0005",
    "contradictory-0003",
    "paraphrase-0004",
    "polarized-500",
)
FAKE_TABLE_KWARGS = {"n_candidates": 6, "max_tokens": 16, "seed": 0}

TINY_SCENARIOS = ("polarized-0004", "polarized-500")


def _golden(name):
    path = GOLDEN_DIR / f"{name}.json"
    assert path.exists(), (
        f"missing golden {path}; run scripts/gen_fairness_goldens.py")
    return json.loads(path.read_text())


def _assert_close(got, want, path="table", rel=1e-4, abs_tol=1e-6):
    """Structural equality with float tolerance: XLA's threaded CPU
    reductions make 500-term float32 sums run-to-run (and build-to-build)
    different in the last ulps, so no table can be compared bit-exactly.
    Winners, flags and counts are compared exactly."""
    if isinstance(want, dict):
        assert isinstance(got, dict) and set(got) == set(want), path
        for key in want:
            _assert_close(got[key], want[key], f"{path}.{key}", rel, abs_tol)
    elif isinstance(want, (list, tuple)):
        assert len(got) == len(want), path
        for i, (g, w) in enumerate(zip(got, want)):
            _assert_close(g, w, f"{path}[{i}]", rel, abs_tol)
    elif isinstance(want, float) and not isinstance(want, bool):
        assert got == pytest.approx(want, rel=rel, abs=abs_tol), (
            f"{path}: {got} != {want}")
    else:
        assert got == want, f"{path}: {got!r} != {want!r}"


# ---------------------------------------------------------------------------
# Fake backend: pinned tables + the rule-separation acceptance bar
# ---------------------------------------------------------------------------


class TestFakeWelfareGaps:
    @pytest.fixture(scope="class")
    def backend(self):
        return FakeBackend()

    @pytest.fixture(scope="class")
    def tables(self, backend):
        return {
            sid: welfare_gap_table(
                backend, resolve_scenario_ref(f"corpus:v2:{sid}"),
                **FAKE_TABLE_KWARGS)
            for sid in FAKE_SCENARIOS
        }

    @pytest.mark.parametrize("sid", FAKE_SCENARIOS)
    def test_table_matches_golden(self, tables, sid):
        _assert_close(tables[sid], _golden(f"fake_{sid}"))

    def test_rules_separated_on_at_least_three_families(self, tables):
        families = separated_families(tables.values(), channel="mean_prob")
        assert len(families) >= 3, families

    def test_three_way_separation_on_at_least_three_families(self, tables):
        # Stronger than pairwise: all THREE rules pick distinct winners.
        three_way = sorted({
            t["family"] for t in tables.values()
            if len(set(t["channels"]["mean_prob"]["winners"].values()))
            == len(RULES)
        })
        assert len(three_way) >= 3, three_way

    def test_gaps_are_nonnegative_and_zero_for_egalitarian(self, tables):
        for table in tables.values():
            for channel in table["channels"].values():
                gaps = channel["gaps"]
                assert gaps["egalitarian_price_of_egalitarian"] == 0.0
                assert all(v >= 0.0 for v in gaps.values()), gaps

    def test_big_scenario_covers_500_agents(self, tables):
        table = tables["polarized-500"]
        assert table["n_agents"] == 500
        assert table["family"] == "polarized"
        assert table["channels"]["mean_prob"]["rules_separated"]


# ---------------------------------------------------------------------------
# tiny-gemma2: fused score-matrix path, 500 agents chunked under budget
# ---------------------------------------------------------------------------


class TestTinyGemmaWelfareGaps:
    @pytest.fixture(scope="class")
    def backend(self):
        from consensus_tpu.backends.tpu import TPUBackend

        # The corpus agent prompts tokenize to ~670 ids under the tiny
        # near-char-level tokenizer; max_context must cover prefix +
        # candidate or _score_matrix_fused falls back.
        return TPUBackend(model="tiny-gemma2", dtype="float32",
                          max_context=1024)

    @pytest.mark.parametrize("sid", TINY_SCENARIOS)
    def test_table_matches_golden(self, backend, sid):
        scenario = resolve_scenario_ref(f"corpus:v2:{sid}")
        before = backend.matrix_stats["chunks"]
        table = welfare_gap_table(backend, scenario, candidates=BIG_SLATE)
        table["matrix_chunks"] = backend.matrix_stats["chunks"] - before
        _assert_close(table, _golden(f"tiny-gemma2_{sid}"))

    def test_500_agents_take_the_fused_path_chunked(self, backend):
        golden = _golden("tiny-gemma2_polarized-500")
        assert golden["matrix_path"] == "fused"
        assert golden["matrix_chunks"] > 1  # under the HBM session budget
        assert golden["n_agents"] == 500


# ---------------------------------------------------------------------------
# End-to-end: the 500-agent scenario served through the DecodeEngine
# ---------------------------------------------------------------------------


class TestBigScenarioServe:
    def test_polarized_500_served_via_scenario_ref(self):
        from consensus_tpu.obs.metrics import Registry
        from consensus_tpu.serve import create_server

        # The 500-opinion reference prompt needs more KV pages than the
        # default 1024-page pool; size the pool for the big scenario the
        # same way a real deployment would.
        instance = create_server(
            backend=FakeBackend(), port=0, max_inflight=2,
            max_queue_depth=8, registry=Registry(), engine=True,
            engine_options={"num_pages": 16384},
        ).start()
        try:
            request = urllib.request.Request(
                instance.base_url + "/v1/consensus",
                data=json.dumps({
                    "scenario": "corpus:v2:polarized-500",
                    "method": "best_of_n",
                    "params": {"n": 2, "max_tokens": 16},
                    "seed": 7,
                    "evaluate": False,
                    "request_id": "big-1",
                }).encode("utf-8"),
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            with urllib.request.urlopen(request, timeout=120.0) as response:
                assert response.status == 200
                body = json.loads(response.read().decode())
        finally:
            instance.stop()
        assert body["request_id"] == "big-1"
        assert body["statement"].strip()
        # The server resolved the 500-agent scenario itself: the request
        # payload above carries no opinions, only the registry ref.
        scenario = resolve_scenario_ref("corpus:v2:polarized-500")
        assert len(scenario["agent_opinions"]) == 500
