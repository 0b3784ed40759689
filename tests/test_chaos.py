"""Chaos suite: the ISSUE-4 acceptance proofs, pytest-marked ``chaos``.

* **Determinism under faults**: a seeded transient-fault sweep whose fault
  count stays under the retry budget produces a ``results.csv``
  byte-identical to a fault-free run, and its ``metrics.json`` shows
  ``supervisor_retries_total > 0`` (the faults really happened and were
  really absorbed).
* **Crash-safe resume**: a sweep killed after K of N rows, resumed with
  ``resume: true``, re-executes only the N-K missing rows and merges to a
  byte-identical ``results.csv``.
* **Structured error rows**: ``on_error: skip`` converts a permanent
  backend loss into an error row carrying the typed exception name.
* **Poison-row isolation**: one NaN row inside a merged device batch fails
  only the session that owns it (typed ``BackendIntegrityError``); sibling
  sessions' results stay bit-identical to a clean run.
"""

import json

import pytest

from consensus_tpu.backends import FakeBackend, ScoreRequest, wrap_backend
from consensus_tpu.backends.base import BackendIntegrityError
from consensus_tpu.backends.batching import BatchingBackend
from consensus_tpu.experiment import Experiment, run_config_hash
from consensus_tpu.utils.io_atomic import read_journal

pytestmark = pytest.mark.chaos

ISSUE = "Should the town build a new park?"
OPINIONS = {"alice": "Yes, green space matters.", "bob": "Too expensive."}


def base_config(tmp_path, sub, **overrides):
    config = {
        "experiment_name": "chaos",
        "seed": 42,
        "num_seeds": 2,
        "backend": "fake",
        "models": {"generation_model": "fake-lm"},
        "scenario": {"issue": ISSUE, "agent_opinions": dict(OPINIONS)},
        "methods_to_run": ["zero_shot", "best_of_n"],
        "best_of_n": {"n": [2, 3], "max_tokens": 16},
        "output_dir": str(tmp_path / sub),
        # Wall-clock columns zeroed so byte-identity proofs are meaningful.
        "deterministic_artifacts": True,
    }
    config.update(overrides)
    return config


def run_bytes(experiment):
    experiment.run()
    return (experiment.run_dir / "results.csv").read_bytes()


class TestChaosDeterminism:
    def test_faulted_sweep_byte_identical_and_retries_recorded(self, tmp_path):
        clean = run_bytes(Experiment(base_config(tmp_path, "clean")))
        # Sequential execution pins per-op call indices, so the pinned
        # transient faults deterministically hit real calls.
        plan = {"seed": 7, "faults": [
            {"kind": "transient_error", "op": "generate", "call_index": 0},
            {"kind": "timeout_error", "op": "score", "call_index": 1},
        ]}
        chaotic = Experiment(base_config(
            tmp_path, "chaos", fault_plan=plan, concurrent_execution=False))
        assert run_bytes(chaotic) == clean
        metrics = json.loads((chaotic.run_dir / "metrics.json").read_text())
        families = metrics["metrics"]["families"]
        retries = sum(
            s["value"]
            for s in families["supervisor_retries_total"]["series"])
        injected = sum(
            s["value"] for s in families["faults_injected_total"]["series"])
        assert retries > 0 and injected > 0

    def test_concurrent_faulted_sweep_byte_identical(self, tmp_path):
        clean = run_bytes(Experiment(base_config(tmp_path, "clean")))
        plan = {"seed": 11, "faults": [
            {"kind": "transient_error", "op": "*", "rate": 0.2}]}
        chaotic = Experiment(base_config(tmp_path, "chaos", fault_plan=plan))
        assert run_bytes(chaotic) == clean


class TestResume:
    def test_killed_sweep_resumes_and_merges_byte_identical(self, tmp_path):
        clean = run_bytes(Experiment(base_config(tmp_path, "clean")))

        # "Kill" after K rows: a permanent device loss at the 3rd
        # sequential generate call with on_error=fail aborts the sweep
        # mid-flight (faults unsupervised so nothing absorbs the loss).
        crash_config = base_config(
            tmp_path, "crash",
            fault_plan={"faults": [
                {"kind": "device_lost", "op": "generate", "call_index": 2}]},
            supervisor=False,
            on_error="fail",
            concurrent_execution=False,
        )
        crashed = Experiment(crash_config)
        with pytest.raises(Exception):
            crashed.run()
        journaled = read_journal(crashed.run_dir / "journal.jsonl")
        completed = len(journaled)
        assert 0 < completed < 6  # mid-sweep, not empty, not done

        # Resume with a healthy backend: only the missing rows execute.
        resumed = Experiment(base_config(tmp_path, "crash", resume=True))
        assert resumed.run_dir == crashed.run_dir
        assert run_bytes(resumed) == clean
        after = read_journal(resumed.run_dir / "journal.jsonl")
        assert len(after) == 6  # N total: K reused + (N-K) new appends
        reexecuted = {r["run_index"] for r in after[completed:]}
        original = {r["run_index"] for r in after[:completed]}
        assert not (reexecuted & original)  # nothing ran twice

    def test_fully_journaled_resume_executes_nothing(self, tmp_path):
        first = Experiment(base_config(tmp_path, "full"))
        clean = run_bytes(first)
        resumed = Experiment(base_config(tmp_path, "full", resume=True))
        assert run_bytes(resumed) == clean
        # No new journal appends: every row came from the journal.
        assert len(read_journal(resumed.run_dir / "journal.jsonl")) == 6

    def test_resume_without_prior_run_starts_fresh(self, tmp_path):
        experiment = Experiment(base_config(tmp_path, "fresh", resume=True))
        assert not experiment.resumed
        assert len(experiment.run()) == 6

    def test_journal_key_is_stable_and_seed_free(self):
        assert run_config_hash({"n": 2, "seed": 1}) == \
            run_config_hash({"n": 2, "seed": 9})
        assert run_config_hash({"n": 2}) != run_config_hash({"n": 3})


class TestOnErrorPolicies:
    def test_skip_records_structured_error_row(self, tmp_path):
        frame = Experiment(base_config(
            tmp_path, "skip",
            num_seeds=1,
            methods_to_run=["zero_shot"],
            fault_plan={"faults": [
                {"kind": "device_lost", "op": "*", "call_index": 0}]},
            on_error="skip",
        )).run()
        assert len(frame) == 1
        row = frame.iloc[0]
        assert row["statement"] == ""
        assert row["error_message"].startswith("BackendLostError")
        assert row["evaluation_status"] == "pending"

    def test_fail_aborts_the_sweep(self, tmp_path):
        experiment = Experiment(base_config(
            tmp_path, "fail",
            num_seeds=1,
            methods_to_run=["zero_shot"],
            fault_plan={"faults": [
                {"kind": "device_lost", "op": "*", "call_index": 0}]},
            on_error="fail",
        ))
        with pytest.raises(Exception):
            experiment.run()

    def test_retry_policy_reruns_the_row(self, tmp_path):
        # Fault exhausts the supervisor budget (rate 1.0 on the first
        # row's generate calls is too blunt) — instead fail the row once
        # at the experiment level via an unsupervised transient fault.
        frame = Experiment(base_config(
            tmp_path, "retry",
            num_seeds=1,
            methods_to_run=["zero_shot"],
            concurrent_execution=False,
            fault_plan={"faults": [
                {"kind": "transient_error", "op": "generate",
                 "call_index": 0}]},
            supervisor=False,
            on_error="retry",
        )).run()
        row = frame.iloc[0]
        assert row["error_message"] == ""
        assert row["statement"]

    def test_invalid_policy_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="on_error"):
            Experiment(base_config(tmp_path, "bad", on_error="explode"))


class TestPoisonRowIsolation:
    def test_one_nan_row_fails_one_session_siblings_bit_identical(self):
        # Three sessions' score calls merge into ONE device batch; the
        # fault poisons merged row 1 only.
        plan = {"faults": [
            {"kind": "nan_logprobs", "op": "score", "call_index": 0,
             "row_index": 1}]}
        from consensus_tpu.obs.metrics import Registry
        registry = Registry()
        stack = wrap_backend(
            FakeBackend(), fault_plan=plan, registry=registry)
        batching = BatchingBackend(
            stack, flush_ms=50.0, expected_sessions=3, registry=registry,
            engine=False)

        reqs = [ScoreRequest(context="ctx", continuation=f"row {i}")
                for i in range(3)]
        clean = FakeBackend().score(reqs)
        results = {}

        import threading

        def worker(i):
            with batching.session():
                try:
                    results[i] = batching.score([reqs[i]])[0]
                except Exception as exc:  # noqa: BLE001 - recorded for assert
                    results[i] = exc

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)

        assert batching.batch_counts["score"] == 1  # really merged
        # Merged row 1 belongs to whichever session arrived second in the
        # queue — exactly one session fails, typed; siblings bit-identical.
        failed = [i for i in range(3) if isinstance(results[i], Exception)]
        assert len(failed) == 1
        assert isinstance(results[failed[0]], BackendIntegrityError)
        for i in range(3):
            if i not in failed:
                assert results[i].logprobs == clean[i].logprobs
        assert 'batching_row_errors_total{kind="score"} 1' in \
            registry.to_prometheus()


class TestEngineChaos:
    """ISSUE-7 satellite: the chaos invariants hold through the
    continuous-batching engine path — faults surface and resolve via
    ``DecodeEngine.submit``, not just the legacy flush merge."""

    @staticmethod
    def _engine_stack(plan, registry, **engine_options):
        stack = wrap_backend(
            FakeBackend(), fault_plan=plan, supervise=True,
            registry=registry)
        options = {"slots": 4, "num_pages": 512}
        options.update(engine_options)
        return BatchingBackend(
            stack, engine=True, engine_options=options, registry=registry)

    def test_transient_fault_absorbed_below_engine_submit(self):
        from consensus_tpu.obs.metrics import Registry

        plan = {"seed": 7, "faults": [
            {"kind": "transient_error", "op": "score", "call_index": 0}]}
        registry = Registry()
        batching = self._engine_stack(plan, registry)
        reqs = [ScoreRequest(context="ctx", continuation=f"row {i}")
                for i in range(3)]
        try:
            results = batching.score(reqs)
        finally:
            batching.close()
        clean = FakeBackend().score(reqs)
        assert [r.logprobs for r in results] == [r.logprobs for r in clean]
        retries = sum(
            s["value"] for s in registry.snapshot()["families"]
            ["supervisor_retries_total"]["series"])
        assert retries > 0

    def test_nan_poison_row_fails_one_engine_session_siblings_identical(self):
        # Three sessions submit one score row each into the engine; the
        # fault poisons merged row 1 of the first device batch.  The
        # supervisor bisects, the engine slices the PartialBatchError per
        # item: exactly one session fails, typed, siblings bit-identical.
        from consensus_tpu.obs.metrics import Registry

        plan = {"faults": [
            {"kind": "nan_logprobs", "op": "score", "call_index": 0,
             "row_index": 1}]}
        registry = Registry()
        # The fault needs a row 1 in the first device batch, so the three
        # calls have to merge into one dispatch whatever the threads'
        # timing: the engine is stepped by hand, once all three are queued.
        batching = self._engine_stack(plan, registry, auto_start=False)
        engine = batching.engine
        reqs = [ScoreRequest(context="ctx", continuation=f"row {i}")
                for i in range(3)]
        clean = FakeBackend().score(reqs)
        results = {}

        import threading

        def worker(i):
            with batching.session():
                try:
                    results[i] = batching.score([reqs[i]])[0]
                except Exception as exc:  # noqa: BLE001 - asserted below
                    results[i] = exc

        threads = [threading.Thread(target=worker, args=(i,), daemon=True)
                   for i in range(3)]
        try:
            for t in threads:
                t.start()
            # ``submit`` notifies this condition once its call is queued.
            with engine._work:
                assert engine._work.wait_for(
                    lambda: engine._queue_depth() == 3, timeout=30)
            engine.run_iteration()
            for t in threads:
                t.join(timeout=30)
        finally:
            batching.close()
        assert not any(t.is_alive() for t in threads)
        assert engine.dispatch_counts["score"] == 1  # one merged dispatch

        failed = [i for i in range(3) if isinstance(results[i], Exception)]
        assert len(failed) == 1
        assert isinstance(results[failed[0]], BackendIntegrityError)
        for i in range(3):
            if i not in failed:
                assert results[i].logprobs == clean[i].logprobs

    def test_device_lost_is_sticky_through_engine_submit(self):
        from consensus_tpu.backends.base import BackendLostError
        from consensus_tpu.obs.metrics import Registry

        plan = {"faults": [
            {"kind": "device_lost", "op": "score", "call_index": 0}]}
        registry = Registry()
        batching = self._engine_stack(plan, registry)
        reqs = [ScoreRequest(context="ctx", continuation="row")]
        try:
            with pytest.raises(BackendLostError):
                batching.score(reqs)
            # The engine latched the loss (the fleet router's passive
            # health signal) and stays lost for every later submit.
            assert batching.engine.backend_lost
            assert batching.engine.stats()["backend_lost"]
            with pytest.raises(BackendLostError):
                batching.score(reqs)
        finally:
            batching.close()
