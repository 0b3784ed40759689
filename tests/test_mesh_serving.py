"""Multichip mesh serving path (the MULTICHIP dryrun, promoted to pytest).

The PR 9 contract, pinned here:

* mesh construction and ``mesh={'dp': N, 'tp': M}`` spec parsing;
* regex partition rules cover EVERY param path of both tiny model
  families (and an unmatched path fails loudly, naming the path);
* the sharded paged slot programs (prefill / decode / gather) under a
  dp x tp mesh reproduce the single-device logits to fp32 tolerance;
* the engine's mesh mode partitions slots + page pools over dp shards
  with balanced admission, and aggregate capacity really is dp x the
  per-shard pool;
* statements are byte-identical across dp widths through the real
  backend (``texts_match_dp``), and the dp=1/tp=1 mesh path returns the
  exact bytes of the plain PR 6 engine path;
* ``kv_cache_identity`` partitions the prefix-cache keyspace by tp (tp
  changes the bytes in a page) but not by dp (pages replicate over data).

Runs on the 8-virtual-device CPU mesh forced by conftest.py.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from consensus_tpu.backends.base import GenerationRequest
from consensus_tpu.backends.engine import DecodeEngine
from consensus_tpu.backends.fake import FakeBackend
from consensus_tpu.models import stepper
from consensus_tpu.models.config import get_model_config
from consensus_tpu.models.quant import QTensor, quantize_params
from consensus_tpu.models.transformer import init_params, project_logits
from consensus_tpu.obs.metrics import Registry
from consensus_tpu.ops.kv_pages import BlockTable, PagePool
from consensus_tpu.parallel import (
    make_mesh,
    match_partition_rules,
    param_shardings,
    parse_mesh_spec,
    shard_params,
)
from consensus_tpu.parallel.mesh import MODEL_AXIS

TINY_MODELS = ["tiny-gemma2", "tiny-llama3"]


# ---------------------------------------------------------------------------
# Mesh construction + spec parsing
# ---------------------------------------------------------------------------


class TestMeshSpec:
    def test_make_mesh_serving_shapes(self):
        plan = make_mesh(dp=4, tp=2)
        assert plan.dp == 4 and plan.tp == 2 and plan.n_devices == 8
        assert plan.mesh.axis_names == ("data", "model")

    def test_parse_accepts_str_dict_plan_none(self):
        assert parse_mesh_spec(None) is None
        assert parse_mesh_spec("dp=4,tp=2") == {"dp": 4, "tp": 2}
        assert parse_mesh_spec("tp=2") == {"dp": 1, "tp": 2}
        assert parse_mesh_spec({"dp": 3}) == {"dp": 3, "tp": 1}
        plan = make_mesh(tp=2)
        assert parse_mesh_spec(plan) == {"dp": plan.dp, "tp": 2}

    def test_parse_rejects_garbage(self):
        with pytest.raises(ValueError):
            parse_mesh_spec("replicas=4")
        with pytest.raises(ValueError):
            parse_mesh_spec({"dp": 0})
        with pytest.raises(ValueError):
            parse_mesh_spec("dp")


# ---------------------------------------------------------------------------
# Partition-rule coverage (satellite: fails on any unmatched param path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg_name", TINY_MODELS)
class TestPartitionRules:
    def test_rules_cover_every_param_path(self, cfg_name):
        cfg = get_model_config(cfg_name)
        params = init_params(cfg, jax.random.PRNGKey(0))
        specs = match_partition_rules(params)
        # Megatron layout: attention/ffn first matmuls split output
        # features, second matmuls split input features, vocab rows shard.
        assert tuple(specs["layers"]["wq"])[-1] == MODEL_AXIS
        assert tuple(specs["layers"]["wo"])[1] == MODEL_AXIS
        assert tuple(specs["layers"]["w_down"])[1] == MODEL_AXIS
        assert tuple(specs["embed"])[0] == MODEL_AXIS
        assert all(a is None for a in tuple(specs["layers"]["attn_norm"]))

    def test_unmatched_param_path_fails_loudly(self, cfg_name):
        cfg = get_model_config(cfg_name)
        params = init_params(cfg, jax.random.PRNGKey(0))
        params["layers"]["mystery_weight"] = jnp.ones((2, 4, 4))
        with pytest.raises(ValueError, match="layers/mystery_weight"):
            match_partition_rules(params)

    def test_param_shardings_int8_scale_replicates(self, cfg_name):
        """QTensor q shards like the weight; squeezed scale axes go None."""
        cfg = get_model_config(cfg_name)
        qparams = quantize_params(init_params(cfg, jax.random.PRNGKey(0)))
        shardings = param_shardings(qparams, make_mesh(tp=2).mesh)
        wq = shardings["layers"]["wq"]
        assert isinstance(wq, QTensor)
        assert tuple(wq.q.spec)[-1] == MODEL_AXIS
        wo = shardings["layers"]["wo"]
        # wo contracts its (sharded) input axis, so its per-output-channel
        # scale has size 1 there and must replicate.
        assert all(a is None for a in tuple(wo.scale.spec))


# ---------------------------------------------------------------------------
# Sharded paged programs: tp=2 logits vs single-device
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg_name", TINY_MODELS)
class TestShardedPagedPrograms:
    def test_tp_mesh_matches_single_device(self, cfg_name):
        """prefill -> greedy decode -> gather under a dp=4,tp=2 mesh
        reproduces the unsharded paged path's logits and token choices."""
        cfg = get_model_config(cfg_name)
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(2)
        prompt = rng.randint(1, cfg.vocab_size, size=(8,)).astype(np.int32)
        page_size, num_pages, max_blocks, n_decode = 4, 16, 8, 3

        def run(mesh, run_params):
            pool = PagePool(num_pages, page_size)
            state = stepper.make_page_state(
                cfg, num_pages, page_size, jnp.float32, mesh=mesh
            )
            sink = num_pages
            table = BlockTable(0)
            table.append_tokens(pool, 8)
            tok = np.zeros((2, 8), np.int32)
            cvalid = np.zeros((2, 8), bool)
            wp = np.full((2, 8), sink, np.int32)
            wo = np.zeros((2, 8), np.int32)
            tok[0] = prompt
            cvalid[0] = True
            for t in range(8):
                wp[0, t] = table.pages[t // page_size]
                wo[0, t] = t % page_size
            tables = np.full((2, max_blocks), -1, np.int32)
            tables[0] = table.as_array(max_blocks)
            hidden, state = stepper.paged_prefill_chunk(
                run_params, cfg, jnp.asarray(tok), jnp.asarray(cvalid),
                state, jnp.asarray(tables),
                jnp.asarray([8, 0], np.int32), jnp.asarray(wp),
                jnp.asarray(wo), mesh=mesh,
            )
            trace = [np.asarray(project_logits(run_params, cfg, hidden)[0])]
            tokens = []
            last = trace[0]
            for _ in range(n_decode):
                nxt = int(np.argmax(last))
                tokens.append(nxt)
                table.append_tokens(pool, 1)
                page, offset = table.write_cursor(pool)
                tables = np.full((2, max_blocks), -1, np.int32)
                tables[0] = table.as_array(max_blocks)
                lg, state = stepper.paged_decode_step(
                    run_params, cfg, jnp.asarray([nxt, 0], jnp.int32),
                    state, jnp.asarray(tables),
                    jnp.asarray([table.num_tokens, 0], np.int32),
                    jnp.asarray([page, sink], np.int32),
                    jnp.asarray([offset, 0], np.int32), mesh=mesh,
                )
                last = np.asarray(lg[0])
                trace.append(last)
            g_logits, _ = stepper.paged_gather_step(
                run_params, cfg,
                jnp.asarray([int(prompt[-1]), 0], jnp.int32), state,
                jnp.asarray(tables),
                jnp.asarray([table.num_tokens, 0], np.int32), mesh=mesh,
            )
            trace.append(np.asarray(g_logits[0]))
            return tokens, trace

        ref_tokens, ref_trace = run(None, params)
        plan = make_mesh(dp=4, tp=2)
        sh_tokens, sh_trace = run(
            plan.mesh, shard_params(params, plan.mesh)
        )
        assert sh_tokens == ref_tokens
        for ref, got in zip(ref_trace, sh_trace):
            np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Engine mesh mode: dp-partitioned slots, pools, balanced admission
# ---------------------------------------------------------------------------


def _submit_async(engine, requests):
    out = {}

    def worker():
        try:
            out["result"] = engine.submit("generate", requests)
        except BaseException as exc:  # noqa: BLE001 - test captures verbatim
            out["error"] = exc

    thread = threading.Thread(target=worker, daemon=True)
    thread.start()
    return thread, out


def _wait_until(predicate, timeout=5.0):
    import time

    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(0.002)
    return False


class TestEngineMeshMode:
    def test_dp_partitions_pools_and_balances_admission(self):
        """4 rows needing 5 pages each all become resident at once under
        dp=4 with 8-page per-shard pools (aggregate capacity is dp x the
        per-shard pool — a dp=1 engine with the same per-shard pool holds
        one); admission spreads them one per shard."""
        reg = Registry()
        engine = DecodeEngine(
            FakeBackend(), slots=8, page_size=4, num_pages=8,
            auto_start=False, mesh={"dp": 4, "tp": 2}, registry=reg,
        )
        assert engine.mesh_dp == 4 and engine.mesh_tp == 2
        assert len(engine.pools) == 4
        assert len({id(p) for p in engine.pools}) == 4

        reqs = [
            GenerationRequest(
                user_prompt="one two three four five", max_tokens=12, seed=i,
            )
            for i in range(4)
        ]
        solo = FakeBackend().generate(reqs)
        threads = [_submit_async(engine, [r]) for r in reqs]
        assert _wait_until(lambda: engine.stats()["queue_depth"] == 4)
        with engine._lock:
            engine._admit()
        shards = sorted(s.shard for s in engine._slots if s is not None)
        assert shards == [0, 1, 2, 3]
        stats = engine.stats()
        assert stats["slots_occupied"] == 4
        assert stats["mesh"]["dp"] == 4 and stats["mesh"]["tp"] == 2
        assert [s["slots_occupied"] for s in stats["mesh"]["per_shard"]] == [
            1, 1, 1, 1,
        ]
        assert all(
            s["kv_pages_reserved"] == 5 for s in stats["mesh"]["per_shard"]
        )

        for _ in range(4):
            engine.run_iteration()
        for thread, _ in threads:
            thread.join(timeout=5.0)
        assert [out["result"][0].text for _, out in threads] == [
            r.text for r in solo
        ]
        stats = engine.stats()
        assert stats["slots_occupied"] == 0
        assert all(pool.in_use == 0 for pool in engine.pools)
        assert stats["kv_pages_reserved"] == 0
        engine.close()

    def test_mesh_gauges_emitted(self):
        """The mesh's widths are in the engine's stats (``/healthz``
        ``engine.mesh``), with one row a data-parallel shard; the registry
        carries no gauge that repeats them."""
        reg = Registry()
        engine = DecodeEngine(
            FakeBackend(), slots=4, num_pages=16, auto_start=False,
            mesh="dp=2,tp=1", registry=reg,
        )
        mesh = engine.stats()["mesh"]
        assert (mesh["dp"], mesh["tp"]) == (2, 1)
        assert len(mesh["per_shard"]) == 2
        families = reg.snapshot()["families"]
        assert not {"engine_mesh_dp", "engine_mesh_tp"} & set(families)
        engine.close()

    def test_dp1_mesh_is_the_legacy_engine(self):
        """mesh={'dp': 1} must be structurally the PR 6 engine: one pool,
        aliased as .pool, legacy FIFO admission order."""
        engine = DecodeEngine(
            FakeBackend(), slots=2, num_pages=16, auto_start=False,
            mesh={"dp": 1, "tp": 1},
        )
        assert engine.pools == [engine.pool]
        assert engine.mesh_dp == 1
        engine.close()


# ---------------------------------------------------------------------------
# End-to-end: dp-width text identity through the real backend
# ---------------------------------------------------------------------------


class TestMeshServingEndToEnd:
    N_REQUESTS = 6
    MAX_TOKENS = 4

    @pytest.fixture(scope="class")
    def base_backend(self):
        from consensus_tpu.backends.tpu import TPUBackend

        backend = TPUBackend(model="tiny-gemma2", max_context=128)
        yield backend

    def _requests(self):
        return [
            GenerationRequest(
                user_prompt=f"Draft a statement on issue {i}.",
                max_tokens=self.MAX_TOKENS, temperature=0.8, seed=100 + i,
                chat=False,
            )
            for i in range(self.N_REQUESTS)
        ]

    def _texts(self, backend, mesh):
        from consensus_tpu.backends.batching import BatchingBackend
        from concurrent.futures import ThreadPoolExecutor

        batching = BatchingBackend(
            backend, registry=Registry(), engine=True,
            engine_options={
                "slots": 8, "page_size": 16, "num_pages": 4,
                **({"mesh": mesh} if mesh is not None else {}),
            },
        )
        try:
            with ThreadPoolExecutor(max_workers=self.N_REQUESTS) as pool:
                futures = [
                    pool.submit(batching.generate, [r])
                    for r in self._requests()
                ]
                return [f.result()[0].text for f in futures]
        finally:
            batching.close()

    def test_texts_match_dp(self, base_backend):
        """The MULTICHIP dryrun invariant: statements are identical across
        dp widths, and the dp=1/tp=1 mesh path is byte-identical to the
        plain single-device engine path."""
        from consensus_tpu.backends.tpu import TPUBackend

        plain = self._texts(base_backend, None)
        dp1 = self._texts(base_backend, {"dp": 1, "tp": 1})
        assert dp1 == plain  # dp=1/tp=1 == the PR 6 engine path, exactly

        wide_backend = TPUBackend(
            model="tiny-gemma2", max_context=128, dp=4,
            params=base_backend.params, config=base_backend.config,
        )
        dp4 = self._texts(wide_backend, {"dp": 4, "tp": 1})
        assert dp4 == dp1  # texts_match_dp

    def test_kv_cache_identity_partitions_by_tp_not_dp(self, base_backend):
        """tp changes the bytes a page holds (each chip's kv-head slice),
        so it must partition the prefix-cache keyspace; dp replicates
        pages and must NOT."""
        from consensus_tpu.backends.tpu import TPUBackend

        tp1 = base_backend.kv_cache_identity()
        assert ("tp", 1) in tp1
        tp2 = TPUBackend(
            model="tiny-gemma2", max_context=128, tp=2,
            params=base_backend.params, config=base_backend.config,
        ).kv_cache_identity()
        assert tp1 != tp2
        dp2 = TPUBackend(
            model="tiny-gemma2", max_context=128, dp=2,
            params=base_backend.params, config=base_backend.config,
        ).kv_cache_identity()
        assert dp2 == tp1
