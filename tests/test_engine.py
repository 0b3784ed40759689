"""Continuous-batching decode engine (backends/engine.py) + paged KV cache
(ops/kv_pages.py).

The PR 6 contract, pinned here:

* byte-identity — every GENERATOR_MAP method produces the same statement
  through the engine, the legacy flush path, and a solo backend;
* page-pool soundness — all-or-nothing allocation, no aliasing under
  churn, double/foreign frees raise;
* graceful OOM — a request that can never fit the pool gets the serving
  tier's typed ``SchedulerRejected("kv_oom")``, not a crash;
* interleaved chunked prefill never perturbs decode results;
* cancellation evicts resident rows and returns their KV pages;
* engine mode keeps ``flush_reason="timeout"`` unreachable and
  ``batching_spurious_wakeups_total`` at 0, and stays recompile-flat
  across ragged load.
"""

import threading
import time

import pytest

from consensus_tpu.backends.base import (
    GenerationRequest,
    GenerationResult,
    RequestCancelled,
)
from consensus_tpu.backends.batching import BatchingBackend
from consensus_tpu.backends.engine import DecodeEngine
from consensus_tpu.backends.fake import FakeBackend
from consensus_tpu.methods import get_method_generator
from consensus_tpu.obs.backends import bucket_recompiles
from consensus_tpu.obs.metrics import Registry, diff_snapshots
from consensus_tpu.ops.kv_pages import (
    BlockTable,
    PagePool,
    PagePoolExhausted,
    PrefixCache,
)

ISSUE = "Should the city invest in more bike lanes?"
OPINIONS = {
    "Agent 1": "Bike lanes make streets safer and should be expanded.",
    "Agent 2": "Road space is scarce; cars and buses need priority.",
    "Agent 3": "Invest only where cycling demand is proven.",
}

#: Small-but-real params for every method in GENERATOR_MAP (same settings
#: the per-method suites use, so any drift shows up in one place).
METHOD_PARAMS = {
    "zero_shot": {"seed": 42, "max_tokens": 30},
    "predefined": {"predefined_statement": "Exactly this statement."},
    "best_of_n": {"num_best_of_n": 4, "seed": 7, "max_tokens": 24},
    "beam_search": {"beam_width": 2, "max_tokens": 6, "seed": 5},
    "finite_lookahead": {
        "branching_factor": 2, "max_depth": 2, "max_tokens": 5, "seed": 9,
    },
    "mcts": {
        "num_simulations": 4, "expansion_sample_width": 3, "max_tokens": 4,
        "rollout_depth": 3, "seed": 2,
    },
    "habermas_machine": {
        "num_candidates": 3, "num_rounds": 1, "seed": 42, "max_tokens": 64,
    },
}


def _counter_total(registry, name, **labels):
    family = registry.snapshot()["families"].get(name)
    total = 0.0
    for series in (family or {}).get("series", ()):
        if all(series["labels"].get(k) == v for k, v in labels.items()):
            total += series["value"]
    return total


def _wait_until(predicate, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(0.002)
    return False


# ---------------------------------------------------------------------------
# Page pool / block table
# ---------------------------------------------------------------------------


class TestPagePool:
    def test_alloc_free_roundtrip(self):
        pool = PagePool(8, page_size=4)
        pages = pool.alloc(3, owner="a")
        assert len(set(pages)) == 3
        assert pool.in_use == 3 and pool.free_count == 5
        pool.free(pages)
        assert pool.in_use == 0 and pool.free_count == 8

    def test_exhaustion_is_all_or_nothing(self):
        pool = PagePool(4, page_size=4)
        pool.alloc(3, owner="a")
        with pytest.raises(PagePoolExhausted):
            pool.alloc(2, owner="b")
        # The failed alloc must not have consumed the last free page.
        assert pool.free_count == 1
        pool.alloc(1, owner="b")

    def test_double_free_raises(self):
        pool = PagePool(4)
        pages = pool.alloc(2)
        pool.free(pages)
        with pytest.raises(ValueError, match="double free|not allocated"):
            pool.free(pages)

    def test_foreign_page_free_raises(self):
        pool = PagePool(4)
        with pytest.raises(ValueError):
            pool.free([99])

    def test_no_aliasing_under_churn(self):
        """Interleaved alloc/free never hands one page to two live owners."""
        pool = PagePool(16, page_size=4)
        live = {}
        for step in range(200):
            if step % 3 == 2 and live:
                victim = sorted(live)[step % len(live)]
                pool.free(live.pop(victim))
            else:
                n = 1 + step % 3
                if n <= pool.free_count:
                    live[step] = pool.alloc(n, owner=step)
            held = [p for pages in live.values() for p in pages]
            assert len(held) == len(set(held))  # no page in two hands
            assert pool.in_use == len(held)
        assert pool.stats().high_water <= pool.num_pages

    def test_pages_for_tokens_ceil(self):
        pool = PagePool(8, page_size=16)
        assert pool.pages_for_tokens(0) == 0
        assert pool.pages_for_tokens(1) == 1
        assert pool.pages_for_tokens(16) == 1
        assert pool.pages_for_tokens(17) == 2

    # -- refcounted sharing (prefix cache) ---------------------------------

    def test_shared_page_survives_first_free(self):
        """free() drops one reference; the page rejoins the free list only
        when the LAST holder lets go."""
        pool = PagePool(8, page_size=4)
        pages = pool.alloc(2, owner="slot")
        pool.share(pages)  # cache pins them
        assert all(pool.refcount(p) == 2 for p in pages)
        pool.free(pages)  # slot retires
        assert pool.in_use == 2 and pool.free_count == 6
        assert all(pool.refcount(p) == 1 for p in pages)
        pool.free(pages)  # cache evicts
        assert pool.in_use == 0 and pool.free_count == 8

    def test_double_free_of_shared_page_still_raises(self):
        """Sharing must not launder a double free: once every reference is
        gone, another free raises exactly like the unshared case."""
        pool = PagePool(4, page_size=4)
        pages = pool.alloc(1)
        pool.share(pages)
        pool.free(pages)
        pool.free(pages)
        with pytest.raises(ValueError, match="double free|not allocated"):
            pool.free(pages)

    def test_share_free_page_raises(self):
        pool = PagePool(4, page_size=4)
        pages = pool.alloc(1)
        pool.free(pages)
        with pytest.raises(ValueError, match="cannot share a free page"):
            pool.share(pages)
        with pytest.raises(ValueError):
            pool.share([99])

    def test_freed_while_refcounted_page_is_not_reallocated(self):
        """A page another holder still references must never come back out
        of alloc() — the aliasing bug refcounting exists to prevent."""
        pool = PagePool(4, page_size=4)
        shared = pool.alloc(2, owner="a")
        pool.share(shared)
        pool.free(shared)  # one reference remains
        grabbed = pool.alloc(2, owner="b")  # only the 2 never-shared pages
        assert not (set(grabbed) & set(shared))
        with pytest.raises(PagePoolExhausted):
            pool.alloc(1, owner="c")

    def test_no_aliasing_under_churn_with_sharing(self):
        """Mixed private/shared churn keeps the invariant: at every step a
        page is either free, or held by exactly its current reference
        holders — never handed out twice."""
        pool = PagePool(16, page_size=4)
        private = {}  # step -> pages (one ref)
        shared = {}  # step -> pages (two refs: "slot" + "cache")
        for step in range(300):
            action = step % 5
            if action == 0 and pool.free_count >= 2:
                private[step] = pool.alloc(2, owner=step)
            elif action == 1 and pool.free_count >= 1:
                pages = pool.alloc(1, owner=step)
                pool.share(pages)
                shared[step] = pages
            elif action == 2 and private:
                pool.free(private.pop(sorted(private)[0]))
            elif action == 3 and shared:
                # Drop ONE of the two references; entry stays live.
                key = sorted(shared)[0]
                pool.free(shared[key])
                private[key] = shared.pop(key)
            elif action == 4 and private:
                pool.free(private.pop(sorted(private)[-1]))
            held = [
                p for pages in list(private.values()) + list(shared.values())
                for p in pages
            ]
            assert len(held) == len(set(held))
            assert pool.in_use == len(held)
            for pages in shared.values():
                assert all(pool.refcount(p) == 2 for p in pages)
        for pages in private.values():
            pool.free(pages)
        for pages in shared.values():
            pool.free(pages)
            pool.free(pages)
        assert pool.in_use == 0 and pool.free_count == 16

    def test_adopt_shared_requires_alignment_and_empty_table(self):
        pool = PagePool(8, page_size=4)
        donor = BlockTable(0)
        donor.append_tokens(pool, 8)
        table = BlockTable(1)
        with pytest.raises(ValueError, match="page-aligned"):
            table.adopt_shared(pool, donor.pages, 7)
        table.adopt_shared(pool, donor.pages, 8)
        assert table.num_tokens == 8 and table.pages == donor.pages
        with pytest.raises(ValueError, match="empty block table"):
            table.adopt_shared(pool, donor.pages, 8)
        # The adopter's release leaves the donor's reference intact.
        table.release(pool)
        assert pool.in_use == 2
        donor.release(pool)
        assert pool.in_use == 0


class TestBlockTable:
    def test_append_allocates_on_page_boundaries_only(self):
        pool = PagePool(8, page_size=4)
        table = BlockTable(0)
        assert len(table.append_tokens(pool, 3)) == 1  # first page
        assert table.append_tokens(pool, 1) == []  # fills page 0
        assert len(table.append_tokens(pool, 5)) == 2  # crosses into 2 more
        assert table.num_tokens == 9 and len(table.pages) == 3

    def test_write_cursor_tracks_last_token(self):
        pool = PagePool(8, page_size=4)
        table = BlockTable(0)
        table.append_tokens(pool, 5)
        page, offset = table.write_cursor(pool)
        assert page == table.pages[1] and offset == 0

    def test_release_returns_everything(self):
        pool = PagePool(8, page_size=4)
        table = BlockTable(0)
        table.append_tokens(pool, 9)
        table.release(pool)
        assert pool.in_use == 0 and table.num_tokens == 0

    def test_as_array_pads_and_bounds(self):
        pool = PagePool(8, page_size=4)
        table = BlockTable(0)
        table.append_tokens(pool, 6)
        arr = table.as_array(4)
        assert arr.tolist()[:2] == table.pages and set(arr.tolist()[2:]) == {-1}
        with pytest.raises(ValueError, match="max_blocks"):
            table.as_array(1)


# ---------------------------------------------------------------------------
# Byte-identity: engine vs legacy flush vs solo, all seven methods
# ---------------------------------------------------------------------------


class TestByteIdentity:
    @pytest.mark.parametrize("method", sorted(METHOD_PARAMS))
    def test_engine_matches_legacy_and_solo(self, method):
        params = METHOD_PARAMS[method]
        solo = get_method_generator(
            method, FakeBackend(), dict(params)
        ).generate_statement(ISSUE, OPINIONS)

        legacy = BatchingBackend(FakeBackend(), flush_ms=1.0, engine=False)
        via_legacy = get_method_generator(
            method, legacy, dict(params)
        ).generate_statement(ISSUE, OPINIONS)

        engined = BatchingBackend(
            FakeBackend(), engine=True,
            engine_options={"slots": 4, "num_pages": 512},
        )
        try:
            via_engine = get_method_generator(
                method, engined, dict(params)
            ).generate_statement(ISSUE, OPINIONS)
        finally:
            engined.close()

        assert via_engine == solo, f"{method}: engine result diverged"
        assert via_legacy == solo, f"{method}: legacy result diverged"


# ---------------------------------------------------------------------------
# Prefix KV cache
# ---------------------------------------------------------------------------


class TestPrefixCache:
    def _cache(self, num_pages=16, max_pages=8, identity=("m", "dense")):
        pool = PagePool(num_pages, page_size=4)
        return pool, PrefixCache(pool, max_pages, identity=identity)

    def test_miss_then_hit_roundtrip(self):
        pool, cache = self._cache()
        tokens = list(range(8))
        assert cache.lookup(tokens) == ([], 0)
        pages = pool.alloc(2, owner="slot")
        assert cache.insert(tokens, pages)
        got_pages, got_tokens = cache.lookup(tokens + [99, 98])
        assert got_pages == pages and got_tokens == 8
        # Three holders now: slot, cache, and the lookup's adopter.
        assert all(pool.refcount(p) == 3 for p in pages)
        stats = cache.stats()
        assert stats["hits"] == 1 and stats["misses"] == 1
        assert stats["tokens_saved"] == 8

    def test_lookup_returns_longest_prefix(self):
        pool, cache = self._cache()
        short, long_ = list(range(4)), list(range(8))
        p_short = pool.alloc(1, owner="a")
        p_long = pool.alloc(2, owner="b")
        assert cache.insert(short, p_short)
        assert cache.insert(long_, p_long)
        pages, n = cache.lookup(long_ + [42])
        assert (pages, n) == (p_long, 8)
        # A stream sharing only the first page matches the short entry.
        pages, n = cache.lookup(short + [77, 77, 77, 77])
        assert (pages, n) == (p_short, 4)

    def test_unaligned_or_oversized_insert_rejected(self):
        pool, cache = self._cache(max_pages=1)
        pages = pool.alloc(2, owner="a")
        assert not cache.insert(list(range(7)), pages)  # unaligned
        assert not cache.insert(list(range(8)), pages)  # over budget
        assert not cache.insert([], [])  # empty
        assert pool.refcount(pages[0]) == 1  # no stray references taken

    def test_identity_partitions_the_keyspace(self):
        """Same token stream, different (tier, quant) identity — never the
        same entry: two tiers' KV bytes must not alias."""
        pool = PagePool(16, page_size=4)
        a = PrefixCache(pool, 8, identity=("m", "dense"))
        b = PrefixCache(pool, 8, identity=("m", "int8"))
        tokens = list(range(8))
        pages = pool.alloc(2, owner="x")
        assert a.insert(tokens, pages)
        assert b.lookup(tokens) == ([], 0)
        assert a.lookup(tokens)[1] == 8

    def test_lru_eviction_frees_cache_reference_only(self):
        pool, cache = self._cache(max_pages=2)
        first = pool.alloc(2, owner="a")
        assert cache.insert(list(range(8)), first)
        pool.free(first)  # slot retires; cache holds the last reference
        second = pool.alloc(2, owner="b")
        assert cache.insert(list(range(100, 108)), second)  # evicts first
        assert cache.stats()["evictions"] == 1
        assert cache.stats()["pages"] == 2
        # The evicted entry's pages went back to the free list...
        assert pool.in_use == 2
        # ...and the survivor is still servable.
        assert cache.lookup(list(range(100, 108)))[1] == 8

    def test_eviction_spares_pages_adopted_by_live_slots(self):
        pool, cache = self._cache(max_pages=2)
        first = pool.alloc(2, owner="a")
        assert cache.insert(list(range(8)), first)
        pool.free(first)
        adopted, n = cache.lookup(list(range(8)))  # a live slot adopts
        assert n == 8
        second = pool.alloc(2, owner="b")
        assert cache.insert(list(range(100, 108)), second)  # evicts entry
        # The entry is gone but the adopter's reference keeps pages alive.
        assert cache.lookup(list(range(8)))[1] == 0
        assert all(pool.refcount(p) == 1 for p in adopted)
        pool.free(adopted)
        assert pool.in_use == 2  # only the second entry's pages remain


class TestEnginePrefixByteIdentity:
    """With the prefix cache ON the engine must return byte-identical
    results for every method — the cache only changes which prefill work
    runs, never what any request computes."""

    @pytest.mark.parametrize("method", sorted(METHOD_PARAMS))
    def test_cache_on_equals_cache_off(self, method):
        params = METHOD_PARAMS[method]

        def run(**engine_options):
            backend = BatchingBackend(
                FakeBackend(), engine=True,
                engine_options={"slots": 4, "num_pages": 512,
                                **engine_options},
            )
            try:
                statement = get_method_generator(
                    method, backend, dict(params)
                ).generate_statement(ISSUE, OPINIONS)
                stats = backend.engine.stats()
            finally:
                backend.close()
            return statement, stats

        off, stats_off = run()
        on, stats_on = run(prefix_cache=True)
        assert on == off, f"{method}: prefix cache changed the statement"
        assert stats_off["prefix_cache"] == {"enabled": False}
        assert stats_on["prefix_cache"]["enabled"]

    def test_repeated_requests_hit_and_leave_no_leak(self):
        backend = BatchingBackend(
            FakeBackend(), engine=True,
            engine_options={"slots": 4, "page_size": 4, "num_pages": 64,
                            "prefix_cache": True},
        )
        req = GenerationRequest(
            user_prompt="alpha beta gamma delta epsilon zeta eta theta",
            max_tokens=8, seed=3,
        )
        solo = FakeBackend().generate([req, req])
        try:
            first = backend.generate([req])
            second = backend.generate([req])
            stats = backend.engine.stats()["prefix_cache"]
            engine = backend.engine
            # Cached pages stay pinned by the cache; nothing else leaks.
            assert engine.pool.in_use == stats["pages"]
        finally:
            backend.close()
        assert first[0].text == solo[0].text
        assert second[0].text == solo[1].text
        assert stats["hits"] >= 1
        assert stats["tokens_saved"] > 0
        assert stats["inserted_pages"] >= 1

    def test_prefix_metrics_families_emitted(self):
        reg = Registry()
        engine = DecodeEngine(
            FakeBackend(), slots=2, page_size=4, num_pages=64,
            prefix_cache=True, registry=reg,
        )
        req = GenerationRequest(
            user_prompt="one two three four five six seven eight",
            max_tokens=4, seed=1,
        )
        try:
            engine.submit("generate", [req])
            engine.submit("generate", [req])
        finally:
            engine.close()
        assert _counter_total(reg, "prefix_cache_hits_total") >= 1
        assert _counter_total(reg, "prefix_cache_misses_total") >= 1
        assert _counter_total(reg, "prefix_tokens_saved_total") > 0
        assert _counter_total(reg, "prefix_cache_inserted_pages_total") >= 1


# ---------------------------------------------------------------------------
# Scheduling semantics (deterministic stepping via auto_start=False)
# ---------------------------------------------------------------------------


def _submit_async(engine, requests, probe=None):
    """Run ``engine.submit`` in a thread; returns (thread, outbox dict)."""
    out = {}

    def worker():
        try:
            out["result"] = engine.submit("generate", requests, probe=probe)
        except BaseException as exc:  # noqa: BLE001 - test captures verbatim
            out["error"] = exc

    thread = threading.Thread(target=worker, daemon=True)
    thread.start()
    return thread, out


class TestEngineScheduling:
    def test_full_slot_table_occupancy(self):
        """8 co-batched statements keep the whole slot table busy —
        occupancy mean >= 0.8 is the BENCH_ENGINE acceptance floor."""
        reg = Registry()
        engine = DecodeEngine(
            FakeBackend(), slots=8, num_pages=512, auto_start=False,
            registry=reg,
        )
        threads = []
        for i in range(8):
            t, _ = _submit_async(
                engine,
                [GenerationRequest(
                    user_prompt=f"prompt {i} with a few extra words",
                    max_tokens=8, seed=i,
                )],
            )
            threads.append(t)
        assert _wait_until(lambda: engine.stats()["queue_depth"] == 8)
        engine.run_iteration()
        for t in threads:
            t.join(timeout=5.0)
        stats = engine.stats()
        assert stats["slot_occupancy_mean"] >= 0.8
        assert stats["slots_occupied"] == 0  # everything retired
        assert engine.pool.in_use == 0

    def test_admission_is_reservation_bounded(self):
        """Admission reserves prompt+max_tokens pages, so a resident row can
        always finish; the backlog holds FIFO until pages free up."""
        engine = DecodeEngine(
            FakeBackend(), slots=4, page_size=4, num_pages=8,
            auto_start=False, min_fill=1,
        )
        # Each request needs ceil((5 + 12)/4) = 5 pages; two can't coexist
        # in an 8-page pool.
        reqs = [
            GenerationRequest(
                user_prompt="one two three four five", max_tokens=12, seed=i,
            )
            for i in range(2)
        ]
        threads = [_submit_async(engine, [r])[0] for r in reqs]
        assert _wait_until(lambda: engine.stats()["queue_depth"] == 2)
        engine.run_iteration()
        stats = engine.stats()
        assert stats["kv_pages_reserved"] <= 8
        # Second row waited its turn; a later iteration retires it too.
        for _ in range(4):
            engine.run_iteration()
        for t in threads:
            t.join(timeout=5.0)
        assert engine.stats()["kv_pages_reserved"] == 0
        assert engine.pool.in_use == 0

    def test_oversized_request_rejected_as_kv_oom(self):
        from consensus_tpu.serve.scheduler import SchedulerRejected

        backend = BatchingBackend(
            FakeBackend(), engine=True,
            engine_options={"slots": 2, "page_size": 4, "num_pages": 2},
        )
        try:
            with pytest.raises(SchedulerRejected) as excinfo:
                backend.generate(
                    [GenerationRequest(
                        user_prompt="this prompt is fine",
                        max_tokens=256, seed=0,
                    )]
                )
        finally:
            backend.close()
        assert excinfo.value.reason == "kv_oom"

    def test_interleaved_prefill_does_not_perturb_decode(self):
        """A second request arriving mid-prefill (chunk=1 drip) must not
        change the first request's tokens — token-for-token vs solo."""
        reqs = [
            GenerationRequest(
                user_prompt="alpha beta gamma delta epsilon zeta",
                max_tokens=8, seed=11,
            ),
            GenerationRequest(
                user_prompt="one two three four five six seven eight nine",
                max_tokens=8, seed=12,
            ),
        ]
        solo = FakeBackend().generate(reqs)

        engine = DecodeEngine(
            FakeBackend(), slots=2, page_size=4, num_pages=64,
            prefill_chunk=1, min_fill=1, auto_start=False,
        )
        t1, out1 = _submit_async(engine, [reqs[0]])
        assert _wait_until(lambda: engine.stats()["queue_depth"] == 1)
        engine.run_iteration()  # admit + first 1-token prefill chunk
        assert engine.stats()["slots_occupied"] == 1
        t2, out2 = _submit_async(engine, [reqs[1]])
        assert _wait_until(lambda: engine.stats()["queue_depth"] == 1)
        for _ in range(40):
            if out1 and out2:
                break
            engine.run_iteration()
        t1.join(timeout=5.0)
        t2.join(timeout=5.0)
        assert out1["result"][0].text == solo[0].text
        assert out2["result"][0].text == solo[1].text
        assert engine.pool.in_use == 0

    def test_cancellation_evicts_and_frees_pages(self):
        reg = Registry()
        engine = DecodeEngine(
            FakeBackend(), slots=2, page_size=4, num_pages=64,
            prefill_chunk=2, auto_start=False, registry=reg,
        )
        flag = {"cancelled": False}
        thread, out = _submit_async(
            engine,
            [GenerationRequest(
                user_prompt="one two three four five six seven eight",
                max_tokens=4, seed=3,
            )],
            probe=lambda: flag["cancelled"],
        )
        assert _wait_until(lambda: engine.stats()["queue_depth"] == 1)
        engine.run_iteration()  # admit + partial prefill (2 of 8 tokens)
        assert engine.stats()["slots_occupied"] == 1
        assert engine.pool.in_use > 0
        flag["cancelled"] = True
        engine.run_iteration()
        thread.join(timeout=5.0)
        assert isinstance(out.get("error"), RequestCancelled)
        assert engine.pool.in_use == 0
        assert engine.stats()["slots_occupied"] == 0
        assert _counter_total(reg, "engine_evicted_total") >= 1

    def test_submit_after_close_raises(self):
        engine = DecodeEngine(FakeBackend(), auto_start=False)
        engine.close()
        with pytest.raises(RuntimeError, match="closed"):
            engine.submit(
                "generate",
                [GenerationRequest(user_prompt="late", max_tokens=4, seed=0)],
            )


# ---------------------------------------------------------------------------
# Cohorts form by prompt: a slot holds one prompt with its rows
# ---------------------------------------------------------------------------


class _CohortRecorder(FakeBackend):
    """Fake backend that keeps every ``generate`` call's prompts."""

    def __init__(self):
        super().__init__()
        self.cohorts = []

    def generate(self, requests):
        self.cohorts.append([r.user_prompt for r in requests])
        return super().generate(requests)


def _group(prompt, rows, max_tokens=12, seed=0):
    return [
        GenerationRequest(
            user_prompt=prompt, max_tokens=max_tokens, seed=seed + i)
        for i in range(rows)
    ]


def _run_calls(engine, calls, limit=40):
    """Submit ``calls`` one after the other (so the backlog holds them in
    that order), step the engine until all are answered; returns each
    call's outbox."""
    boxes, threads, queued = [], [], 0
    for requests in calls:
        thread, out = _submit_async(engine, requests)
        queued += len(requests)
        assert _wait_until(
            lambda: engine.stats()["queue_depth"] == queued)
        threads.append(thread)
        boxes.append(out)
    for _ in range(limit):
        if all(boxes):
            break
        engine.run_iteration()
    for thread in threads:
        thread.join(timeout=5.0)
    assert all("result" in out for out in boxes), boxes
    return boxes


PROMPT_A = "one two three four five"  # 5 pseudo-tokens: 2 pages of 4
PROMPT_B = "six seven eight nine ten"

#: name -> (calls, engine options, the inner generate calls expected: one
#: (rows, distinct prompts) pair a cohort, in order).  12 tokens are 3 pages
#: of 4, so a group of N rows reserves 2 + 3N pages.
COHORT_CASES = {
    # One statement of best_of_n: one program, not four cohorts of eight.
    "one_call_of_32_is_one_cohort": (
        [_group(PROMPT_A, 32)], {}, [(32, 1)]),
    # Each large group alone, in arrival order: merged, the backend would
    # run them one after the other and both callers would wait for both.
    "two_calls_of_32_are_two_cohorts_in_arrival_order": (
        [_group(PROMPT_A, 32), _group(PROMPT_B, 32, seed=100)], {},
        [(32, 1), (32, 1)]),
    # 98 + 98 pages do not fit 128: the second group waits whole and is
    # admitted whole once the first has retired.
    "a_group_that_does_not_fit_waits_whole": (
        [_group(PROMPT_A, 32), _group(PROMPT_B, 32, seed=100)],
        {"num_pages": 128}, [(32, 1), (32, 1)]),
    # 98 pages never fit 64: the halves (50 each) go in its place.
    "a_group_the_pool_never_holds_goes_in_halves": (
        [_group(PROMPT_A, 32)], {"num_pages": 64}, [(16, 1), (16, 1)]),
    # Distinct prompts share a cohort as before, a row a slot.
    "eight_distinct_prompts_are_one_cohort": (
        [[r for i in range(8) for r in _group(f"prompt number {i}", 1, seed=i)]],
        {}, [(8, 8)]),
    # Small groups (habermas candidates) go on sharing one cohort.
    "small_groups_share_a_cohort": (
        [_group(f"draft for statement {i}", 4, seed=10 * i) for i in range(3)],
        {}, [(12, 3)]),
    # A call's large group runs alone, its other rows in the cohort after.
    "a_call_of_a_large_and_a_small_group": (
        [_group(PROMPT_A, 16) + _group(PROMPT_B, 2, seed=50)], {},
        [(16, 1), (2, 1)]),
    # More distinct prompts than slots: the slot count still caps them.
    "slots_cap_the_resident_prompts": (
        [[r for i in range(6) for r in _group(f"prompt number {i}", 1, seed=i)]],
        {"slots": 4, "min_fill": 1}, [(4, 4), (2, 2)]),
}


class TestCohortsFormByPrompt:
    @pytest.mark.parametrize("case", sorted(COHORT_CASES))
    def test_cohorts(self, case):
        calls, options, expected = COHORT_CASES[case]
        inner = _CohortRecorder()
        options = {"slots": 8, "page_size": 4, "num_pages": 512, **options}
        engine = DecodeEngine(inner, auto_start=False, **options)
        try:
            boxes = _run_calls(engine, calls)
            stats = engine.stats()
        finally:
            engine.close()
        assert [(len(c), len(set(c))) for c in inner.cohorts] == expected
        # arrival order: the cohorts' prompts follow the calls' prompts
        order = dict.fromkeys(p for cohort in inner.cohorts for p in cohort)
        assert list(order) == list(dict.fromkeys(
            r.user_prompt for requests in calls for r in requests))
        # every row answered as a solo backend answers it, in its place
        for requests, out in zip(calls, boxes):
            assert [r.text for r in out["result"]] == [
                r.text for r in FakeBackend().generate(requests)]
        assert stats["kv_pages_reserved"] == 0 and stats["slots_occupied"] == 0
        assert engine.pool.in_use == 0

    @pytest.mark.parametrize("inner_kind", ["fake", "tpu"])
    def test_submit_asks_for_a_prompts_ids_once_and_forms_the_same_groups(
            self, inner_kind, monkeypatch):
        """The page accounting tokenises a distinct prompt once a call,
        before it takes the condition the loop waits on, and the rows of
        one prompt share the ids and the group: on the fake's
        pseudo-tokenizer and through ``TPUBackend.token_ids``, where the
        call then adds two encodings in all (the accounting's text and the
        rendered prompt) however many rows it has."""
        from paged_capture import CountingTokenizer

        asked = []
        if inner_kind == "fake":
            class Counting(FakeBackend):
                def _tokenize(self, text):
                    asked.append(text)
                    return super()._tokenize(text)

            inner = Counting()
            ids_of = FakeBackend()._tokenize
        else:
            from consensus_tpu.backends.tpu import TPUBackend
            from consensus_tpu.models.tokenizer import ByteTokenizer

            inner = TPUBackend(model="tiny-gemma2", max_context=256)
            inner.tokenizer = CountingTokenizer(inner.tokenizer)
            asked = inner.tokenizer.texts
            ids_of = ByteTokenizer().encode
            launched = []

            def no_device(requests, prompt_ids):
                launched.append((len(requests), list(prompt_ids)))
                return [GenerationResult(text="x", token_ids=(1,))
                        for _ in requests]

            monkeypatch.setattr(inner, "_generate_shared", no_device)
        calls = [_group(PROMPT_A, 32), _group(PROMPT_A, 16) + _group(PROMPT_B, 2, seed=50)]
        engine = DecodeEngine(
            inner, slots=8, page_size=4, num_pages=2048, auto_start=False)
        try:
            held_while_tokenising = []
            tokenize_text = engine._tokenize_text

            def noting(text):
                if threading.current_thread() is not threading.main_thread():
                    held_while_tokenising.append(engine._work._is_owned())
                return tokenize_text(text)  # (the loop's own asks: stepped here)

            monkeypatch.setattr(engine, "_tokenize_text", noting)
            thread, out = _submit_async(engine, calls[0])
            assert _wait_until(lambda: engine.stats()["queue_depth"] == 32)
            assert asked == [PROMPT_A]
            (group,) = engine._gen_backlog
            assert [row.index for row in group] == list(range(32))
            assert all(row.prompt_ids == ids_of(PROMPT_A) for row in group)
            for _ in range(40):
                if out:
                    break
                engine.run_iteration()
            thread.join(timeout=5.0)
            assert len(out["result"]) == 32
            if inner_kind == "tpu":
                rendered = inner.tokenizer.chat_prompt(PROMPT_A, None)
                assert asked == [PROMPT_A, rendered]
                assert launched == [(32, ids_of(rendered, add_bos=True))]
            del asked[:]
            thread, out = _submit_async(engine, calls[1])
            assert _wait_until(lambda: engine.stats()["queue_depth"] == 18)
            # the first call's prompt is the backend's to remember, not the fake's
            assert asked == ([PROMPT_B] if inner_kind == "tpu" else [PROMPT_A, PROMPT_B])
            assert [[row.index for row in g] for g in engine._gen_backlog] == [
                list(range(16)), [16, 17]]
            assert held_while_tokenising == [False] * 3
        finally:
            engine.close()
        thread.join(timeout=5.0)

    @pytest.mark.parametrize("decode_steps, resident_rows, reserved", [
        # one blocking generate: the prompt's 2 pages once, 3 pages a row
        (None, 32, 2 + 32 * 3),
        # the paged stream: a row a slot, ceil((5 + 12) / 4) = 5 pages each
        (4, 8, 8 * 5),
    ])
    def test_reservation_is_what_the_cohort_holds(
        self, decode_steps, resident_rows, reserved
    ):
        reg = Registry()
        engine = DecodeEngine(
            FakeBackend(), slots=8, page_size=4, num_pages=512,
            auto_start=False, decode_steps=decode_steps, registry=reg,
        )
        try:
            thread, out = _submit_async(engine, _group(PROMPT_A, 32))
            assert _wait_until(lambda: engine.stats()["queue_depth"] == 32)
            with engine._lock:
                engine._admit()
            stats = engine.stats()
            assert stats["kv_pages_reserved"] == reserved
            assert stats["queue_depth"] == 32 - resident_rows
            assert stats["slots_occupied"] == (1 if decode_steps is None else 8)
            assert _counter_total(reg, "engine_admitted_total") == resident_rows
            for _ in range(80):
                if out:
                    break
                engine.run_iteration()
            thread.join(timeout=5.0)
            assert len(out["result"]) == 32
            stats = engine.stats()
            assert stats["kv_pages_reserved"] == 0
            assert engine.pool.in_use == 0
            # the pool never held more than was reserved
            assert stats["kv_pages_high_water"] <= reserved
        finally:
            engine.close()

    def test_a_waiting_group_stays_whole_in_the_backlog(self):
        engine = DecodeEngine(
            FakeBackend(), slots=8, page_size=4, num_pages=128,
            auto_start=False,
        )
        try:
            first = _submit_async(engine, _group(PROMPT_A, 32))
            assert _wait_until(lambda: engine.stats()["queue_depth"] == 32)
            second = _submit_async(engine, _group(PROMPT_B, 32, seed=100))
            assert _wait_until(lambda: engine.stats()["queue_depth"] == 64)
            with engine._lock:
                engine._admit()
            stats = engine.stats()
            # free slots and 30 free pages, and not one row of the second
            assert stats["slots_occupied"] == 1
            assert stats["kv_pages_reserved"] == 98
            assert stats["queue_depth"] == 32
            engine.run_iteration()  # the first runs and retires
            assert _wait_until(lambda: "result" in first[1])
            assert not second[1]
            with engine._lock:
                engine._admit()
            stats = engine.stats()
            assert stats["slots_occupied"] == 1 and stats["queue_depth"] == 0
            assert stats["kv_pages_reserved"] == 98
            for _ in range(4):
                engine.run_iteration()
            for thread, _ in (first, second):
                thread.join(timeout=5.0)
            assert len(second[1]["result"]) == 32
        finally:
            engine.close()

    def test_cancelled_group_is_evicted_whole(self):
        reg = Registry()
        engine = DecodeEngine(
            FakeBackend(), slots=8, page_size=4, num_pages=512,
            prefill_chunk=2, auto_start=False, registry=reg,
        )
        flag = {"cancelled": False}
        try:
            thread, out = _submit_async(
                engine, _group(PROMPT_A, 32), probe=lambda: flag["cancelled"])
            assert _wait_until(lambda: engine.stats()["queue_depth"] == 32)
            engine.run_iteration()  # admitted, 2 of 5 prompt tokens prefilled
            assert engine.stats()["slots_occupied"] == 1
            assert engine.pool.in_use == 1  # one chunk a group, not a row
            flag["cancelled"] = True
            engine.run_iteration()
            thread.join(timeout=5.0)
            assert isinstance(out.get("error"), RequestCancelled)
            stats = engine.stats()
            assert stats["slots_occupied"] == 0
            assert stats["kv_pages_reserved"] == 0 and engine.pool.in_use == 0
            assert _counter_total(reg, "engine_evicted_total") == 32
            assert _counter_total(reg, "engine_prefill_chunks_total") == 1
        finally:
            engine.close()


# ---------------------------------------------------------------------------
# Obs pins: no timeout flushes, no spurious wakeups, recompile-flat
# ---------------------------------------------------------------------------


class TestEngineObservability:
    def _run_ragged_load(self, registry, inner=None):
        inner = inner if inner is not None else FakeBackend(registry=registry)
        backend = BatchingBackend(
            inner, engine=True, registry=registry,
            engine_options={"slots": 4, "num_pages": 512},
        )
        results = {}

        def worker(i):
            with backend.session():
                results[i] = backend.generate(
                    [GenerationRequest(
                        user_prompt="word " * (3 + 7 * i),  # ragged lengths
                        max_tokens=8, seed=i,
                    )]
                )[0]

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(6)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10.0)
        backend.close()
        assert len(results) == 6
        return backend

    def test_no_timeout_flushes_and_no_spurious_wakeups(self):
        reg = Registry()
        self._run_ragged_load(reg)
        assert _counter_total(
            reg, "batching_flushes_total", reason="timeout") == 0
        assert _counter_total(reg, "batching_flushes_total") == 0
        assert _counter_total(reg, "batching_spurious_wakeups_total") == 0

    def test_engine_metric_families_recorded(self):
        reg = Registry()
        self._run_ragged_load(reg)
        snap = reg.snapshot()["families"]
        assert "engine_slot_occupancy" in snap
        assert _counter_total(reg, "engine_admitted_total") >= 6
        assert _counter_total(reg, "engine_prefill_chunks_total") >= 6
        tokens_iter = snap["engine_tokens_per_iteration"]["series"]
        assert tokens_iter and tokens_iter[0]["count"] >= 1
        pages = snap["kv_pages_in_use"]["series"]
        assert pages and pages[0]["max"] >= 1

    def test_bucket_recompiles_flat_across_ragged_load(self):
        """Slot lengths are data, not shapes: after warmup, ragged prompt
        lengths must add zero new compiled program shapes."""
        reg = Registry()
        inner = FakeBackend(registry=reg)
        self._run_ragged_load(reg, inner)  # warmup: first shape sightings
        cut = reg.snapshot()
        self._run_ragged_load(reg, inner)  # same bucketed shapes, new lengths
        delta = diff_snapshots(cut, reg.snapshot())
        assert bucket_recompiles(delta) == 0

    def test_engine_stats_surface(self):
        backend = BatchingBackend(
            FakeBackend(), engine=True,
            engine_options={"slots": 4, "num_pages": 128},
        )
        try:
            backend.generate(
                [GenerationRequest(user_prompt="hello", max_tokens=4, seed=0)]
            )
            stats = backend.engine.stats()
        finally:
            backend.close()
        assert stats["slots"] == 4
        assert stats["kv_pages"] == 128
        assert stats["iterations"] >= 1
        assert stats["kv_pages_high_water"] >= 1
        assert backend.batch_counts["generate"] >= 1  # aliased dispatch count


# ---------------------------------------------------------------------------
# Paged slot programs: token-for-token vs the dense forward pass
# ---------------------------------------------------------------------------


class TestPagedProgramNumerics:
    """Chunked paged prefill + paged decode must reproduce the dense
    ``forward`` pass exactly — same greedy tokens AND close logits — with
    the second slot idle (writes routed to the sink page)."""

    @pytest.mark.parametrize("cfg_name", ["tiny-gemma2", "tiny-llama3"])
    def test_matches_dense_forward(self, cfg_name):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from consensus_tpu.models import stepper
        from consensus_tpu.models.config import get_model_config
        from consensus_tpu.models.transformer import (
            forward, init_params, make_cache, project_logits,
        )

        cfg = get_model_config(cfg_name)
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        prompt = rng.randint(1, cfg.vocab_size, size=(7,)).astype(np.int32)
        n_decode = 5

        # Dense reference: prefill then greedy decode through KVCache.
        cache = make_cache(cfg, 1, 32, jnp.float32)
        logits, cache = forward(
            params, cfg, jnp.asarray(prompt)[None, :],
            jnp.arange(7)[None, :], jnp.ones((1, 7), bool), cache, 0,
        )
        dense_tokens, dense_logits = [], []
        last, cur = logits[0, -1], 7
        for _ in range(n_decode):
            nxt = int(jnp.argmax(last))
            dense_tokens.append(nxt)
            dense_logits.append(np.asarray(last))
            lg, cache = forward(
                params, cfg, jnp.asarray([[nxt]], jnp.int32),
                jnp.asarray([[cur]], jnp.int32), jnp.ones((1, 1), bool),
                cache, cur,
            )
            last, cur = lg[0, -1], cur + 1

        # Paged path: 2 slots (slot 1 idle), 4-token prefill chunks.
        page_size, num_pages, max_blocks, chunk = 4, 16, 8, 4
        pool = PagePool(num_pages, page_size)
        state = stepper.make_page_state(cfg, num_pages, page_size, jnp.float32)
        sink = num_pages
        table = BlockTable(0)

        def write_cursors(n_new):
            return [
                (table.pages[t // page_size], t % page_size)
                for t in range(table.num_tokens - n_new, table.num_tokens)
            ]

        def slot_arrays():
            tables = np.full((2, max_blocks), -1, np.int32)
            tables[0] = table.as_array(max_blocks)
            lengths = np.array([table.num_tokens, 0], np.int32)
            return jnp.asarray(tables), jnp.asarray(lengths)

        hidden = None
        for start in range(0, len(prompt), chunk):
            piece = prompt[start : start + chunk]
            table.append_tokens(pool, len(piece))
            tok = np.zeros((2, chunk), np.int32)
            cvalid = np.zeros((2, chunk), bool)
            wp = np.full((2, chunk), sink, np.int32)
            wo = np.zeros((2, chunk), np.int32)
            tok[0, : len(piece)] = piece
            cvalid[0, : len(piece)] = True
            for j, (p, o) in enumerate(write_cursors(len(piece))):
                wp[0, j], wo[0, j] = p, o
            tables, lengths = slot_arrays()
            hidden, state = stepper.paged_prefill_chunk(
                params, cfg, jnp.asarray(tok), jnp.asarray(cvalid), state,
                tables, lengths, jnp.asarray(wp), jnp.asarray(wo),
            )
        last = project_logits(params, cfg, hidden)[0]

        paged_tokens = []
        for step in range(n_decode):
            nxt = int(jnp.argmax(last))
            paged_tokens.append(nxt)
            np.testing.assert_allclose(
                np.asarray(last), dense_logits[step], rtol=2e-4, atol=2e-4,
            )
            table.append_tokens(pool, 1)
            page, offset = table.write_cursor(pool)
            tables, lengths = slot_arrays()
            lg, state = stepper.paged_decode_step(
                params, cfg, jnp.asarray([nxt, 0], jnp.int32), state,
                tables, lengths,
                jnp.asarray([page, sink], np.int32),
                jnp.asarray([offset, 0], np.int32),
            )
            last = lg[0]
        assert paged_tokens == dense_tokens

    @pytest.mark.parametrize("cfg_name", ["tiny-gemma2", "tiny-llama3"])
    def test_gather_step_reads_shared_pages_without_copying(self, cfg_name):
        """The prefix-cache gather path: slot 1 adopts slot 0's prompt
        pages (refcounted, read-only) and ``paged_gather_step`` must
        reproduce the dense last-prompt-position logits from them — while
        leaving every shared page's bytes untouched."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from consensus_tpu.models import stepper
        from consensus_tpu.models.config import get_model_config
        from consensus_tpu.models.transformer import (
            forward, init_params, make_cache, project_logits,
        )

        cfg = get_model_config(cfg_name)
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(1)
        prompt = rng.randint(1, cfg.vocab_size, size=(8,)).astype(np.int32)

        # Dense reference logits at the last prompt position.
        cache = make_cache(cfg, 1, 32, jnp.float32)
        logits, _ = forward(
            params, cfg, jnp.asarray(prompt)[None, :],
            jnp.arange(8)[None, :], jnp.ones((1, 8), bool), cache, 0,
        )
        dense_last = np.asarray(logits[0, -1])

        # Slot 0 prefills the prompt into its own pages (page-aligned).
        page_size, num_pages, max_blocks = 4, 16, 8
        pool = PagePool(num_pages, page_size)
        state = stepper.make_page_state(cfg, num_pages, page_size, jnp.float32)
        sink = num_pages
        owner = BlockTable(0)
        owner.append_tokens(pool, 8)
        tok = np.zeros((2, 8), np.int32)
        cvalid = np.zeros((2, 8), bool)
        wp = np.full((2, 8), sink, np.int32)
        wo = np.zeros((2, 8), np.int32)
        tok[0] = prompt
        cvalid[0] = True
        for t in range(8):
            wp[0, t] = owner.pages[t // page_size]
            wo[0, t] = t % page_size
        tables = np.full((2, max_blocks), -1, np.int32)
        tables[0] = owner.as_array(max_blocks)
        hidden, state = stepper.paged_prefill_chunk(
            params, cfg, jnp.asarray(tok), jnp.asarray(cvalid), state,
            jnp.asarray(tables), jnp.asarray([8, 0], np.int32),
            jnp.asarray(wp), jnp.asarray(wo),
        )
        prefill_last = np.asarray(project_logits(params, cfg, hidden)[0])
        np.testing.assert_allclose(
            prefill_last, dense_last, rtol=2e-4, atol=2e-4
        )

        # Slot 1 adopts the SAME pages via the refcounted share path.
        adopter = BlockTable(1)
        adopter.adopt_shared(pool, owner.pages, 8)
        assert all(pool.refcount(p) == 2 for p in owner.pages)
        g_tables = np.full((2, max_blocks), -1, np.int32)
        g_tables[0] = owner.as_array(max_blocks)
        g_tables[1] = adopter.as_array(max_blocks)
        shared_before = np.asarray(
            state.k_pages[:, owner.pages, :, :, :]
        ).copy()
        g_logits, state = stepper.paged_gather_step(
            params, cfg,
            jnp.asarray([int(prompt[-1]), int(prompt[-1])], jnp.int32),
            state, jnp.asarray(g_tables), jnp.asarray([8, 8], np.int32),
        )
        # Both slots read the one shared copy and reproduce the dense
        # logits at the last prompt position...
        np.testing.assert_allclose(
            np.asarray(g_logits[0]), dense_last, rtol=2e-4, atol=2e-4
        )
        np.testing.assert_allclose(
            np.asarray(g_logits[1]), dense_last, rtol=2e-4, atol=2e-4
        )
        # ...and the shared pages' bytes are bit-identical afterwards
        # (every write went to the sink page).
        shared_after = np.asarray(state.k_pages[:, owner.pages, :, :, :])
        np.testing.assert_array_equal(shared_before, shared_after)


# ---------------------------------------------------------------------------
# Multi-token decode: K-step on-device windows (PR 15)
# ---------------------------------------------------------------------------


class TestMultiTokenByteIdentity:
    """``decode_steps`` must never change results — for any K, for every
    method: the K-step scan replays the sequential per-row key-split
    schedule and the engine's stream scheduling retires the same rows."""

    @pytest.mark.parametrize("method", sorted(METHOD_PARAMS))
    def test_engine_k_family_matches_legacy_all_methods(self, method):
        params = METHOD_PARAMS[method]
        solo = get_method_generator(
            method, FakeBackend(), dict(params)
        ).generate_statement(ISSUE, OPINIONS)

        for k in (1, 4, 8):
            engined = BatchingBackend(
                FakeBackend(), engine=True,
                engine_options={"slots": 4, "num_pages": 512,
                                "decode_steps": k},
            )
            try:
                via_engine = get_method_generator(
                    method, engined, dict(params)
                ).generate_statement(ISSUE, OPINIONS)
                stats = engined.engine.stats()
            finally:
                engined.close()
            assert via_engine == solo, f"{method}: K={k} diverged"
            assert stats["decode_steps"] == k


def _drain_stream(stream):
    """Drive a generate stream to completion; returns (results, windows)."""
    results, windows = {}, 0
    while not stream.finished:
        stream.dispatch()
        _, finished = stream.collect()
        results.update(finished)
        windows += 1
        assert windows < 200, "stream failed to drain"
    stream.close()
    return results, windows


class TestMultiTokenDecodeTPU:
    """Real-model multi-token decode: the paged K-step scan against the
    paged K=1 stream, the dense legacy path, and the engine seam.

    Dense-vs-paged comparisons ride on a pinned cohort verified free of
    argmax/sampling near-ties (paged and dense forwards differ by ~2e-4 in
    the logits; a near-tie can legitimately flip a sampled token, which is
    a numerics property, not a scheduling bug — the K-family comparisons
    are exact by construction and carry the real invariant)."""

    COHORT = (
        ("Say something about apples.", 11, 12, 0.8),
        ("Hi", 22, 5, 0.0),
        ("A longer prompt that should span several pages of the stream "
         "pool for testing purposes.", 33, 20, 0.9),
    )

    @pytest.fixture(scope="class")
    def tpu_backend(self):
        from consensus_tpu.backends.tpu import TPUBackend

        return TPUBackend(model="tiny-gemma2", max_context=128, base_seed=7)

    def _requests(self):
        return [
            GenerationRequest(
                user_prompt=prompt, seed=seed, max_tokens=mt, temperature=t,
            )
            for prompt, seed, mt, t in self.COHORT
        ]

    def test_k_family_byte_identical_and_matches_dense(self, tpu_backend):
        legacy = tpu_backend.generate(self._requests())
        outputs = {}
        for k in (1, 4, 8):
            stream = tpu_backend.generate_stream(
                self._requests(), decode_steps=k
            )
            results, windows = _drain_stream(stream)
            outputs[k] = [
                (results[i].text, results[i].token_ids,
                 results[i].finish_reason)
                for i in range(len(self.COHORT))
            ]
            # Window count collapses with K: 21 sample steps (20-token
            # budget + eos-check) need 21 / 6 / 3 dispatches.
            assert windows <= -(-21 // k) + 1
        assert outputs[1] == outputs[4] == outputs[8]
        assert outputs[1] == [
            (r.text, r.token_ids, r.finish_reason) for r in legacy
        ]

    def test_engine_decode_steps_matches_direct_stream(self, tpu_backend):
        direct = _drain_stream(
            tpu_backend.generate_stream(self._requests(), decode_steps=4)
        )[0]
        engined = BatchingBackend(
            tpu_backend, engine=True,
            engine_options={"slots": 4, "num_pages": 512, "decode_steps": 4},
        )
        try:
            via_engine = engined.generate(self._requests())
            stats = engined.engine.stats()
            mfu = stats["mfu_attribution"]
        finally:
            engined.close()
        for i, result in enumerate(via_engine):
            assert (result.text, result.token_ids, result.finish_reason) == (
                direct[i].text, direct[i].token_ids, direct[i].finish_reason
            )
        # The whole point: way fewer host iterations than tokens.
        assert stats["iterations"] / max(mfu["tokens"], 1) < 0.5

    def test_eos_early_exit_freezes_row_mid_scan(self, tpu_backend):
        """A row that samples EOS inside a K-step window must freeze there:
        emitted stops, lengths stop advancing, hit_eos latches, and every
        later write of that row lands in the sink — pool pages beyond the
        frozen cursor stay byte-identical to their post-prefill state."""
        import numpy as np

        # Learn the greedy continuation, then declare its 3rd token EOS.
        probe = _drain_stream(
            tpu_backend.generate_stream(
                [GenerationRequest(
                    user_prompt="freeze me", seed=5, max_tokens=8,
                    temperature=0.0,
                )],
                decode_steps=1,
            )
        )[0][0]
        assert len(probe.token_ids) == 8
        eos_token = probe.token_ids[2]
        if eos_token in probe.token_ids[:2]:
            pytest.skip("greedy continuation repeats the chosen EOS early")

        original_eos = tpu_backend.tokenizer.eos_ids
        tpu_backend.tokenizer.eos_ids = (int(eos_token),)
        try:
            stream = tpu_backend.generate_stream(
                [GenerationRequest(
                    user_prompt="freeze me", seed=5, max_tokens=8,
                    temperature=0.0,
                )],
                decode_steps=8,
            )
            prefill_pages = np.asarray(stream._state.k_pages).copy()
            prompt_len = int(np.asarray(stream._lengths)[0])
            tables = np.asarray(stream._tables)
            page_size = prefill_pages.shape[2]
            stream.dispatch()
            _, finished = stream.collect()
            assert stream.finished  # froze inside the FIRST window
            frozen_len = int(np.asarray(stream._lengths)[0])
            pages_after = np.asarray(stream._state.k_pages)
            stream.close()
        finally:
            tpu_backend.tokenizer.eos_ids = original_eos

        result = finished[0]
        assert result.finish_reason == "stop"
        assert result.token_ids == probe.token_ids[:2]
        # The cursor froze after two emitted tokens; the EOS sample and
        # every later step of the window wrote only the sink.
        assert frozen_len == prompt_len + 2
        row_pages = [int(p) for p in tables[0] if p >= 0]
        # Reserved pages wholly beyond the frozen cursor: byte-identical
        # to their post-prefill state (all-zero init, never written).
        first_free = -(-frozen_len // page_size)
        for page in row_pages[first_free:]:
            np.testing.assert_array_equal(
                pages_after[:, page], prefill_pages[:, page]
            )
        # The partially-filled page: offsets past the cursor untouched.
        if frozen_len % page_size:
            page = row_pages[frozen_len // page_size]
            np.testing.assert_array_equal(
                pages_after[:, page, frozen_len % page_size:],
                prefill_pages[:, page, frozen_len % page_size:],
            )

    def test_window_crossing_page_boundary_spares_shared_pages(
        self, tpu_backend
    ):
        """A K-step window that crosses a page boundary in-scan writes only
        pages reserved at dispatch time.  Rows adopting shared prefix pages
        (prefix-cache discipline) must leave the shared bytes untouched."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from consensus_tpu.models import stepper
        from consensus_tpu.models.config import get_model_config
        from consensus_tpu.models.transformer import init_params, project_logits

        cfg = get_model_config("tiny-gemma2")
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(3)
        prompt = rng.randint(1, cfg.vocab_size, size=(8,)).astype(np.int32)
        page_size, max_blocks = 4, 8
        # Pages: 0-1 shared prompt, 2-3 row0 private, 4-5 row1 private.
        num_pages, sink = 6, 6
        state = stepper.make_page_state(cfg, num_pages, page_size, jnp.float32)
        tables = np.full((2, max_blocks), -1, np.int32)
        tables[0, :4] = [0, 1, 2, 3]
        tables[1, :4] = [0, 1, 4, 5]  # adopts the shared prompt pages

        # Prefill the shared prompt ONCE through row 0's table.
        tok = np.zeros((2, 8), np.int32)
        cval = np.zeros((2, 8), bool)
        wp = np.full((2, 8), sink, np.int32)
        wo = np.zeros((2, 8), np.int32)
        tok[0] = prompt
        cval[0] = True
        for t in range(8):
            wp[0, t] = t // page_size
            wo[0, t] = t % page_size
        hidden, state = stepper.paged_prefill_chunk(
            params, cfg, jnp.asarray(tok), jnp.asarray(cval), state,
            jnp.asarray(tables), jnp.asarray([8, 0], np.int32),
            jnp.asarray(wp), jnp.asarray(wo),
        )
        shared_before = np.asarray(state.k_pages[:, :2]).copy()
        logits0 = project_logits(params, cfg, hidden)
        logits = jnp.stack([logits0[0], logits0[0]])

        # Both rows decode 6 greedy tokens from the shared prefix: the
        # window crosses the page-2 boundary (length 8 -> 14) in-scan.
        keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray([1, 2], jnp.uint32))
        out = stepper.paged_decode_steps(
            params, cfg, logits, state, jnp.asarray(tables),
            jnp.asarray([8, 8], np.int32), keys,
            jnp.zeros(2, bool), jnp.asarray([6, 6], np.int32),
            jnp.zeros(2, bool),
            temperature=jnp.zeros(2, jnp.float32), num_steps=8,
        )
        tokens, emitted, state_after = out[0], out[1], out[3]
        tokens, emitted = np.asarray(tokens), np.asarray(emitted)
        # Identical rows, identical greedy continuations across the
        # boundary; both emit exactly the 6-token budget.
        np.testing.assert_array_equal(tokens[0], tokens[1])
        assert emitted.sum(axis=1).tolist() == [6, 6]
        np.testing.assert_array_equal(
            np.asarray(out[4]), [14, 14]  # lengths advanced to 8 + 6
        )
        # Shared prompt pages: byte-identical after the window.
        np.testing.assert_array_equal(
            shared_before, np.asarray(state_after.k_pages[:, :2])
        )
        # Each row's private writes live in its OWN reserved pages and the
        # two rows' continuation KV bytes match (same tokens, positions).
        kp = np.asarray(state_after.k_pages)
        np.testing.assert_array_equal(kp[:, 2:4], kp[:, 4:6])

    def test_dp4_matches_dp1(self):
        """Sharding the stream's slot axis over data must not change a
        single emitted token (conftest provides 8 virtual CPU devices)."""
        from consensus_tpu.backends.tpu import TPUBackend

        def run(dp):
            backend = TPUBackend(
                model="tiny-gemma2", max_context=128, base_seed=7, dp=dp,
            )
            requests = [
                GenerationRequest(
                    user_prompt=f"device parallel prompt {i}", seed=100 + i,
                    max_tokens=6 + i, temperature=0.7,
                )
                for i in range(4)
            ]
            results = _drain_stream(
                backend.generate_stream(requests, decode_steps=4)
            )[0]
            return [
                (results[i].text, results[i].token_ids,
                 results[i].finish_reason)
                for i in range(4)
            ]

        assert run(1) == run(4)


class TestLedgerDispatchBlockSplit:
    """PR 15 splits the ledger's device axis into dispatch (host enqueue)
    and block (waiting on results); the sum must still cover wall time."""

    def test_split_sums_and_coverage(self):
        engine = DecodeEngine(
            FakeBackend(), slots=8, num_pages=512, auto_start=False,
            decode_steps=4,
        )
        outboxes, threads = [], []
        try:
            for i in range(4):
                out = {}

                def worker(i=i, out=out):
                    out["result"] = engine.submit("generate", [
                        GenerationRequest(
                            user_prompt=f"prompt {i} with extra words",
                            max_tokens=8, seed=i,
                        )])

                thread = threading.Thread(target=worker, daemon=True)
                thread.start()
                threads.append(thread)
                outboxes.append(out)
            assert _wait_until(
                lambda: engine.stats()["queue_depth"] == 4)
            for _ in range(12):
                engine.run_iteration()
                if all("result" in out for out in outboxes):
                    break
            for thread in threads:
                thread.join(timeout=10.0)
            assert all("result" in out for out in outboxes)
            report = engine.stats()["mfu_attribution"]
            assert report["coverage"] >= 0.95  # the acceptance bar
            assert report["dispatch_s"] >= 0.0
            assert report["block_s"] > 0.0
            assert report["device_s"] == pytest.approx(
                report["dispatch_s"] + report["block_s"], abs=1e-5)
            # Fractions round to 4 decimals independently, so the split can
            # differ from device_fraction by one ulp each.
            assert report["dispatch_fraction"] + report["block_fraction"] \
                == pytest.approx(report["device_fraction"], abs=2e-4)
            # The CPU caveat ships in the report itself, not just the docs.
            assert "note" in report and "CPU" in report["note"]
            assert engine.stats()["decode_steps"] == 4
        finally:
            engine.close()

    def test_legacy_device_kwarg_books_as_block(self):
        from consensus_tpu.obs.trace import IterationLedger

        ledger = IterationLedger()
        ledger.record(
            start_s=0.0, end_s=1.0, idle_s=0.1, device_s=0.5,
            host={"sweep": 0.2}, tokens=4, cohort=1,
        )
        report = ledger.mfu_attribution()
        assert report["block_s"] == pytest.approx(0.5)
        assert report["dispatch_s"] == 0.0
        assert report["device_s"] == pytest.approx(0.5)
