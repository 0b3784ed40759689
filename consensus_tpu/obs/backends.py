"""The instrument set backends record into, and its derived readings.

Every backend that pads work onto a device grid answers three questions
through one :class:`BackendInstruments` handle:

* **Padding efficiency** — of the tokens a padded ``rows × width`` program
  processed, how many were real?  Recorded per (kind, rows, width) bucket
  so a lopsided bucket ladder shows up as one bad cell, not a blended
  average.
* **Compile cache** — was this padded program shape seen before?  First
  sightings count as compiles, repeats as cache hits; the compile/launch
  ratio is the recompile pressure the bucket ladder is supposed to bound.
* **Host↔device transfer** — time spent placing batches (H2D) and fetching
  results (D2H).  Note: on asynchronous-dispatch runtimes the D2H fetch
  blocks on device execution, so ``backend_d2h_seconds`` is an upper bound
  that includes device time still in flight.

``padding_efficiency`` / ``bucket_recompiles`` reduce a registry snapshot
to the two headline numbers ``bench.py`` and ``metrics.json`` report.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Iterator, Mapping, Optional, Set, Tuple

from consensus_tpu.obs.metrics import (
    DEFAULT_TIME_BUCKETS,
    Registry,
    get_registry,
)


class BackendInstruments:
    """Per-backend handles on the shared metric families.

    ``backend`` labels every series (e.g. ``"tpu"``, ``"fake"``) so two
    backends in one process — the tp=2 parity harness runs both — stay
    separable in one registry.
    """

    def __init__(self, backend: str, registry: Optional[Registry] = None) -> None:
        reg = registry if registry is not None else get_registry()
        self.backend = backend
        self.registry = reg
        self._useful = reg.counter(
            "backend_padding_useful_tokens_total",
            "Real (non-padding) tokens processed by padded device programs.",
            labels=("backend", "kind", "rows", "width"),
        )
        self._allocated = reg.counter(
            "backend_padding_allocated_tokens_total",
            "Total token slots (rows x width) allocated by padded device programs.",
            labels=("backend", "kind", "rows", "width"),
        )
        self._compiles = reg.counter(
            "backend_bucket_compiles_total",
            "First sighting of a padded program shape (a compile, or a "
            "compile-cache load).",
            labels=("backend", "kind"),
        )
        self._cache_hits = reg.counter(
            "backend_bucket_cache_hits_total",
            "Launches whose padded program shape was already compiled.",
            labels=("backend", "kind"),
        )
        self._h2d = reg.histogram(
            "backend_h2d_seconds",
            "Host-to-device batch placement time.",
            labels=("backend",),
            buckets=DEFAULT_TIME_BUCKETS,
        )
        self._d2h = reg.histogram(
            "backend_d2h_seconds",
            "Device-to-host result fetch time (includes in-flight device "
            "execution under async dispatch).",
            labels=("backend",),
            buckets=DEFAULT_TIME_BUCKETS,
        )
        self._state_forks = reg.counter(
            "backend_state_fork_rows_total",
            "Rows that started from a copy of another row's recurrent state "
            "(one trunk forked to its decode rows, a context's snapshot to "
            "its score rows), by kind of call.",
            labels=("backend", "kind"),
        )
        self._recurrent_bytes = reg.gauge(
            "backend_recurrent_state_bytes",
            "Bytes of recurrent state the latest program launch held by row, "
            "beside its key-value pages (0 for a configuration without "
            "recurrent layers).",
            labels=("backend",),
        )
        self._prefix_declined = reg.counter(
            "backend_prefix_runs_declined_total",
            "Prefix-cache page runs neither donated nor adopted because the "
            "configuration has recurrent layers and a run of pages carries "
            "no state at its end, by operation (lookup or insert).",
            labels=("backend", "op"),
        )
        self._moe_assignments = reg.counter(
            "backend_moe_assignments_total",
            "Assignments of rows to routed experts that the counted program "
            "launches made (generation's decode steps, the paged prefill and "
            "score chunks): to experts held here, which the program "
            "computed, and to absent ones, which it skipped.",
            labels=("backend", "held"),
        )
        self._moe_expert_calls = reg.counter(
            "backend_moe_expert_calls_total",
            "Products of a held expert in those launches: held experts x "
            "routed layers x passes through the layers (a decode step, a "
            "chunk).  Held assignments over this is the rows an expert's "
            "product sees.",
            labels=("backend",),
        )
        self._moe_experts_read = reg.counter(
            "backend_moe_experts_read_total",
            "Products of a held expert in those launches that ran on at least "
            "one row: held experts that a pass's rows reached, whose "
            "matrices alone were read, summed over routed layers and passes. "
            " Over backend_moe_expert_calls_total this is the share of the "
            "held experts read.",
            labels=("backend",),
        )
        self._kv_bytes_per_token = reg.gauge(
            "backend_kv_bytes_per_token",
            "Bytes of keys and values one position holds over the layers of "
            "one kind of attention (all, full, window, latent: a latent "
            "position is one buffer's [latent | rotary key], counted once).",
            labels=("backend", "kind"),
        )
        self._mla_queries = reg.counter(
            "backend_mla_queries_total",
            "Query positions x latent layers that the launches served in "
            "each form of latent attention, from the launches' shapes: "
            "absorbed (queries folded through the heads' key matrices, the "
            "cached latents read as they lie) or expanded (every head's keys "
            "and values made of the gathered latents first).",
            labels=("backend", "form"),
        )
        self._mla_keys_expanded = reg.counter(
            "backend_mla_keys_expanded_total",
            "Cached latent positions x latent layers that the launches of "
            "the expanded form turned into every head's keys and values: "
            "each row's gathered positions, a context that rows share once "
            "a row.",
            labels=("backend",),
        )
        self._tokenized = reg.counter(
            "backend_tokenize_texts_total",
            "Texts whose token ids the backend was asked for: those the "
            "tokenizer ran on (encoded) and those answered from the ids of "
            "an earlier asking (reused).",
            labels=("backend", "outcome"),
        )
        self._seen_lock = threading.Lock()
        self._seen_shapes: Set[Tuple[str, Tuple[int, ...]]] = set()

    # -- padding -------------------------------------------------------------

    def record_padding(
        self,
        kind: str,
        rows: int,
        width: int,
        useful_tokens: int,
        allocated_tokens: Optional[int] = None,
    ) -> None:
        """One padded program call: ``useful_tokens`` real tokens inside an
        ``rows × width`` grid (override ``allocated_tokens`` for programs
        whose footprint isn't the plain product, e.g. trunk+segment)."""
        allocated = rows * width if allocated_tokens is None else allocated_tokens
        self._useful.labels(self.backend, kind, rows, width).inc(useful_tokens)
        self._allocated.labels(self.backend, kind, rows, width).inc(allocated)

    def record_tokenized(self, encoded: bool) -> None:
        """One text's ids handed out: the tokenizer ran (``encoded``) or an
        earlier asking's ids were reused."""
        self._tokenized.labels(
            self.backend, "encoded" if encoded else "reused").inc()

    # -- compile cache -------------------------------------------------------

    def record_launch(self, kind: str, shape: Tuple[int, ...]) -> bool:
        """Count a program launch; returns True on the shape's first
        sighting (a compile), False on a cache hit."""
        key = (kind, tuple(int(d) for d in shape))
        with self._seen_lock:
            first = key not in self._seen_shapes
            if first:
                self._seen_shapes.add(key)
        if first:
            self._compiles.labels(self.backend, kind).inc()
        else:
            self._cache_hits.labels(self.backend, kind).inc()
        return first

    # -- recurrent state -----------------------------------------------------

    def record_state_fork(self, kind: str, rows: int, nbytes: int) -> None:
        """One launch of call kind ``kind`` that forks ``rows`` rows' state
        and holds ``nbytes`` of recurrent state while it runs."""
        self._state_forks.labels(self.backend, kind).inc(rows)
        self._recurrent_bytes.labels(self.backend).set(nbytes)

    def record_prefix_run_declined(self, op: str, runs: int = 1) -> None:
        self._prefix_declined.labels(self.backend, op).inc(runs)

    # -- routed experts, caches by kind --------------------------------------

    def record_moe(
        self, held: int, assignments: int, expert_calls: int, experts_read: int
    ) -> None:
        """Launches that made ``assignments`` assignments of rows to experts,
        ``held`` of them to experts held here, in ``expert_calls`` products
        of a held expert, ``experts_read`` of which some row reached."""
        self._moe_assignments.labels(self.backend, "held").inc(held)
        self._moe_assignments.labels(self.backend, "absent").inc(
            assignments - held)
        self._moe_expert_calls.labels(self.backend).inc(expert_calls)
        self._moe_experts_read.labels(self.backend).inc(experts_read)

    def record_kv_bytes_per_token(self, kind: str, nbytes: float) -> None:
        self._kv_bytes_per_token.labels(self.backend, kind).set(nbytes)

    def record_mla(self, form: str, queries: int, keys_expanded: int) -> None:
        """A launch's ``queries`` query positions x latent layers in
        ``form`` (absorbed, expanded), and the latent positions x layers it
        expanded to heads."""
        self._mla_queries.labels(self.backend, form).inc(queries)
        if keys_expanded:
            self._mla_keys_expanded.labels(self.backend).inc(keys_expanded)

    # -- transfers -----------------------------------------------------------

    @contextlib.contextmanager
    def time_h2d(self) -> Iterator[None]:
        start = time.perf_counter()
        try:
            yield
        finally:
            self._h2d.labels(self.backend).observe(time.perf_counter() - start)

    @contextlib.contextmanager
    def time_d2h(self) -> Iterator[None]:
        start = time.perf_counter()
        try:
            yield
        finally:
            self._d2h.labels(self.backend).observe(time.perf_counter() - start)


# -- derived readings --------------------------------------------------------


def _sum_series(
    snapshot: Mapping[str, Any], name: str, backend: Optional[str] = None
) -> float:
    total = 0.0
    family = snapshot.get("families", {}).get(name)
    for series in (family or {}).get("series", ()):
        if backend is not None and series["labels"].get("backend") != backend:
            continue
        total += series["value"]
    return total


def padding_efficiency(
    snapshot: Mapping[str, Any], backend: Optional[str] = None
) -> Optional[float]:
    """useful / allocated tokens across all padded programs in ``snapshot``
    (optionally one backend); None when nothing was recorded."""
    allocated = _sum_series(
        snapshot, "backend_padding_allocated_tokens_total", backend
    )
    if allocated <= 0:
        return None
    useful = _sum_series(snapshot, "backend_padding_useful_tokens_total", backend)
    return useful / allocated


def bucket_recompiles(
    snapshot: Mapping[str, Any], backend: Optional[str] = None
) -> int:
    """Distinct padded program shapes compiled in ``snapshot``'s window."""
    return int(_sum_series(snapshot, "backend_bucket_compiles_total", backend))
