"""The instrument set backends record into, and its derived readings.

Every backend that pads work onto a device grid answers three questions
through one :class:`BackendInstruments` handle:

* **Padding efficiency** — of the tokens a padded ``rows × width`` program
  processed, how many were real?  Recorded per (kind, rows, width) bucket
  so a lopsided bucket ladder shows up as one bad cell, not a blended
  average.
* **Padded buckets** — was this padded ``(kind, rows, width)`` seen before
  in this backend?  First sightings and repeats are counted; the ratio is
  the pressure the bucket ladder is supposed to bound.  A bucket is not a
  program: JAX compiles per program and per shape of every argument (a
  score matrix's page pool among them), which :class:`CompileRecord`
  counts.
* **Host↔device transfer** — time spent placing batches (H2D) and fetching
  results (D2H).  Note: on asynchronous-dispatch runtimes the D2H fetch
  blocks on device execution, so ``backend_d2h_seconds`` is an upper bound
  that includes device time still in flight.

One process-wide :class:`CompileRecord` (``install_compile_record``, called
by ``utils/compile_cache.enable_compile_cache``) hears JAX's own events for
every stage of making an executable (trace, lower, compile or read from the
persistent cache), by jitted function: counters on ``GET /metrics``, the
``compiles`` block of ``/healthz``, and a ``backend.compile`` span for each
stage.

``padding_efficiency`` / ``bucket_recompiles`` reduce a registry snapshot
to the two headline numbers ``bench.py`` and ``metrics.json`` report.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from typing import Any, Dict, Iterator, List, Mapping, Optional, Set, Tuple

from consensus_tpu.obs.metrics import (
    DEFAULT_TIME_BUCKETS,
    Registry,
    get_registry,
)
from consensus_tpu.obs.trace import span

logger = logging.getLogger(__name__)


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
            "First sightings of a padded (kind, rows, width) bucket in this "
            "backend; not compiles: backend_compile_programs_total counts "
            "those.",
            labels=("backend", "kind"),
        )
        self._cache_hits = reg.counter(
            "backend_bucket_cache_hits_total",
            "Launches whose padded (kind, rows, width) bucket this backend "
            "had launched before.",
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

    # -- padded buckets ------------------------------------------------------

    def record_launch(self, kind: str, shape: Tuple[int, ...]) -> bool:
        """Count a program launch; returns True on the padded bucket's first
        sighting in this backend, False on a repeat.  What JAX compiled is
        :class:`CompileRecord`'s."""
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
    """Padded (kind, rows, width) buckets first met in ``snapshot``'s
    window; the programs JAX compiled are ``backend_compile_programs_total``."""
    return int(_sum_series(snapshot, "backend_bucket_compiles_total", backend))


# -- what JAX paid to make this process's executables -----------------------

#: JAX's stages of making an executable, by the event that announces each
#: (``jax._src.dispatch.LogElapsedTimeContextManager``: ``record_scalar`` at
#: the start, ``record_event_time_span`` at the end, also when the stage
#: raises).  ``compile`` wraps ``compile_or_get_cached``: a read from the
#: persistent cache is inside it.
COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_READ_TIME = "/jax/compilation_cache/cache_retrieval_time_sec"


def _program_name(fun_name: Any) -> str:
    """A jitted function's name: ``jit(f)`` (lowering and compiling) and
    ``f`` (tracing) are one program."""
    name = str(fun_name or "")
    head, paren, rest = name.partition("(")
    if paren and head.isidentifier() and rest.endswith(")"):
        name = rest[:-1]
    return name[:63]


class _Stage:
    """One open stage on one thread."""

    __slots__ = ("stage", "program", "folded", "nested_s", "span", "late",
                 "cache_read")

    def __init__(self, stage: str, program: str, folded: bool) -> None:
        self.stage = stage
        self.program = program
        #: A trace inside another open stage: the jitted function is inlined
        #: into the program that stage makes, and its time is that stage's.
        self.folded = folded
        #: Seconds of the recorded stages inside this one.
        self.nested_s = 0.0
        self.span: Any = None
        self.late: Dict[str, Any] = {}
        self.cache_read = False


class CompileRecord:
    """Seconds by stage, meetings and persistent-cache reads, by jitted
    function, from JAX's own compile events.

    A meeting is one backend-compile event: an executable compiled, or read
    from the persistent cache (outcome ``cache_read``).  Seconds are counted
    once: a stage's own seconds are its span less the recorded stages inside
    it on the same thread, and a trace inside another open stage (a jitted
    function called in another's trace) is that stage's time, not a program
    of its own.  Each recorded stage is a ``backend.compile`` span on the
    thread that compiles: a child in the request's tree where that thread
    works for one, and a ``TraceAnnotation`` on the profiler's host plane.
    A stage's listeners never raise into JAX: a fault is logged."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._local = threading.local()
        self._totals: Dict[str, float] = {
            "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
            "cache_read_s": 0.0, "programs": 0, "cache_reads": 0}
        self._by_program: Dict[str, Dict[str, float]] = {}

    def _open(self) -> List[_Stage]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _families(self) -> Tuple[Any, Any]:
        # Looked up at every stage's end: a registry reset in between leaves
        # no stale handle behind (a process compiles some hundreds of times).
        reg = get_registry()
        seconds = reg.counter(
            "backend_compile_seconds_total",
            "Seconds this process spent making executables, by jitted "
            "function and stage (trace, lower, compile; a persistent-cache "
            "read is inside compile), each second counted once.",
            labels=("program", "stage"),
        )
        programs = reg.counter(
            "backend_compile_programs_total",
            "Executables this process made, by jitted function and outcome "
            "(compiled, or cache_read from the persistent cache).",
            labels=("program", "outcome"),
        )
        return seconds, programs

    def _program_row(self, program: str) -> Dict[str, float]:
        row = self._by_program.get(program)
        if row is None:
            row = self._by_program[program] = {
                "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
                "cache_read_s": 0.0, "meetings": 0, "cache_reads": 0}
        return row

    # -- jax.monitoring listeners ---------------------------------------------

    def on_stage_start(self, event: str, value: float, **kwargs: Any) -> None:
        stage = COMPILE_STAGES.get(event)
        if stage is None:
            return
        try:
            stack = self._open()
            entry = _Stage(stage, _program_name(kwargs.get("fun_name")),
                           folded=stage == "trace" and bool(stack))
            if not entry.folded:
                entry.span = span("backend.compile", program=entry.program,
                                  stage=stage)
                entry.late = entry.span.__enter__()
            stack.append(entry)
        except Exception:
            logger.exception("compile record: the start of %s", event)

    def on_stage_end(self, event: str, start: float, end: float,
                     **kwargs: Any) -> None:
        stage = COMPILE_STAGES.get(event)
        if stage is None:
            return
        try:
            self._close(stage, _program_name(kwargs.get("fun_name")),
                        max(0.0, float(end) - float(start)))
        except Exception:
            logger.exception("compile record: the end of %s", event)

    def _close(self, stage: str, program: str, elapsed: float) -> None:
        stack = self._open()
        # Stages are context managers: the one ending is the innermost open
        # (none where its start came before the record was installed).
        entry = None
        if stack and (stack[-1].stage, stack[-1].program) == (stage, program):
            entry = stack.pop()
        if entry is not None and entry.folded:
            return
        own = max(0.0, elapsed - (entry.nested_s if entry else 0.0))
        for outer in reversed(stack):
            if not outer.folded:
                outer.nested_s += elapsed
                break
        cache_read = bool(entry and entry.cache_read)
        with self._lock:
            row = self._program_row(program)
            row[f"{stage}_s"] += own
            self._totals[f"{stage}_s"] += own
            if stage == "compile":
                row["meetings"] += 1
                self._totals["programs"] += 1
                if cache_read:
                    row["cache_reads"] += 1
                    self._totals["cache_reads"] += 1
        seconds, programs = self._families()
        seconds.labels(program, stage).inc(own)
        if stage == "compile":
            programs.labels(
                program, "cache_read" if cache_read else "compiled").inc()
        if entry is not None and entry.span is not None:
            if stage == "compile":
                entry.late["cache_read"] = cache_read
            entry.span.__exit__(None, None, None)

    def _open_compile(self) -> Optional[_Stage]:
        return next((s for s in reversed(self._open())
                     if s.stage == "compile"), None)

    def on_event(self, event: str, **kwargs: Any) -> None:
        if event != _CACHE_HIT:
            return
        entry = self._open_compile()
        if entry is not None:
            entry.cache_read = True

    def on_duration(self, event: str, seconds: float, **kwargs: Any) -> None:
        if event != _CACHE_READ_TIME:
            return
        entry = self._open_compile()
        with self._lock:
            self._totals["cache_read_s"] += float(seconds)
            if entry is not None:
                self._program_row(entry.program)["cache_read_s"] += float(seconds)

    # -- readings ---------------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """The ``/healthz`` ``compiles`` block: totals since the record was
        installed, and ``by_program``."""
        with self._lock:
            out: Dict[str, Any] = dict(self._totals)
            out["by_program"] = {name: dict(row) for name, row in
                                 sorted(self._by_program.items())}
        return out


_RECORD: Optional[CompileRecord] = None
_RECORD_LOCK = threading.Lock()


def install_compile_record() -> CompileRecord:
    """The process's compile record, its listeners registered with
    ``jax.monitoring`` on the first call and never again."""
    global _RECORD
    with _RECORD_LOCK:
        if _RECORD is None:
            import jax.monitoring as monitoring

            record = CompileRecord()
            monitoring.register_scalar_listener(record.on_stage_start)
            monitoring.register_event_time_span_listener(record.on_stage_end)
            monitoring.register_event_listener(record.on_event)
            monitoring.register_event_duration_secs_listener(record.on_duration)
            _RECORD = record
        return _RECORD


def compile_record() -> Optional[CompileRecord]:
    """The installed record, or None in a process that never enabled the
    compile cache (and so never made an executable on purpose)."""
    return _RECORD
