"""What JAX compiled, and the program's own counters, as increases."""

from __future__ import annotations

import logging
import threading
from typing import Any, Dict, Tuple


class CompileMeter:
    """Programs JAX handed to the backend compiler (a persistent-cache read
    counts: the program was not in this process yet), persistent-cache hits,
    and the seconds spent tracing, lowering and compiling, from
    ``jax.monitoring``."""

    _DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "compile_s",
    }

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._totals = {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
                        "programs": 0, "cache_hits": 0}
        #: Names of the programs compiled, in order, from JAX's own debug
        #: log line ("Compiling <name> with global shapes ...").
        self.names: list = []
        self._handler = _NameHandler(self.names)

    def _on_duration(self, event: str, seconds: float, **_: Any) -> None:
        key = self._DURATIONS.get(event)
        if key is None:
            return
        with self._lock:
            self._totals[key] += seconds
            if key == "compile_s":
                self._totals["programs"] += 1

    def _on_event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self._totals["cache_hits"] += 1

    def __enter__(self) -> "CompileMeter":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        self._handler.install()
        return self

    def __exit__(self, *exc: Any) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        self._handler.remove()

    def totals(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._totals)

    def since(self, before: Dict[str, float]) -> Dict[str, float]:
        now = self.totals()
        return {key: now[key] - before[key] for key in now}


class _NameHandler(logging.Handler):
    """Collects what follows "Compiling " in the lowering log's lines."""

    LOGGER = "jax._src.interpreters.pxla"

    def __init__(self, names: list) -> None:
        super().__init__(logging.DEBUG)
        self._names = names
        self._was = None

    def emit(self, record: logging.LogRecord) -> None:
        if isinstance(record.msg, str) and record.msg.startswith("Compiling %s"):
            self._names.append(str(record.args[0]))

    def install(self) -> None:
        logger = logging.getLogger(self.LOGGER)
        self._was = (logger.level, logger.propagate)
        logger.setLevel(logging.DEBUG)
        logger.propagate = False
        logger.addHandler(self)

    def remove(self) -> None:
        logger = logging.getLogger(self.LOGGER)
        logger.removeHandler(self)
        if self._was is not None:
            logger.setLevel(self._was[0])
            logger.propagate = self._was[1]


Series = Dict[Tuple[str, Tuple[Tuple[str, str], ...]], Dict[str, float]]


def read_registry(registry: Any) -> Series:
    """Every series of the program's metrics registry: counters and gauges as
    ``value``, histograms as ``sum`` and ``count``."""
    out: Series = {}
    for family, block in registry.snapshot()["families"].items():
        for series in block.get("series", []):
            labels = tuple(sorted(
                (k, str(v)) for k, v in series.get("labels", {}).items()))
            out[(family, labels)] = {
                key: float(series[key]) for key in ("value", "sum", "count")
                if isinstance(series.get(key), (int, float))}
    return out


def increases(before: Series, after: Series) -> Series:
    out: Series = {}
    for key, now in after.items():
        was = before.get(key, {})
        grown = {k: v - was.get(k, 0.0) for k, v in now.items()}
        if any(grown.values()):
            out[key] = grown
    return out


def family_total(deltas: Series, family: str, field: str = "value", **labels: str) -> float:
    """Sum of one family's increases over the series whose labels match."""
    total = 0.0
    for (name, series_labels), grown in deltas.items():
        have = dict(series_labels)
        if name == family and all(have.get(k) == v for k, v in labels.items()):
            total += grown.get(field, 0.0)
    return total
