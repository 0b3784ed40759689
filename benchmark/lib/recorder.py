"""Spans round the calls into the backend layer, taken from outside.

The program has no spans of its own at this boundary yet, so the benchmark
records them: each public entry of the serving backend that a cell's
traffic drives (``generate``, ``score_matrix``, ``embed``) is wrapped on the
one backend object the server holds.  A record keeps the call's kind, start and end on the host clock, and
references to what went in and what came out.  Nothing is copied or
converted inside the timed path; the output check and the work counts read
the records once the window has closed.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List


class Recorder:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.calls: List[Dict[str, Any]] = []
        self._backend = None
        self._originals: Dict[str, Any] = {}

    def _add(self, **record: Any) -> Dict[str, Any]:
        with self._lock:
            self.calls.append(record)
        return record

    def clear(self) -> None:
        with self._lock:
            self.calls = []

    def snapshot(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self.calls)

    def attach(self, backend: Any) -> None:
        self._backend = backend
        for kind in ("generate", "score_matrix", "embed"):
            original = getattr(backend, kind)
            self._originals[kind] = original
            setattr(backend, kind, self._wrap(kind, original))

    def detach(self) -> None:
        for kind in self._originals:
            # The wrappers are instance attributes over class methods.
            self._backend.__dict__.pop(kind, None)
        self._originals = {}
        self._backend = None

    def _wrap(self, kind: str, original: Any) -> Any:
        def call(requests, *args, **kwargs):
            start = time.perf_counter()
            results = original(requests, *args, **kwargs)
            self._add(kind=kind, start=start, end=time.perf_counter(),
                      requests=requests, results=results)
            return results

        return call
