"""One run of one cell: find its files, build the server, warm up, measure.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric is a file of its own under the benchmark's directory,
found by name: ``configs/``, ``traffic/``, ``workloads/``, ``metrics/`` with
their ``readers/``, and what is an architecture's, ``references/`` (the
plain forward and the weights' draws) and ``work/`` (the least FLOPs and
bytes), which a configuration file names.  This module holds what is common
to all of them.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import pathlib
import statistics
import sys
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence

from benchmark.lib import reference as ref
from benchmark.lib import traffic as traffic_lib
from benchmark.lib.meter import CompileMeter, increases, read_registry
from benchmark.lib.recorder import Recorder
from benchmark.lib.useful import Lengths


def load_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as handle:
        return json.load(handle)


@dataclasses.dataclass
class Cell:
    name: str
    workload: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    bench_dir: pathlib.Path
    #: The configuration's reference and work files, as modules.
    reference: Any = None
    work: Any = None

    @property
    def model(self) -> Dict[str, Any]:
        return self.config["model"]


def find_file(bench_dirs: List[pathlib.Path], kind: str, name: str,
              suffix: str = ".json") -> pathlib.Path:
    for root in bench_dirs:
        path = root / kind / f"{name}{suffix}"
        if path.exists():
            return path
    raise FileNotFoundError(
        f"no {kind}/{name}{suffix} under {[str(d) for d in bench_dirs]}")


def load_module(bench_dirs: List[pathlib.Path], kind: str, name: str,
                gives: Sequence[str]) -> Any:
    """``<kind>/<name>.py`` as a module of its own; one that lacks a function
    of ``gives`` is refused here, not where the function is first missed."""
    path = find_file(bench_dirs, kind, name, ".py")
    module_name = f"benchmark_{kind}_{name}"
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[module_name] = module  # dataclasses look their module up
    spec.loader.exec_module(module)
    missing = [g for g in gives if not callable(getattr(module, g, None))]
    if missing:
        raise ValueError(f"{path} gives no {', '.join(missing)}")
    return module


REFERENCE_GIVES = ("ref_config", "make_weights", "score_rows")
WORK_GIVES = ("param_count", "weight_bytes", "span_flops", "step_bytes")

#: Where a work file's terms are held to its whole: (cached positions, new
#: positions, of them with the head, rows that decode).
_WORK_PROBES = ((0, 1, 1, 1), (0, 3, 0, 1), (9, 1, 1, 1), (0, 2000, 1, 8),
                (2000, 50, 50, 32), (700, 100, 100, 160))


def load_work(bench_dirs: List[pathlib.Path], name: str,
              model: Dict[str, Any]) -> Any:
    """The work file ``work/<name>.py``, refused unless its terms sum to its
    whole on this ``model`` block: FLOPs of a span, bytes of a decode step,
    bytes of the weights."""
    work = load_module(bench_dirs, "work", name, WORK_GIVES)
    terms = getattr(work, "TERMS", ())
    if not terms:
        raise ValueError(f"work/{name}.py names no TERMS")

    def held(what: str, count: Any) -> None:
        whole, parts = count(None), sum(count(term) for term in terms)
        if abs(parts - whole) > 1e-9 * abs(whole):
            raise ValueError(f"work/{name}.py: the terms' {what} sum to "
                             f"{parts!r}, the whole is {whole!r}")

    held("weight_bytes", lambda term: work.weight_bytes(model, term=term))
    for start, count, with_head, rows in _WORK_PROBES:
        held(f"span_flops{(start, count, with_head)}", lambda term:
             work.span_flops(model, start, count, with_head, term=term))
        held(f"step_bytes{(start, rows)}", lambda term:
             work.step_bytes(model, start, rows, term=term))
    return work


def load_cell(bench_dirs: List[pathlib.Path], name: str) -> Cell:
    workload = load_json(find_file(bench_dirs, "workloads", name))
    config = load_json(find_file(bench_dirs, "configs", workload["config"]))
    return Cell(
        name=name, workload=workload, config=config,
        traffic=load_json(find_file(bench_dirs, "traffic", workload["traffic"])),
        bench_dir=bench_dirs[0],
        reference=load_module(bench_dirs, "references",
                              config.get("reference", "dense"), REFERENCE_GIVES),
        work=load_work(bench_dirs, config.get("work", "dense"), config["model"]))


def load_metrics(bench_dirs: List[pathlib.Path]) -> Dict[str, Dict[str, Any]]:
    """Every per-layer metric file, with its reader's ``read`` function."""
    out: Dict[str, Dict[str, Any]] = {}
    for root in bench_dirs:
        for path in sorted((root / "metrics").glob("*.json")):
            metric = load_json(path)
            if metric["name"] in out:
                continue
            metric["read"] = load_module(
                bench_dirs, "readers", metric["reader"], ("read",)).read
            out[metric["name"]] = metric
    return out


def _hashable(value: Any) -> Any:
    if isinstance(value, list):
        return tuple(_hashable(v) for v in value)
    return value


def model_config(cell: Cell) -> Any:
    """The program's ``ModelConfig`` for this cell's configuration file: the
    ``model`` block whole, so a key the program lacks is a ``TypeError``
    here and not a default, with every list a tuple (the config is a static
    argument of ``jit`` and has to hash)."""
    from consensus_tpu.models.config import ModelConfig

    fields = {key: _hashable(value) for key, value in cell.model.items()}
    return ModelConfig(name=cell.config["name"], **fields)


def make_params(config: Any, seed: int) -> Any:
    """The served weights: the program's ``init_params`` under one ``jit``,
    on the device, in bfloat16, from the run's seed."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib.reference import seed_key
    from consensus_tpu.models.transformer import init_params

    params = jax.jit(init_params, static_argnums=(0, 2))(
        config, seed_key(seed), jnp.bfloat16)
    return jax.block_until_ready(params)


@contextlib.contextmanager
def serving(cell: Cell, params: Any, config: Any) -> Iterator[Any]:
    """A started server built as ``python -m consensus_tpu.serve --backend
    tpu`` builds it, on this configuration and these weights."""
    from consensus_tpu.backends import clear_backend_cache
    from consensus_tpu.serve import create_server

    options = dict(cell.config["backend_options"])
    options.update(config=config, params=params)
    server = create_server(backend="tpu", backend_options=options, port=0,
                           default_timeout_s=900.0)
    server.start()
    try:
        yield server
    finally:
        server.stop(drain=True)
        clear_backend_cache()


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile of a non-empty list."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def answer_problems(sent: traffic_lib.Sent) -> List[str]:
    """Why this response is not a statement answered (empty when it is)."""
    if sent.error:
        return [sent.error]
    if sent.status != 200 or not isinstance(sent.body, dict):
        return [f"HTTP {sent.status}: {json.dumps(sent.body)[:200]}"]
    body, problems = sent.body, []
    statement = body.get("statement")
    if not isinstance(statement, str) or not statement.strip():
        problems.append(f"empty statement {statement!r}")
    elif statement.startswith("[ERROR"):
        problems.append(f"error returned as a statement: {statement[:80]!r}")
    if body.get("degraded"):
        problems.append(f"degraded answer: {body.get('degraded_reason')}")
    if not body.get("utilities") or not body.get("welfare"):
        problems.append("no utilities or no welfare")
    return problems


def warm_up(server: Any, cell: Cell, meter: CompileMeter, seed: int,
            log: Any, recorder: Recorder) -> Dict[str, Any]:
    """Every program the window can meet, in three steps.

    One request of each kind the window's stream holds (scenario and
    parameters, ``traffic.warm_bodies``), through the cell's own loop at the
    cell's own concurrency, under request seeds the window does not use.  A
    request that fails here fails the run, but for this: greedy decoding on
    random weights can run into white space alone, which the method refuses,
    so the mix's greedy requests go to the first scenario of the seed's order
    on which one is answered (``greedy_scenario`` of what is returned).

    The program sizes a score matrix's page pool by its longest candidate,
    and what a sampled request generates differs from seed to seed; so each
    score matrix of that pass is made again with candidates of every length
    the mix's file spans (``warm_matrix_shapes``).

    And the engine hands the embedding calls of requests that reach it in
    the same iteration to the backend as one batch, whose rows and width
    follow which requests met; so the texts that the pass embedded are
    embedded again together, two and three requests at a time."""
    before, named, start = meter.totals(), len(meter.names), time.perf_counter()
    sent: List[traffic_lib.Sent] = []
    refused: List[str] = []
    for scenario in traffic_lib.greedy_scenarios(cell.traffic, seed):
        bodies = traffic_lib.warm_bodies(cell.traffic, seed, scenario)
        if sent:  # the rest has been answered: the greedy kinds alone
            bodies = [b for b in bodies
                      if not traffic_lib.paper_shaped(cell.traffic, b)]
        batch = traffic_lib.drive(
            server.base_url, cell.traffic,
            traffic_lib.payloads(iter(bodies), tag=f"warm{len(refused)}"),
            stop=lambda n, _s: n >= len(bodies))
        sent += batch
        problems = [p for s in batch for p in answer_problems(s)
                    if traffic_lib.paper_shaped(cell.traffic, s.payload)]
        empty = [s for s in batch if answer_problems(s)
                 and not traffic_lib.paper_shaped(cell.traffic, s.payload)]
        if problems or not empty:
            break
        refused.append(f"{scenario}: {answer_problems(empty[0])[0]}")
    else:
        problems = [f"no scenario answers a greedy request: {refused}"]
    grown = meter.since(before)
    out: Dict[str, Any] = {"greedy_scenario": scenario, "requests": {
        "seconds": time.perf_counter() - start, "requests": len(sent),
        "greedy_refused": refused,
        "programs": grown["programs"], "cache_hits": grown["cache_hits"],
        "compiled": sorted(set(meter.names[named:]))[:12],
        "problems": problems[:3]}}
    log(f"warm-up, one request of each kind: {out['requests']}")
    if problems:
        raise RuntimeError(f"warm-up request failed: {problems[0]}")
    out["matrix_shapes"] = warm_matrix_shapes(server, cell, meter, recorder)
    log(f"warm-up, score matrices by candidate length: {out['matrix_shapes']}")
    statements = {s.body["statement"] for s in sent if not answer_problems(s)}
    out["merged_embeds"] = warm_merged_embeds(
        server, cell, meter, recorder, statements)
    log(f"warm-up, embedding calls merged: {out['merged_embeds']}")
    return out


def matrix_shape(request: Any, longest: int, page: int, n: Any) -> Any:
    """What a paged score matrix's programs are sized by, for candidates of
    at most ``longest`` tokens: each agent's context keeps its whole pages
    but the last as shared pages, a row holds the rest of its context and
    its candidate in pages of its own.  (Pages of the longest row, most
    pages one row's table names.)"""
    private = blocks = 0
    for agent in request.agents:
        ids = n(ref.score_prefix(agent.context, agent.system_prompt, agent.chat,
                                 agent.role), True)
        shared = (ids - 1) // page
        own = (ids - shared * page + max(longest - 1, 0) - 1) // page + 1
        private, blocks = max(private, own), max(blocks, shared + own)
    return private, blocks


def warm_matrix_shapes(server: Any, cell: Cell, meter: CompileMeter,
                       recorder: Recorder) -> Dict[str, Any]:
    """Each distinct score matrix of the records (its agents, its statistic,
    its number of candidates) again, once for every ``matrix_shape`` that
    candidates of ``warm.matrix_candidate_ids`` [fewest, most] tokens give
    and the records have not met."""
    plan = cell.traffic.get("warm", {})
    spans = plan.get("matrix_candidate_ids", {})
    page = int(plan.get("page_tokens", 16))
    n = Lengths()
    met: Dict[Any, Any] = {}
    for call in recorder.snapshot():
        if call["kind"] != "score_matrix":
            continue
        for request in call["requests"]:
            key = (request.stat, len(request.candidates), request.agents)
            longest = max((n(c) for c in request.candidates), default=0)
            met.setdefault(key, (request, set()))[1].add(
                matrix_shape(request, longest, page, n))
    backend = server.scheduler.inner_backend
    before, start, calls = meter.totals(), time.perf_counter(), 0
    for (stat, _, _), (request, shapes) in met.items():
        fewest, most = spans.get(stat, (1, 0))
        for ids in range(int(fewest), int(most) + 1):
            shape = matrix_shape(request, ids, page, n)
            if shape in shapes:
                continue
            shapes.add(shape)
            # One byte is one token to the byte tokenizer.
            backend.score_matrix([dataclasses.replace(
                request, candidates=("a" * ids,) * len(request.candidates))])
            calls += 1
    grown = meter.since(before)
    return {"seconds": time.perf_counter() - start, "calls": calls,
            "programs": grown["programs"], "cache_hits": grown["cache_hits"]}


def warm_merged_embeds(server: Any, cell: Cell, meter: CompileMeter,
                       recorder: Recorder, statements: Any) -> Dict[str, Any]:
    # One request embeds its statement and then its opinions; a recorded
    # call that already holds several requests is cut at the statements.
    texts: List[List[str]] = []
    for call in recorder.snapshot():
        if call["kind"] != "embed":
            continue
        one: List[str] = []
        for text in list(call["requests"]) + [None]:
            if one and (text is None or text in statements):
                if one not in texts:
                    texts.append(one)
                one = []
            if text is not None:
                one.append(text)
    # The batch's width follows its longest text and its rows the number of
    # texts: two and three requests next to each other by length give every
    # width with its two row counts.
    texts.sort(key=lambda one: max(len(text) for text in one))
    backend = server.scheduler.inner_backend
    before, start, calls = meter.totals(), time.perf_counter(), 0
    most = min(len(texts), int(cell.traffic["loop"].get("clients", 1)), 3)
    for size in range(2, most + 1):
        for last in range(size - 1, len(texts)):
            group = texts[last - size + 1:last + 1]
            backend.embed([text for one in group for text in one])
            calls += 1
    grown = meter.since(before)
    return {"seconds": time.perf_counter() - start, "calls": calls,
            "programs": grown["programs"], "cache_hits": grown["cache_hits"]}


def window(server: Any, cell: Cell, seed: int, seconds: float,
           greedy_scenario: Optional[str] = None) -> List[traffic_lib.Sent]:
    """The measured window: the mix's stream under ``seed`` until ``seconds``
    have passed; what is in flight then finishes and counts."""
    return traffic_lib.drive(
        server.base_url, cell.traffic,
        traffic_lib.payloads(traffic_lib.bodies(
            cell.traffic, seed, greedy_scenario=greedy_scenario)),
        stop=lambda _n, elapsed: elapsed >= seconds)


def end_to_end(cell: Cell, sent: List[traffic_lib.Sent]) -> Dict[str, float]:
    """The rate and the percentiles are of the requests that carry the mix's
    own parameters; a greedy request is there for the output check and is
    neither a statement of the rate nor a time of the percentile.  The rate
    is over the seconds from the first send to the last response of the
    whole window; a failed request counts as the slowest."""
    first = min(s.sent for s in sent)
    last = max(s.done for s in sent)
    span = last - first
    slowest = max((s.seconds for s in sent if s.seconds is not None), default=0.0)
    own = [s for s in sent if traffic_lib.paper_shaped(cell.traffic, s.payload)]
    times = [s.seconds if not answer_problems(s) else max(slowest, span)
             for s in own]
    out = {
        "statements_per_s": sum(1 for s in own if not answer_problems(s)) / span,
        "time_to_statement_p50_s": statistics.median(times) if times else span,
        "window_span_s": span,
    }
    if len(times) >= 100:
        out["time_to_statement_p90_s"] = percentile(times, 90)
    return out


def registry_deltas(before: Any) -> Any:
    from consensus_tpu.obs.metrics import get_registry

    return increases(before, read_registry(get_registry()))


def registry_now() -> Any:
    from consensus_tpu.obs.metrics import get_registry

    return read_registry(get_registry())


def memory_peak_bytes() -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
