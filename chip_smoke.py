"""The quickest proof that the serving path still starts on the chip.

``python chip_smoke.py`` builds the server the way ``python -m
consensus_tpu.serve --backend tpu`` does, at gemma2-2b's published widths
(26 layers, d 2304, 8/4 heads of 256, ffn 9216, vocabulary 256,128) with
seeded random bf16 weights, POSTs a few ``/v1/consensus`` requests on paper
scenario 2, checks every answer, and prints one JSON report.  The last line
of its standard output is ``{"ok": true, "device": {...}}``.

It needs an accelerator: where JAX reports anything but ``tpu`` it exits
with code 2 before any phase runs and prints no result.  There is no CPU
switch on the command line; ``tests/test_chip_smoke.py`` runs the same
phase functions at ``tiny-gemma2``'s size.

One process touches the chip, and it starts no other.  It reads nothing
outside tracked files, generates weights and requests from seeds, and
writes only ``chiprun_out/chip_smoke.json`` and the compile cache.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import pathlib
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the smoke serves.  Widths come from ``model``; the rest are
    counts, cut so that a cold run ends inside the driver's time limit."""

    model: str = "gemma2-2b"
    #: Paper scenario 2 (5 agents; ``consensus_tpu/data/aamas_scenarios.py``),
    #: as a reference the server resolves.
    scenario: str = "aamas:2"
    #: Holds every prompt the smoke's requests produce (scenario 2's
    #: reference prompt is about 2.9k byte-tokens).
    max_context: int = 4096
    bon_n: int = 8
    bon_tokens: int = 50
    beam_width: int = 4
    search_tokens: int = 12
    lookahead_depth: int = 2
    mcts_simulations: int = 8
    mcts_rollout_depth: int = 4
    #: At least 256, so that the segmented decode with the int8 tail runs.
    habermas_tokens: int = 256
    habermas_candidates: int = 4
    #: One critique/revise round would put every opinion, the draft and five
    #: 256-token critiques into one prompt — past ``max_context``.  Rounds
    #: are a count; the context is not cut to make room for them.
    habermas_rounds: int = 0
    decode_steps: int = 8
    request_timeout_s: float = 900.0


# -- measuring -----------------------------------------------------------------


class CompileMeter:
    """Seconds JAX spent tracing, lowering and compiling, and how many
    programs it compiled or took from the persistent cache, read from
    ``jax.monitoring``.  The smoke is one process, so everything compiled
    while a request is in flight was compiled for it."""

    _DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "compile_s",
    }

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._totals = {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
                        "programs": 0, "cache_hits": 0}

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
        return self

    def __exit__(self, *exc: Any) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def totals(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._totals)

    def since(self, before: Dict[str, float]) -> Dict[str, float]:
        now = self.totals()
        return {key: round(now[key] - before[key], 3) for key in now}


def device_report() -> Dict[str, Any]:
    """The device as JAX reports it, the toolchain, and every device's
    memory counters."""
    import jax
    import jaxlib

    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
        "versions": {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "libtpu": libtpu_version,
        },
    }


def memory_report() -> List[Dict[str, Any]]:
    import jax

    out = []
    for device in jax.devices():
        stats = device.memory_stats() or {}
        out.append({
            "id": device.id,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
        })
    return out


class Counters:
    """The obs registry's counters, as increases since this object was made
    (the registry is one per process and outlives any one run)."""

    def __init__(self) -> None:
        from consensus_tpu.obs.metrics import get_registry

        self._registry = get_registry()
        self._base = self._read()

    def _read(self) -> Dict[Tuple[str, Tuple[Tuple[str, str], ...]], float]:
        out = {}
        families = self._registry.snapshot()["families"]
        for family in (
            "backend_bucket_compiles_total",
            "backend_bucket_cache_hits_total",
            "backend_padding_allocated_tokens_total",
            "token_search_sessions_total",
        ):
            for series in families.get(family, {}).get("series", []):
                labels = tuple(sorted(
                    (k, str(v)) for k, v in series["labels"].items()))
                out[(family, labels)] = series["value"]
        return out

    def _increases(self, family: str) -> Iterator[Tuple[Dict[str, str], int]]:
        for (name, labels), value in self._read().items():
            grown = value - self._base.get((name, labels), 0)
            if name == family and grown > 0:
                yield dict(labels), int(grown)

    def programs(self) -> Dict[str, Dict[str, int]]:
        """Padded device programs by kind: buckets first seen
        (``compiled``) and launches in all, from the backend's own
        instruments."""
        out: Dict[str, Dict[str, int]] = {}
        for labels, n in self._increases("backend_bucket_compiles_total"):
            entry = out.setdefault(labels["kind"], {"compiled": 0, "launches": 0})
            entry["compiled"] += n
            entry["launches"] += n
        for labels, n in self._increases("backend_bucket_cache_hits_total"):
            entry = out.setdefault(labels["kind"], {"compiled": 0, "launches": 0})
            entry["launches"] += n
        return out

    def sessions(self) -> Dict[str, int]:
        out = {"fused": 0, "prefix": 0}
        for labels, n in self._increases("token_search_sessions_total"):
            out[labels["kind"]] += n
        return out

    def forward_widths(self) -> List[int]:
        """Sequence widths the no-cache forward ran at (the path the flash
        kernel would replace)."""
        return sorted({
            int(labels["width"])
            for labels, _ in self._increases(
                "backend_padding_allocated_tokens_total")
            if labels["kind"] in ("score", "next_token", "embed")
        })


# -- serving -------------------------------------------------------------------


@contextlib.contextmanager
def serving(
    sizes: Sizes,
    engine_options: Optional[Dict[str, Any]] = None,
    mesh: Optional[str] = None,
) -> Iterator[Any]:
    """A started server built as ``python -m consensus_tpu.serve --backend
    tpu`` builds it (``serve/__main__.py`` -> ``create_server``): engine on,
    every backend option at its default but the model and the context."""
    from consensus_tpu.serve import create_server

    server = create_server(
        backend="tpu",
        backend_options={
            "model": sizes.model,
            "max_context": sizes.max_context,
            "dtype": "bfloat16",
        },
        port=0,
        default_timeout_s=sizes.request_timeout_s,
        engine_options=engine_options,
        mesh=mesh,
    )
    server.start()
    try:
        yield server
    finally:
        server.stop(drain=True)


def post(server: Any, payload: Dict[str, Any], timeout_s: float) -> Tuple[int, Any]:
    request = urllib.request.Request(
        server.base_url + "/v1/consensus",
        data=json.dumps(payload).encode("utf-8"),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(request, timeout=timeout_s) as response:
            return response.status, json.loads(response.read())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read() or b"null")


def get_json(server: Any, path: str) -> Any:
    with urllib.request.urlopen(server.base_url + path, timeout=30.0) as response:
        return json.loads(response.read())


def answer_problems(status: int, body: Any, agents: Sequence[str]) -> List[str]:
    """Why this response is not a right answer (empty when it is): HTTP 200,
    a statement that is text and not an error string, the full budget
    spent, and finite utilities and welfare for every agent."""
    if status != 200:
        return [f"HTTP {status}: {json.dumps(body)[:300]}"]
    problems = []
    statement = body.get("statement")
    if not isinstance(statement, str) or not statement.strip():
        problems.append(f"empty statement {statement!r}")
    elif statement.startswith("[ERROR"):
        problems.append(f"error returned as a statement: {statement!r}")
    if body.get("degraded"):
        problems.append(f"degraded answer: {body.get('degraded_reason')}")
    utilities = body.get("utilities") or {}
    for agent in agents:
        values = utilities.get(agent)
        if not values:
            problems.append(f"no utilities for {agent}")
        elif not all(_finite(v) for v in values.values()):
            problems.append(f"non-finite utilities for {agent}: {values}")
    welfare = body.get("welfare") or {}
    if not welfare:
        problems.append("no welfare")
    elif not all(_finite(v) for v in welfare.values()):
        problems.append(f"non-finite welfare: {welfare}")
    return problems


def _finite(value: Any) -> bool:
    return isinstance(value, (int, float)) and math.isfinite(value)


def run_requests(
    server: Any,
    meter: CompileMeter,
    named_payloads: Sequence[Tuple[str, Dict[str, Any]]],
    agents: Sequence[str],
    timeout_s: float,
) -> Dict[str, Any]:
    """POST the payloads AT ONCE (one thread each) and check each answer.
    Seconds run on the host clock to the moment the response is in hand."""
    results: Dict[str, Any] = {}

    def one(name: str, payload: Dict[str, Any]) -> None:
        start = time.perf_counter()
        try:
            status, body = post(server, payload, timeout_s)
        except Exception as exc:  # a dead socket is a failed request
            results[name] = {
                "seconds": round(time.perf_counter() - start, 3),
                "problems": [f"{type(exc).__name__}: {exc}"],
            }
            return
        results[name] = {
            "seconds": round(time.perf_counter() - start, 3),
            "status": status,
            "statement": body.get("statement") if isinstance(body, dict) else None,
            "problems": answer_problems(status, body, agents),
        }

    before = meter.totals()
    start = time.perf_counter()
    threads = [
        threading.Thread(target=one, args=item, name=f"smoke-{item[0]}")
        for item in named_payloads
    ]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join()
    return {
        "seconds": round(time.perf_counter() - start, 3),
        "jax": meter.since(before),
        "requests": results,
    }


def scenario_of(sizes: Sizes) -> Dict[str, Any]:
    from consensus_tpu.data.scenarios.registry import resolve_scenario_ref

    return resolve_scenario_ref(sizes.scenario)


def payload(sizes: Sizes, method: str, seed: int, **params: Any) -> Dict[str, Any]:
    return {"scenario": sizes.scenario, "method": method, "seed": seed,
            "params": params}


def prompt_lengths(backend: Any, sizes: Sizes) -> Dict[str, Any]:
    """Token counts, by the backend's own tokenizer, of the longest prompt
    each request kind renders, against the room it has."""
    from consensus_tpu.methods.prompts import reference_prompt

    scenario = scenario_of(sizes)
    opinions = dict(scenario["agent_opinions"])
    tokenizer = backend.tokenizer
    out = {}
    for variant, reserve in (
        ("best_of_n", 0),
        ("beam_search", sizes.search_tokens),
        ("finite_lookahead", sizes.search_tokens),
        ("mcts", sizes.search_tokens),
    ):
        system, user = reference_prompt(scenario["issue"], opinions, variant)
        render = tokenizer.chat_prompt if variant == "best_of_n" else tokenizer.raw_prompt
        out[variant] = {
            "tokens": len(tokenizer.encode(render(user, system), add_bos=True)),
            "room": backend.max_context - reserve,
        }
    return out


def phase_serving(
    sizes: Sizes, meter: CompileMeter, counters: Counters,
    mesh: Optional[str] = None,
) -> Dict[str, Any]:
    """Phase 1: one server, engine on by default.  Best-of-N (shared-trunk
    generate, fused score matrix, embed), the three token searches (fused
    session), Habermas at pinned budgets (segmented decode, int8 tail), then
    two best-of-N at once (merged cohorts)."""
    agents = list(scenario_of(sizes)["agent_opinions"])
    report: Dict[str, Any] = {"steps": {}}
    before = meter.totals()
    start = time.perf_counter()
    with serving(sizes, mesh=mesh) as server:
        backend = server.scheduler.inner_backend
        report["setup_seconds"] = round(time.perf_counter() - start, 3)
        report["setup_jax"] = meter.since(before)
        report["model"] = served_model(backend)
        report["placement"] = placement_report(backend)
        report["prompts"] = prompt_lengths(backend, sizes)
        bon = dict(n=sizes.bon_n, max_tokens=sizes.bon_tokens)
        # Step name -> the requests POSTed at once in that step.
        steps: Dict[str, List[Tuple[str, Dict[str, Any]]]] = {
            "best_of_n": [("best_of_n", payload(sizes, "best_of_n", 1, **bon))],
            # Greedy: the statement the four-chip run is compared on.
            "beam_search": [("beam_search", payload(
                sizes, "beam_search", 2, beam_width=sizes.beam_width,
                max_tokens=sizes.search_tokens, temperature=0.0))],
            "finite_lookahead": [("finite_lookahead", payload(
                sizes, "finite_lookahead", 3, branching_factor=2,
                max_depth=sizes.lookahead_depth,
                max_tokens=sizes.search_tokens))],
            "mcts": [("mcts", payload(
                sizes, "mcts", 4, num_simulations=sizes.mcts_simulations,
                expansion_sample_width=2, max_tokens=sizes.search_tokens,
                rollout_depth=sizes.mcts_rollout_depth, mcts_wave_size=4))],
            "habermas_machine": [("habermas_machine", payload(
                sizes, "habermas_machine", 5, pin_budget=True,
                max_tokens=sizes.habermas_tokens,
                num_candidates=sizes.habermas_candidates,
                num_rounds=sizes.habermas_rounds))],
            "best_of_n_pair": [
                (f"best_of_n_pair_{seed}", payload(sizes, "best_of_n", seed, **bon))
                for seed in (11, 31)
            ],
        }
        for name, requests in steps.items():
            report["steps"][name] = run_requests(
                server, meter, requests, agents, sizes.request_timeout_s)
        health = get_json(server, "/healthz")
        report.update(backend_counters(backend, counters, health))
    return report


def phase_stream(
    sizes: Sizes, meter: CompileMeter, counters: Counters,
    mesh: Optional[str] = None,
) -> Dict[str, Any]:
    """Phase 2: a second server in the same process on the same parameters
    (``get_backend`` hands back the backend phase 1 built), with the engine
    dispatching K-step windows: the paged stream."""
    agents = list(scenario_of(sizes)["agent_opinions"])
    report: Dict[str, Any] = {"steps": {}}
    start = time.perf_counter()
    options = {"decode_steps": sizes.decode_steps}
    with serving(sizes, engine_options=options, mesh=mesh) as server:
        backend = server.scheduler.inner_backend
        report["setup_seconds"] = round(time.perf_counter() - start, 3)
        report["steps"]["best_of_n_stream"] = run_requests(
            server, meter,
            [("best_of_n_stream", payload(
                sizes, "best_of_n", 41, n=sizes.bon_n,
                max_tokens=sizes.bon_tokens))],
            agents, sizes.request_timeout_s)
        health = get_json(server, "/healthz")
        report.update(backend_counters(backend, counters, health))
    return report


def backend_counters(
    backend: Any, counters: Counters, health: Dict[str, Any]
) -> Dict[str, Any]:
    """Every count a fallback on this path leaves (cumulative over the
    run), and the server's own view of its device."""
    engine = health.get("engine") or {}
    return {
        # The same parameters serve both phases when this is the same.
        "backend_id": id(backend),
        "device_batches": health.get("device_batches"),
        "programs": counters.programs(),
        "matrix_stats": dict(backend.matrix_stats),
        "sessions_opened": counters.sessions(),
        "truncated_prompts": backend.truncated_prompts,
        "forward_widths": counters.forward_widths(),
        "healthz_backend": health.get("backend"),
        "engine": {
            key: engine.get(key)
            for key in ("decode_steps", "decode_windows", "decoded_tokens",
                        "fused_search_sessions", "fused_search_slots")
        },
    }


def served_model(backend: Any) -> Dict[str, Any]:
    """The widths of the model the backend serves (not of the preset: of
    the configuration its programs are compiled for)."""
    from consensus_tpu.utils.mfu import param_count

    config = backend.config
    return {
        "name": config.name,
        "n_layers": config.n_layers,
        "d_model": config.d_model,
        "n_heads": config.n_heads,
        "n_kv_heads": config.n_kv_heads,
        "head_dim": config.head_dim,
        "ffn_hidden": config.ffn_hidden,
        "vocab_size": config.vocab_size,
        "embed_rows": int(backend.params["embed"].shape[0]),
        "sample_vocab": config.sample_vocab,
        "param_count": param_count(config),
        "dtype": str(backend.params["embed"].dtype),
        "max_context": backend.max_context,
    }


def placement_report(backend: Any) -> Dict[str, Any]:
    """Where the backend put its weights and where it puts a batch: the
    evidence that ``--mesh dp=N`` replicates parameters on every device and
    shards rows over ``data``."""
    import numpy as np

    embed = backend.params["embed"]
    rows = max(8, 2 * backend._dp)
    (batch,) = backend._place_batch(np.zeros((rows, 16), np.int32))
    return {
        "dp": backend._dp,
        "tp": backend._shard_count,
        "embed_devices": sorted(d.id for d in embed.sharding.device_set),
        "embed_shard_shape": list(embed.addressable_shards[0].data.shape),
        "batch_shape": list(batch.shape),
        "batch_shard_shapes": sorted(
            (s.device.id, list(s.data.shape)) for s in batch.addressable_shards
        ),
    }


# -- kernels -------------------------------------------------------------------

#: Both kernels keep bf16 inputs' products and the softmax in float32; the
#: einsum path they would replace rounds its logits to bf16 (2^-9 relative,
#: on logits of magnitude up to ~5) and its softmax weights to bf16 (2^-9
#: again) before the value matmul, and both round the output to bf16.  On
#: unit-normal values that is a few 1e-3 per output element and 1.6e-2 at
#: worst (one bf16 ulp at |x| in [2, 4)); the bound is twice that.  A wrong
#: mask, window or softcap moves outputs by O(1).
KERNEL_ATOL = 3.2e-2


def _einsum_attention(q, keys, values, lengths, starts, scale, softcap, window):
    """The no-cache attention of ``models/transformer.py:forward`` (its
    ``else`` branch), on one contiguous valid span per row."""
    import jax
    import jax.numpy as jnp

    batch, span, heads, head_dim = q.shape
    kv = keys.shape[2]
    pos = jnp.arange(span)[None, :]
    valid = (pos >= starts[:, None]) & (pos < (starts + lengths)[:, None])
    qp, kp = pos[:, :, None], pos[:, None, :]
    mask = (kp <= qp) & valid[:, None, :] & valid[:, :, None]
    if window is not None:
        mask = mask & (qp - kp < window)
    qg = q.reshape(batch, span, kv, heads // kv, head_dim)
    logits = jnp.einsum("bsgrd,btgd->bgrst", qg, keys).astype(jnp.float32)
    logits = softcap * jnp.tanh(logits * scale / softcap)
    logits = jnp.where(mask[:, None, None], logits, -1e9)
    weights = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrst,btgd->bsgrd", weights, values)
    return out.reshape(batch, span, heads, head_dim), valid


def _einsum_decode_attention(
    q, trunk_k, trunk_v, tail_k, tail_v, starts, qpos, write_col,
    n_slots, n_roles, scale, softcap, window,
):
    """The einsum branch of ``models/transformer.py:forward_trunk_tail``:
    one query per (slot x role) row over [shared trunk | own tail]."""
    import jax
    import jax.numpy as jnp

    rows, heads, head_dim = q.shape
    w0, kv = trunk_k.shape[1], trunk_k.shape[2]
    ts = tail_k.shape[1]
    qg = q.reshape(n_slots, n_roles, kv, heads // kv, head_dim)
    tail_kg = tail_k.reshape(n_slots, n_roles, ts, kv, head_dim)
    tail_vg = tail_v.reshape(n_slots, n_roles, ts, kv, head_dim)
    lt = jnp.einsum("prgmd,rtgd->prgmt", qg, trunk_k).astype(jnp.float32)
    ls = jnp.einsum("prgmd,prtgd->prgmt", qg, tail_kg).astype(jnp.float32)
    logits = jnp.concatenate([lt, ls], axis=-1) * scale
    logits = softcap * jnp.tanh(logits / softcap)
    cols = jnp.arange(w0)[None, :]
    trunk_ok = cols >= starts[:, None]
    if window is not None:
        trunk_ok = trunk_ok & (qpos[:, None] - (cols - starts[:, None]) < window)
    tail_cols = jnp.arange(ts)
    tail_ok = tail_cols <= write_col
    if window is not None:
        tail_ok = tail_ok & (write_col - tail_cols < window)
    mask = jnp.concatenate(
        [
            jnp.broadcast_to(trunk_ok[None], (n_slots, n_roles, w0)),
            jnp.broadcast_to(tail_ok[None, None], (n_slots, n_roles, ts)),
        ],
        axis=-1,
    )[:, :, None, None]
    logits = jnp.where(mask, logits, -1e9)
    weights = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("prgmt,rtgd->prgmd", weights[..., :w0], trunk_v)
    out = out + jnp.einsum("prgmt,prtgd->prgmd", weights[..., w0:], tail_vg)
    return out.reshape(rows, heads, head_dim)


def phase_kernels(
    model: str,
    widths: Sequence[int],
    search_shapes: Sequence[Tuple[int, int, int, int]],
    interpret: bool = False,
) -> Dict[str, Any]:
    """Compile both Pallas kernels (off by default in serving) at ``model``'s
    head shapes, with its softcap, with its window and with none, and
    compare each with the einsum path it would replace.  ``widths`` are
    sequence widths for the flash kernel; ``search_shapes`` are (slots,
    roles, trunk width, tail width) for the decode kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from consensus_tpu.models.config import get_model_config
    from consensus_tpu.ops.decode_attention import decode_attention
    from consensus_tpu.ops.flash_attention import flash_attention

    c = get_model_config(model)
    heads, kv, head_dim = c.n_heads, c.n_kv_heads, c.head_dim
    dtype = jnp.bfloat16
    cases: Dict[str, Any] = {}

    def normal(key, *shape):
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)

    einsum_attention = jax.jit(
        _einsum_attention, static_argnames=("scale", "softcap", "window"))
    einsum_decode = jax.jit(
        _einsum_decode_attention,
        static_argnames=("n_slots", "n_roles", "scale", "softcap", "window"))

    for width in widths:
        for window in (c.sliding_window, None):
            batch = 2 if width <= 1024 else 1
            kq, kk, kvv = jax.random.split(jax.random.PRNGKey(width), 3)
            q = normal(kq, batch, width, heads, head_dim)
            keys = normal(kk, batch, width, kv, head_dim)
            values = normal(kvv, batch, width, kv, head_dim)
            # Row 0 fills the width; row 1 is a left-padded third of it.
            lengths = jnp.asarray([width, max(1, width // 3)][:batch], jnp.int32)
            starts = width - lengths
            name = f"flash_s{width}_w{window}"
            try:
                out = flash_attention(
                    q, jnp.repeat(keys, heads // kv, axis=2),
                    jnp.repeat(values, heads // kv, axis=2),
                    lengths, starts, scale=c.q_scale, softcap=c.attn_softcap,
                    window=window, causal=True, interpret=interpret,
                )
                ref, valid = einsum_attention(
                    q, keys, values, lengths, starts, scale=c.q_scale,
                    softcap=c.attn_softcap, window=window)
                err = np.abs(
                    np.asarray(out, np.float32) - np.asarray(ref, np.float32)
                ) * np.asarray(valid)[:, :, None, None]
                cases[name] = _kernel_case(float(err.max()))
            except Exception as exc:  # the compiler's refusal is the finding
                cases[name] = {"ok": False,
                               "error": f"{type(exc).__name__}: {exc}"[:2000]}

    for n_slots, n_roles, w0, ts in search_shapes:
        for window in (c.sliding_window, None):
            rows = n_slots * n_roles
            ks = jax.random.split(jax.random.PRNGKey(w0 + ts), 5)
            q = normal(ks[0], rows, heads, head_dim)
            trunk_k = normal(ks[1], n_roles, w0, kv, head_dim)
            trunk_v = normal(ks[2], n_roles, w0, kv, head_dim)
            tail_k = normal(ks[3], rows, ts, kv, head_dim)
            tail_v = normal(ks[4], rows, ts, kv, head_dim)
            starts = jnp.asarray(
                (np.arange(n_roles) * 7) % max(1, w0 // 2), jnp.int32)
            write_col = jnp.asarray(min(5, ts - 1), jnp.int32)
            qpos = w0 - starts + write_col
            name = f"decode_p{n_slots}_r{n_roles}_t{w0}+{ts}_w{window}"
            try:
                out = decode_attention(
                    q, trunk_k, trunk_v, tail_k, tail_v, starts, qpos,
                    write_col, n_slots=n_slots, n_roles=n_roles,
                    scale=c.q_scale, softcap=c.attn_softcap, window=window,
                    interpret=interpret,
                )
                ref = einsum_decode(
                    q, trunk_k, trunk_v, tail_k, tail_v, starts, qpos,
                    write_col, n_slots=n_slots, n_roles=n_roles,
                    scale=c.q_scale, softcap=c.attn_softcap, window=window)
                err = np.abs(
                    np.asarray(out, np.float32) - np.asarray(ref, np.float32))
                cases[name] = _kernel_case(float(err.max()))
            except Exception as exc:
                cases[name] = {"ok": False,
                               "error": f"{type(exc).__name__}: {exc}"[:2000]}

    return {
        "interpret": interpret,
        "head_shapes": {"n_heads": heads, "n_kv_heads": kv, "head_dim": head_dim,
                        "softcap": c.attn_softcap, "window": c.sliding_window,
                        "dtype": "bfloat16"},
        "atol": KERNEL_ATOL,
        "cases": cases,
    }


def _kernel_case(max_abs_err: float) -> Dict[str, Any]:
    return {"ok": max_abs_err <= KERNEL_ATOL, "max_abs_err": max_abs_err}


# -- the run -------------------------------------------------------------------


def failures(report: Dict[str, Any], min_hbm_bytes: int) -> List[str]:
    """Every reason this report is not a pass."""
    out = []
    for phase in ("serving", "stream"):
        block = report[phase]
        for step, result in block["steps"].items():
            for name, request in result["requests"].items():
                out += [f"{phase}/{name}: {p}" for p in request["problems"]]
        if block["matrix_stats"]["fallbacks"]:
            out.append(f"{phase}: fused score matrix fell back to the per-call "
                       f"scorer {block['matrix_stats']['fallbacks']} time(s)")
        if block["sessions_opened"]["prefix"]:
            out.append(f"{phase}: {block['sessions_opened']['prefix']} token "
                       "search(es) fell back to the full-prefix session")
        if block["truncated_prompts"]:
            out.append(f"{phase}: {block['truncated_prompts']} prompt(s) cut "
                       "to fit max_context")
        if block["engine"]["fused_search_sessions"]:
            out.append(f"{phase}: /healthz reports "
                       f"{block['engine']['fused_search_sessions']} live "
                       "search sessions after every request returned")
    serving_block, stream_block = report["serving"], report["stream"]
    for variant, lengths in serving_block["prompts"].items():
        if lengths["tokens"] > lengths["room"]:
            out.append(f"{variant} prompt of {lengths['tokens']} tokens does "
                       f"not fit {lengths['room']}")
    if not serving_block["sessions_opened"]["fused"]:
        out.append("serving: no fused token-search session was opened")
    if not serving_block["matrix_stats"]["chunks"]:
        out.append("serving: the fused score matrix never ran")
    if not serving_block["programs"].get("generate_shared", {}).get("launches"):
        out.append("serving: the shared-trunk generate never ran")
    streams = stream_block["programs"].get("generate_stream", {}).get("launches", 0)
    if streams <= serving_block["programs"].get("generate_stream", {}).get("launches", 0):
        out.append("stream: the engine opened no K-step stream "
                   "(decode_steps fell back to the blocking generate)")
    if stream_block["backend_id"] != serving_block["backend_id"]:
        out.append("stream: the second server built a backend of its own")
    model = serving_block["model"]
    if model["embed_rows"] != model["vocab_size"]:
        out.append(f"served {model['embed_rows']} embedding rows for a "
                   f"vocabulary of {model['vocab_size']}")
    if report["pallas_interpret"]:
        out.append("Pallas kernels would run interpreted on this platform")
    for name, case in report["kernels"]["cases"].items():
        if not case["ok"]:
            out.append(f"kernel {name}: {case.get('error') or case}")
    for device in report["memory"]:
        if device["bytes_limit"] is not None and device["bytes_limit"] < min_hbm_bytes:
            out.append(f"device {device['id']}: bytes_limit "
                       f"{device['bytes_limit']} below the {min_hbm_bytes} the "
                       "backend sizes its budgets for")
    return out


def run(sizes: Sizes, mesh: Optional[str] = None,
        kernel_interpret: bool = False) -> Dict[str, Any]:
    """Both serving phases and the kernel phase on whatever device JAX has;
    returns the report with its ``failures`` filled in."""
    import jax

    from consensus_tpu.backends import clear_backend_cache
    from consensus_tpu.backends.tpu import _HBM_BYTES
    from consensus_tpu.utils.compile_cache import enable_compile_cache

    report: Dict[str, Any] = {
        "device": device_report(),
        "mesh": mesh,
        "compile_cache_dir": enable_compile_cache(),
        # transformer.py picks interpret mode for its Pallas calls from this.
        "pallas_interpret": jax.default_backend() == "cpu",
        "hbm_bytes_budgeted": _HBM_BYTES,
    }
    start = time.perf_counter()
    try:
        with CompileMeter() as meter:
            counters = Counters()
            report["serving"] = phase_serving(sizes, meter, counters, mesh)
            report["stream"] = phase_stream(sizes, meter, counters, mesh)
            report["memory"] = memory_report()
            prompts = report["serving"]["prompts"]
            search_width = max(
                prompts[v]["tokens"]
                for v in ("beam_search", "finite_lookahead", "mcts"))
            from consensus_tpu.backends.tpu import _width_bucket

            trunk = min(_width_bucket(search_width), sizes.max_context)
            n_roles = 1 + len(scenario_of(sizes)["agent_opinions"])
            before = meter.totals()
            kernel_start = time.perf_counter()
            report["kernels"] = phase_kernels(
                sizes.model,
                # What phase 1 ran, one width under 128, and the ladder's 192
                # (a multiple of 64 but not of 128).
                widths=sorted({64, 192, *report["serving"]["forward_widths"]}),
                search_shapes=[
                    (sizes.beam_width, n_roles, trunk, sizes.search_tokens),
                    (1, n_roles, trunk, sizes.search_tokens),
                    (3, 2, 192, 16),
                ],
                interpret=kernel_interpret,
            )
            report["kernels"]["seconds"] = round(
                time.perf_counter() - kernel_start, 3)
            report["kernels"]["jax"] = meter.since(before)
            report["jax_totals"] = meter.totals()
    finally:
        # The next caller in this process (a test, a second run) builds its
        # own backend; the weights are not held for it.
        clear_backend_cache()
        gc.collect()
    report["total_seconds"] = round(time.perf_counter() - start, 3)
    report["failures"] = failures(report, _HBM_BYTES)
    return report


def require_tpu() -> None:
    """Exit with code 2, before any phase, where JAX has no TPU."""
    try:
        import jax

        device = jax.devices()[0]
    except Exception as exc:  # no backend at all is also "no accelerator"
        print(f"chip_smoke: JAX found no device: {exc}", file=sys.stderr)
        raise SystemExit(2)
    if device.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, JAX reports {device.platform!r} "
            f"({device.device_kind}); no phase was run",
            file=sys.stderr,
        )
        raise SystemExit(2)


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--mesh", default=None, metavar="dp=N,tp=M",
        help="passed to create_server(mesh=...), as the server's --mesh is")
    args = parser.parse_args(argv)

    require_tpu()
    report = run(Sizes(), mesh=args.mesh)
    report["claim"] = None

    out_dir = pathlib.Path(__file__).resolve().parent / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    name = "chip_smoke.json" if args.mesh is None else (
        "chip_smoke_" + args.mesh.replace("=", "").replace(",", "_") + ".json")
    (out_dir / name).write_text(json.dumps(report, indent=1) + "\n")
    print(json.dumps(report, indent=1))
    if report["failures"]:
        for failure in report["failures"]:
            print(f"chip_smoke: FAILED: {failure}", file=sys.stderr)
        return 1
    device = report["device"]
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["count"],
    }}))
    return 0


if __name__ == "__main__":
    try:
        import consensus_tpu  # noqa: F401
    except ImportError as exc:
        print(f"chip_smoke: not in a checkout of the repository: {exc}",
              file=sys.stderr)
        raise SystemExit(2)
    raise SystemExit(main())
