"""On-device model backend: the TPU replacement for the reference's HTTP client.

Where the reference sends every generate/score/sample/embed call to the
Together API one request at a time (src/utils.py:70-525), this backend owns
a resident JAX transformer (Gemma-2 / Llama-3 family or tiny test configs)
and executes each protocol call as ONE padded, jitted device batch:

* ``generate``  — left-padded batch prefill + ``lax.scan`` decode with
  temperature/top-k, per-request logit bias sets, EOS ids, host-side stop-
  string truncation (the ``generate_text`` surface, src/utils.py:77-198);
* ``score``     — right-padded teacher-forced forward with the streaming
  logsumexp scorer; returns continuation-token logprobs directly, replacing
  the echo'd-prompt span extraction (src/utils.py:201-373, SURVEY §7.3);
* ``next_token_logprobs`` — one forward for the exact next-token
  distribution; top-k or seeded Gumbel-top-k gives k DISTINCT candidates,
  replacing rejection-sampling-via-repeated-1-token-calls
  (beam_search.py:199-333, mcts.py:165-247);
* ``embed``     — masked mean-pooled final hidden states, L2-normalized
  (the reference calls a separate embeddings API, src/utils.py:376-407).

Shape discipline: prompts pad into power-of-two length buckets so XLA
compiles a small, reused set of programs.  Multi-device: params are placed
with the tensor-parallel layout and batches shard over ``data`` when a mesh
is configured (consensus_tpu.parallel).

Seed semantics (SURVEY §7.4): each request's seed folds into its OWN row
PRNG key, so a request's output is independent of which other requests
share its device batch (matching the reference's per-request determinism,
habermas_machine.py:91-95) — though not bitwise-comparable to the
reference's server-side seeds.
"""

from __future__ import annotations

import collections
import functools
import hashlib
import logging
import pathlib
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from consensus_tpu.backends.base import (
    SHARED_TRUNK_SOLO_ROWS as _SHARED_TRUNK_SOLO_ROWS,
    GenerationRequest,
    GenerationResult,
    NextTokenRequest,
    ScoreRequest,
    ScoreResult,
    TokenCandidate,
)
from consensus_tpu.models.config import (
    NEEDS_ONE_KIND,
    SEARCH_NEEDS_STATE,
    STREAM_NEEDS_STATE,
    LayerKindsUnsupported,
    ModelConfig,
    RecurrentStateUnsupported,
    get_model_config,
)
from consensus_tpu.obs.backends import BackendInstruments
from consensus_tpu.obs.trace import span
from consensus_tpu.models.generate import generate_tokens, next_token_topk
from consensus_tpu.models.tokenizer import get_tokenizer
from consensus_tpu.models.transformer import (
    forward,
    init_params,
    token_logprobs,
    token_logprobs_streamed,
)
from consensus_tpu.utils.compile_cache import enable_compile_cache

logger = logging.getLogger(__name__)

#: Above this vocab size the streaming scorer replaces full-logit scoring.
_STREAMED_VOCAB_THRESHOLD = 32_768
#: Cap on the shared-scoring suffix attention's per-layer fp32 logits
#: transient (rows x heads x span x (ctx+span) x 4B) — it has no flash
#: kernel, so oversized groups fall back to the classic (flash) path.
_SHARED_SCORE_ATTN_BYTES_CAP = 1 << 31  # 2 GB

#: Below this many identical-prompt rows the shared-trunk generate path
#: isn't worth its own (1-row prefill + B-tail decode) program variant.
_SHARED_TRUNK_MIN_ROWS = 4

#: A small identical-prompt group inside a LARGER batch routes classic
#: instead: combined classic chunks amortize the per-step weight read over
#: every row in the chunk, which beats the shared path's 1-row prefill
#: once the group is under ``_SHARED_TRUNK_SOLO_ROWS`` (backends/base.py:
#: the engine forms its cohorts by the same number; see _generate_impl).

#: Search-session KV caches above this (plus resident weights) risk HBM
#: exhaustion — fall back to the cacheless full-prefix session instead.
_SESSION_CACHE_BYTES_CAP = 8 * 1024**3

#: v5e HBM (15.75 GB usable) and the live-budget floor/reserve used to size
#: the concurrent-session budget against the resident weights.
_HBM_BYTES = 15 * 1024**3
_ACTIVATION_RESERVE_BYTES = 3 * 1024**3
_SESSION_MIN_BUDGET_BYTES = 1 * 1024**3

#: Pages a score matrix's pool and blocks its rows' tables grow by, with
#: layers of more than one kind.  Such a configuration's paged program is a
#: loop a run of equal layers (four at MiMo-V2-Flash's period, 7-10 s of
#: compiling each on the chip's host), and a pool sized to the page makes two
#: new programs of nearly every matrix: 103 over the benchmark's warm-up
#: ladder, 20 in these steps.  The price: at most 255 pages that no table
#: names and 15 blocks a row that the rows' lengths mask.
_KINDS_POOL_STEP_PAGES = 256
_KINDS_TABLE_STEP_BLOCKS = 16

#: Characters of text whose token ids a backend remembers
#: (``TPUBackend.token_ids``), the least recently asked for going first.  A
#: statement asks for the same two prompts, 4-5 agent contexts and 32
#: candidates some 190 times over (the engine's accounting, 32 rows of one
#: prompt, two score matrices, the embedder): some 60k characters, and a
#: sweep sends a scenario's prompts again under every seed.  Two million
#: characters hold every request in flight and the scenarios of a sweep, in
#: at most 20 MB (a byte tokenizer's id a character); a constant and no
#: option, because nothing a caller can observe depends on it.
_TOKEN_MEMO_CHARS = 2 * 1024 * 1024


class _SessionBudget:
    """HBM budget for LIVE session caches.  Concurrent sweep cells each hold
    a session for a whole statement; unbounded, four wide-beam sessions plus
    resident weights exceed a v5e chip's 16 GB.  Opening a session blocks
    until its cache fits; closing releases the reservation."""

    def __init__(self, cap_bytes: int):
        self.cap = cap_bytes
        self.used = 0
        self._cond = threading.Condition()

    def acquire(self, nbytes: int) -> None:
        with self._cond:
            while self.used + nbytes > self.cap:
                self._cond.wait()
            self.used += nbytes

    def release(self, nbytes: int) -> None:
        with self._cond:
            self.used -= nbytes
            self._cond.notify_all()


def _require_requested_platform() -> Dict[str, Any]:
    """The default device as JAX reports it, or an error when JAX fell back
    to the CPU without being asked to.  With no accelerator and
    ``JAX_PLATFORMS`` unset JAX runs on the CPU with only a warning; a
    ``tpu`` backend then serves minutes-per-token statements under an
    accelerator's name.  Naming ``cpu`` in ``JAX_PLATFORMS`` (the tests do)
    is asking for it."""
    devices = jax.devices()
    platform = devices[0].platform
    if platform == "cpu" and "cpu" not in (jax.config.jax_platforms or ""):
        raise RuntimeError(
            "TPUBackend: JAX found no accelerator and fell back to the CPU. "
            "Set JAX_PLATFORMS=cpu to run on the CPU on purpose."
        )
    return {
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
    }


def _bucket(n: int, minimum: int = 32) -> int:
    size = minimum
    while size < n:
        size *= 2
    return size


def _width_bucket(n: int, minimum: int = 128) -> int:
    """Sequence-length bucket on a {1, 1.5} x power-of-two ladder
    (128, 192, 256, 384, 512, ...).  Rows bucket to powers of two, but
    widths deserve the finer ladder: a 350-token scoring prompt padded to
    512 wastes 32% of a compute-bound forward, padded to 384 only 9%.
    Ladder steps stay multiples of the 128-lane TPU tile."""
    size = minimum
    while size < n:
        if size + size // 2 >= n:
            return size + size // 2
        size *= 2
    return size


class TPUBackend:
    name = "tpu"
    #: Temperature-0 generation is argmax (models/sampling.py): the request
    #: seed never enters the program, so re-issuing an identical greedy
    #: request is bitwise-identical.  Callers with seed-incrementing retry
    #: loops (habermas rankings) use this to elide provably-identical
    #: retries.  API backends stay False (server-side nondeterminism).
    deterministic_greedy = True

    def __init__(
        self,
        model: str = "tiny-gemma2",
        checkpoint: Optional[str] = None,
        tokenizer: Optional[str] = None,
        dtype: str = "bfloat16",
        max_context: int = 1024,
        base_seed: int = 0,
        tp: int = 1,
        dp: Optional[int] = None,
        params: Optional[Dict[str, Any]] = None,
        config: Optional[ModelConfig] = None,
        use_flash_attention: bool = False,
        use_decode_attention: bool = False,
        max_batch_rows: int = 64,
        quantization: Optional[str] = None,
        shared_context_scoring: bool = False,
        shared_trunk_generation: bool = True,
        pin_generation_budget: bool = False,
        segmented_decode: bool = True,
        decode_segment_len: int = 128,
        kv_quant: bool = True,
        quantize_frozen_kv: Optional[bool] = None,
        mesh: Optional[Any] = None,
    ):
        # ``mesh={'dp': N, 'tp': M}`` (or the "dp=4,tp=2" CLI string) is the
        # serving-path spelling of the tp/dp pair — create_server and the
        # sweep configs pass one opaque value straight through.  Explicit
        # tp=/dp= args win when both are given.
        if mesh is not None:
            from consensus_tpu.parallel import parse_mesh_spec

            parsed = parse_mesh_spec(mesh)
            if tp == 1:
                tp = parsed["tp"]
            if dp is None:
                dp = parsed["dp"]
        self.config = config if config is not None else get_model_config(model)
        if use_flash_attention and not self.config.use_flash_attention:
            import dataclasses

            self.config = dataclasses.replace(self.config, use_flash_attention=True)
        if use_decode_attention and not self.config.use_decode_attention:
            import dataclasses

            self.config = dataclasses.replace(self.config, use_decode_attention=True)
        self.model_name = model
        family = "llama" if "llama" in self.config.name else "gemma"
        self.tokenizer = get_tokenizer(tokenizer, family=family)
        # The model keeps its published vocabulary whatever the tokenizer's
        # size; ids the tokenizer cannot decode are banned where tokens are
        # sampled or proposed, and nowhere else.
        if self.tokenizer.vocab_size < self.config.vocab_size:
            import dataclasses

            self.config = dataclasses.replace(
                self.config, sample_vocab=self.tokenizer.vocab_size
            )
        #: The device this backend's programs run on, as JAX reports it —
        #: stamped into the server's startup line and /healthz.
        self.device_info = _require_requested_platform()
        enable_compile_cache()
        self.max_context = max_context
        #: Prompts cut to fit ``max_context`` (oldest tokens dropped), over
        #: the life of the backend.  A caller that must not lose context
        #: checks this is zero.
        self.truncated_prompts = 0
        self.base_seed = base_seed
        # Device-batch cap: callers may hand over an arbitrarily large
        # request list (a whole sweep cell); slices bound peak activation
        # memory — a (B, H, S, S) einsum-path batch or (B, S, V) logit batch
        # must not scale with the sweep size.  Each public call processes
        # ceil(B / max_batch_rows) jitted slices and concatenates.
        self.max_batch_rows = max(1, max_batch_rows)
        self.shared_context_scoring = bool(shared_context_scoring)
        self.shared_trunk_generation = bool(shared_trunk_generation)
        # Segmented decode (models/generate.py): long-budget shared-trunk
        # generations carry only a decode_segment_len-column live KV tail
        # through the while_loop (a loop carry is state the compiler may
        # copy every step); completed segments become read-only operands.
        # Kicks in at max_new >= 2*seg_len — short budgets keep the
        # monolithic single-dispatch program.
        self.segmented_decode = bool(segmented_decode)
        self.decode_segment_len = max(16, int(decode_segment_len))
        self._seg_len_fallbacks: set = set()  # budgets already logged
        # int8 generated-token KV for segmented decodes: the live tail is
        # WRITTEN int8+scale (halving the while_loop carry) and frozen
        # segment blocks stay int8
        # (halving their read bytes and roughly doubling the segmented row
        # allowance).  ON by default — generation numerics are no longer
        # bit-identical to the bf16 KV path (teacher-forced scoring never
        # touches generated KV, so scores are unaffected); measured logit/
        # token deltas: reports/kv_quant_delta.md.  ``quantize_frozen_kv``
        # is the round-3 name for the frozen-only variant, kept as an
        # alias so older configs keep working.
        if quantize_frozen_kv is not None:
            kv_quant = bool(quantize_frozen_kv)
        # (A cache a kind of attention has no int8 form: such a
        # configuration keeps its generated keys and values as they are.)
        self.kv_quant = bool(kv_quant) and not self.config.has_layer_kinds
        # Timing mode (VERDICT r2 #4): pin every generation to its full
        # max_tokens budget (no EOS early-exit, no stop-string truncation)
        # so random-weight timing runs can't flatter themselves with 1-token
        # degenerate statements.  Never use for quality runs.
        self.pin_generation_budget = bool(pin_generation_budget)

        if quantization not in (None, "none", "int8"):
            raise ValueError(f"unknown quantization mode: {quantization!r}")
        if self.config.has_layer_kinds:
            if quantization == "int8":
                raise LayerKindsUnsupported("int8 weights", NEEDS_ONE_KIND)
            if tp > 1 or (dp is not None and dp > 1):
                raise LayerKindsUnsupported(
                    "a mesh of several chips (tp > 1 or dp > 1)", NEEDS_ONE_KIND)
        want_int8 = quantization == "int8" and params is None

        jax_dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dtype]
        # Weight-only int8 (models/quant.py) halves the HBM bytes every
        # decode step re-reads — and for gemma2-9b/llama3-8b it is the only
        # way onto one 16 GB v5e at all (their bf16 trees alone exceed HBM).
        # So the full-precision tree must NEVER land on the accelerator:
        # init/load on the host CPU backend, quantize there (threefry is
        # platform-deterministic, so host init == device init), and ship
        # only the int8+scale leaves across.
        import contextlib

        def host():
            # Fresh context per use: jax.default_device returns a
            # single-entry context manager, and the int8 path enters once
            # for init/load and again for the quantize pass.
            return (
                jax.default_device(jax.local_devices(backend="cpu")[0])
                if want_int8
                else contextlib.nullcontext()
            )
        if params is not None:
            self.params = params
        elif checkpoint and (pathlib.Path(checkpoint) / "ingest.json").exists():
            # Pre-converted orbax checkpoint (cli/ingest_checkpoint.py):
            # leaves restore straight to the default device in their stored
            # (possibly already-int8) form — no host conversion pass, no
            # 5-10 min quantize on every process start.
            import json as _json

            from consensus_tpu.models.quant import quantize_params
            from consensus_tpu.utils.checkpoint import restore_params

            meta = _json.loads(
                (pathlib.Path(checkpoint) / "ingest.json").read_text()
            )
            # The manifest must agree with this backend's settings: a
            # silently-mismatched restore either builds a wrong eval_shape
            # template (cryptic orbax failure) or — worse — lands an
            # unquantized 8-9B bf16 tree straight on a 16 GB chip.
            mismatches = []
            if meta.get("model") and meta["model"] != self.config.name:
                mismatches.append(
                    f"model: ingested {meta['model']!r} vs backend "
                    f"{self.config.name!r}"
                )
            if meta.get("dtype") and meta["dtype"] != dtype:
                mismatches.append(
                    f"dtype: ingested {meta['dtype']!r} vs backend {dtype!r}"
                )
            ingested_quant = meta.get("quantization") or None
            wanted_quant = quantization if quantization != "none" else None
            if ingested_quant != wanted_quant:
                mismatches.append(
                    f"quantization: ingested {ingested_quant!r} vs backend "
                    f"{wanted_quant!r} — re-run cli/ingest_checkpoint with "
                    "the matching --quantization"
                )
            if mismatches:
                raise ValueError(
                    f"ingested checkpoint {checkpoint} does not match this "
                    "backend: " + "; ".join(mismatches)
                )
            template = jax.eval_shape(
                lambda: quantize_params(
                    init_params(self.config, jax.random.PRNGKey(0), jax_dtype)
                )
                if meta.get("quantization") == "int8"
                else init_params(self.config, jax.random.PRNGKey(0), jax_dtype)
            )
            self.params = restore_params(
                str(pathlib.Path(checkpoint) / "params"), template
            )
        elif checkpoint:
            from consensus_tpu.models.loader import load_params

            with host():
                self.params = load_params(checkpoint, self.config, jax_dtype)
        else:
            logger.warning(
                "TPUBackend: no checkpoint given — using RANDOM weights (%s). "
                "Statements will be noise; timings/shapes are real.",
                self.config.name,
            )
            with host():
                self.params = init_params(
                    self.config, jax.random.PRNGKey(base_seed), jax_dtype
                )

        if quantization == "int8":
            # Weight-only int8 (models/quant.py): halves decode HBM traffic;
            # composes with tensor parallelism (mesh.py shards q like the
            # weight and replicates squeezed scale axes).  The train step
            # keeps full-precision pytrees.
            from consensus_tpu.models.quant import is_quantized, quantize_params

            if not is_quantized(self.params):  # shared params may already be
                if want_int8:  # host tree: quantize on host, then transfer
                    with host():
                        # jit on the host device so XLA fuses the f32 casts
                        # instead of materializing eager 2x-size temporaries;
                        # donation frees each full-precision leaf as it is
                        # consumed (nothing else references the host tree).
                        quantized = jax.jit(quantize_params, donate_argnums=0)(
                            self.params
                        )
                    if tp > 1:  # shard_params below places the int8 tree
                        self.params = quantized
                    else:
                        self.params = jax.device_put(quantized, jax.devices()[0])
                else:
                    # Caller-supplied device tree (assumed to fit): the
                    # caller may still hold references, so do NOT donate.
                    self.params = jax.jit(quantize_params)(self.params)
        self.quantization = quantization if quantization != "none" else None

        if tp > 1 or (dp is not None and dp > 1):
            # Pure DP (tp=1, dp>1) is the production multi-chip serving mode
            # (SURVEY §2.16 table / §5.8): params replicate over ``data`` —
            # the TP PartitionSpecs never name the data axis, so shard_params
            # on a (dp, 1) mesh replicates every leaf — and the protocol
            # batch rows shard over ``data`` (see _left_pad_batch /
            # _score_impl).  A sweep's co-batched rows then run dp-wide with
            # XLA inserting no per-layer collectives at all.
            from consensus_tpu.parallel import make_mesh, shard_params

            self.mesh_plan = make_mesh(tp=tp, dp=dp)
            self.params = shard_params(self.params, self.mesh_plan.mesh)
        else:
            self.mesh_plan = None

        self._bias_id_cache: Dict[str, Tuple[int, ...]] = {}
        # ``token_ids``' memo: text -> ids without BOS, oldest first; the
        # request threads (through the engine's ``submit``) and the engine's
        # thread both ask.
        self._token_memo: "collections.OrderedDict[str, Tuple[int, ...]]" = (
            collections.OrderedDict())
        self._token_memo_chars = 0
        self._token_memo_lock = threading.Lock()
        # obs: padding efficiency and first sightings per (kind, rows,
        # width) bucket, H2D/D2H transfer timings — recorded into the
        # process registry (metrics.json / bench extra).  What JAX compiled
        # is the compile record's, installed by enable_compile_cache().
        self.instruments = BackendInstruments("tpu")
        if self.config.has_layer_kinds:
            itemsize = jnp.dtype(jax_dtype).itemsize
            for name, n, heads in self.config.cache_kinds:
                self.instruments.record_kv_bytes_per_token(
                    name, n * heads * itemsize
                    * sum(self.config.cache_widths(name)))
            self.instruments.record_kv_bytes_per_token(
                "all", self.config.kv_bytes_per_token(itemsize))
        self.call_counts = {
            "generate": 0, "score": 0, "next_token": 0, "embed": 0,
            "score_matrix": 0,
        }
        # Token-honest accounting (VERDICT r2 #4): "generated" counts
        # statement tokens actually emitted (what the API baseline bills as
        # output); "scored" counts teacher-forced positions whose logprob a
        # caller consumed (continuation tokens, next-token proposals,
        # session candidate x agent evaluations).  Cell-level deltas land in
        # each run dir's token_counts.json (experiment.py).
        self.token_counts = {"generated": 0, "scored": 0}
        # Fused utility-matrix accounting (score_matrix): device chunk
        # launches and per-call fallbacks — the chunked-under-budget tests
        # and BENCH_SCORE read these.
        self.matrix_stats = {"calls": 0, "chunks": 0, "fallbacks": 0}
        self._unseeded_calls = 0
        # Guards the unseeded-call nonce: concurrent sweep cells opening
        # sessions/batches must never derive the same "fresh" stream.
        self._nonce_lock = threading.Lock()
        # Live-session HBM budget: what a v5e chip holds after the resident
        # weights and a reserve for per-call activation transients (merged
        # score/generate batches run concurrently with session steps).
        # PER-CHIP accounting: weights and KV caches shard over ``model``
        # only — over ``data`` the weights replicate (each chip holds the
        # full tree at tp=1), so the divisor is tp, not the device count.
        # DP's capacity win shows up in _generate_rows_allowed instead:
        # batch rows spread over the data axis.
        self._shard_count = self.mesh_plan.tp if self.mesh_plan else 1
        self._dp = self.mesh_plan.dp if self.mesh_plan else 1
        self._params_bytes = sum(
            x.size * jnp.dtype(x.dtype).itemsize
            for x in jax.tree_util.tree_leaves(self.params)
        ) // self._shard_count
        budget = min(
            _SESSION_CACHE_BYTES_CAP,
            max(
                _SESSION_MIN_BUDGET_BYTES,
                _HBM_BYTES - self._params_bytes - _ACTIVATION_RESERVE_BYTES,
            ),
        )
        self._session_budget = _SessionBudget(budget)

    # -- helpers -------------------------------------------------------------

    def kv_cache_identity(self) -> tuple:
        """Content-key identity for cross-request prefix KV reuse: two
        backends may share cached prefix pages only when the model tier, the
        KV quantization mode AND the tensor-parallel width all match — the
        engine's PrefixCache folds this into every blake2b content key
        (ops/kv_pages.py).  tp enters because a tp=2 backend's pages hold
        each chip's half of the kv heads: byte-compatible only with another
        tp=2 mesh, never with tp=1.  (dp does NOT enter — pages replicate
        over data, so any dp width reads tp-compatible pages.)"""
        return (
            self.model_name,
            "int8" if self.kv_quant else "dense",
            ("tp", self._shard_count),
            # Pages alone, or pages that mean nothing without the recurrent
            # state at their end: the two kinds of model never share a run.
            ("state", "recurrent" if self.config.has_ssm else "pages"),
        ) + ((
            # A pool a kind of attention, each at its own heads and widths:
            # (kind, layers, key-value heads, key width, value width; a
            # latent pool's value width is 0, it keeps none).
            ("kinds", tuple(
                (name, n, heads) + self.config.cache_widths(name)
                for name, n, heads in self.config.cache_kinds)),
        ) if self.config.has_layer_kinds else ())

    @property
    def has_layer_kinds(self) -> bool:
        """The configuration has layers of more than one kind: a cache a
        kind of attention, which one pool of one shape is not."""
        return self.config.has_layer_kinds

    @property
    def has_recurrent_state(self) -> bool:
        """The configuration has recurrent layers: a row holds a state
        beside its pages, and a run of pages is no prefix of it."""
        return self.config.has_ssm

    def _recurrent_row_bytes(self) -> int:
        """Bytes of one row's recurrent state over all layers (0 dense)."""
        itemsize = jnp.dtype(self.params["embed"].dtype).itemsize
        return self.config.ssm_state_bytes(itemsize) // self._shard_count

    def _kv_page_bytes(self, page_size: int) -> int:
        c = self.config
        kv_itemsize = (
            1.25
            if self.kv_quant
            else jnp.dtype(self.params["embed"].dtype).itemsize
        )
        bytes_per_token = int(
            c.kv_bytes_per_token(kv_itemsize)
        ) // self._shard_count or 1
        return bytes_per_token * page_size

    def recurrent_state_pages(self, page_size: int = 16) -> int:
        """One row's recurrent state in pages of the engine's pool, rounded
        up: what the engine reserves a row beside its tokens' pages (0 for a
        configuration without recurrent layers)."""
        return -(-self._recurrent_row_bytes() // self._kv_page_bytes(page_size))

    def suggest_kv_page_pool(self, page_size: int = 16) -> int:
        """Size the decode engine's KV page pool from the session HBM
        budget (backends/engine.py asks at construction).  One page holds
        ``page_size`` tokens of per-layer K+V; ``kv_quant`` halves the
        bytes (int8 + per-token scale ≈ half of bf16).  Half the session
        budget goes to pages — the rest stays for fused search sessions,
        which reserve through ``_SessionBudget`` as before.  The pool's
        page count INCLUDES the prefix cache's share: the engine's LRU
        budget (a quarter of the pool by default) bounds how many of these
        pages cached prefixes may pin, so cache + resident slots can never
        outgrow the reservation made here.  A configuration with recurrent
        layers gets the same pool: the engine counts each resident row's
        state against it in pages (``recurrent_state_pages``)."""
        return max(
            64, (self._session_budget.cap // 2) // self._kv_page_bytes(page_size)
        )

    def _sliced(self, requests, fn, limit: Optional[int] = None):
        """Run ``fn`` over ``limit``-sized slices (default max_batch_rows)
        and concatenate.  Safe because per-request PRNG keys make results
        independent of batch composition."""
        limit = limit or self.max_batch_rows
        if len(requests) <= limit:
            return fn(requests)
        out = []
        for i in range(0, len(requests), limit):
            out.extend(fn(requests[i : i + limit]))
        return out


    def token_ids(
        self,
        text: str,
        add_bos: bool = False,
        tally: Optional[Dict[str, int]] = None,
    ) -> List[int]:
        """The tokenizer's ids of ``text``, encoded once however often they
        are asked for: every tokenisation of the serving path comes here (a
        call's rows over one prompt, a score matrix's contexts and
        candidates, the embedder, the engine's page accounting).  The memo
        is keyed by the text alone and holds the ids without BOS: both
        tokenizers put ``bos_id`` in front of what they encode without it,
        and so does this, so the embedder reuses what the scorer encoded.
        The list is the caller's own.  ``tally`` (a ``backend.tokenize``
        span's late attributes) counts under ``encoded`` the texts the
        tokenizer ran on.  Safe under any threads; two that ask for a new
        text at once may both encode it."""
        with self._token_memo_lock:
            ids = self._token_memo.get(text)
            if ids is not None:
                self._token_memo.move_to_end(text)
        fresh = ids is None
        if fresh:
            ids = tuple(self.tokenizer.encode(text))
            if len(text) <= _TOKEN_MEMO_CHARS:
                with self._token_memo_lock:
                    if text not in self._token_memo:
                        self._token_memo[text] = ids
                        self._token_memo_chars += len(text)
                    while self._token_memo_chars > _TOKEN_MEMO_CHARS:
                        gone, _ = self._token_memo.popitem(last=False)
                        self._token_memo_chars -= len(gone)
        self.instruments.record_tokenized(fresh)
        if tally is not None:
            tally["encoded"] = tally.get("encoded", 0) + int(fresh)
        return [self.tokenizer.bos_id, *ids] if add_bos else list(ids)

    def _fit(self, ids: List[int], width: int) -> List[int]:
        """``ids`` cut to its most recent ``width`` tokens.  A cut is counted
        and logged, never silent: the caller's prompt lost its beginning."""
        if len(ids) <= width:
            return ids
        self.truncated_prompts += 1
        logger.warning(
            "prompt of %d tokens cut to its last %d (max_context=%d)",
            len(ids), width, self.max_context,
        )
        return ids[-width:]

    def _render_prompt(self, request) -> str:
        if getattr(request, "chat", True):
            return self.tokenizer.chat_prompt(
                request.user_prompt, request.system_prompt
            )
        return self.tokenizer.raw_prompt(request.user_prompt, request.system_prompt)

    def _batch_width(self, token_lists: List[List[int]]) -> int:
        """The bucketed width _left_pad_batch will allocate for this batch —
        shared so HBM allowances are computed from the allocated width."""
        longest = min(max(len(t) for t in token_lists), self.max_context)
        return min(_width_bucket(longest), self.max_context)

    def _shared_cont_width(self, max_cont: int) -> int:
        """Continuation-width bucket used by _score_shared_group — a coarse
        pow2 ladder from 64 (every variant is a fresh compile, so the
        variant space stays small), capped at the context window."""
        width = 64
        while width < max_cont:
            width *= 2
        return min(width, self.max_context)

    def _place_batch(self, *arrays):
        """Commit batch-leading arrays to the mesh, rows sharded over
        ``data``.  Rows that don't divide dp (sessions with odd role counts)
        stay uncommitted — jit replicates them, still correct.  Single-device
        backends pass through."""
        with span("backend.h2d", arrays=len(arrays)), \
                self.instruments.time_h2d():
            if self._dp > 1 and all(a.shape[0] % self._dp == 0 for a in arrays):
                from consensus_tpu.parallel.mesh import shard_batch

                placed = shard_batch(self.mesh_plan.mesh, *arrays)
                return placed if len(arrays) > 1 else (placed,)
            return tuple(jnp.asarray(a) for a in arrays)

    def _fetch(self, *arrays):
        """np.asarray with D2H timing.  Under async dispatch the fetch
        blocks on device work still in flight, so this reading (and the
        ``backend.d2h`` span) is an upper bound that includes device
        execution, not pure transfer: time inside it is the host waiting
        for the device.  Arrays already on host (the segmented decode loop
        returns numpy) pass through without polluting the histogram with
        zero samples."""
        if all(isinstance(a, np.ndarray) for a in arrays):
            out = arrays
        else:
            with span("backend.d2h", arrays=len(arrays)), \
                    self.instruments.time_d2h():
                out = tuple(np.asarray(a) for a in arrays)
        return out if len(arrays) > 1 else out[0]

    def _left_pad_batch(
        self, token_lists: List[List[int]]
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(tokens, valid) left-padded into a shared length bucket."""
        width = self._batch_width(token_lists)
        pad = self.tokenizer.pad_id
        tokens = np.full((len(token_lists), width), pad, np.int32)
        valid = np.zeros((len(token_lists), width), bool)
        for row, ids in enumerate(token_lists):
            ids = self._fit(ids, width)
            tokens[row, width - len(ids):] = ids
            valid[row, width - len(ids):] = True
        tokens, valid = self._place_batch(tokens, valid)
        return tokens, valid

    def _bias_table(
        self, requests: Sequence
    ) -> Tuple[Optional[jnp.ndarray], Optional[jnp.ndarray]]:
        """Dedup per-request bias sets into a device table + row index.

        Batches share few distinct bias sets (usually one), so shipping a
        (U, V) table and gathering (B, V) rows ON DEVICE replaces a dense
        per-row host matrix (~1 MB/row at 256k vocab)."""
        if not any(r.bias_against_tokens for r in requests):
            return None, None
        unique: Dict[Tuple, int] = {}
        vectors: List[np.ndarray] = []
        index = np.zeros((len(requests),), np.int32)
        for row, request in enumerate(requests):
            key = (tuple(request.bias_against_tokens), request.bias_value)
            if key not in unique:
                vector = self._bias_vector(
                    request.bias_against_tokens, request.bias_value
                )
                if vector is None:
                    vector = np.zeros((self.config.vocab_size,), np.float32)
                unique[key] = len(vectors)
                vectors.append(vector)
            index[row] = unique[key]
        return jnp.asarray(np.stack(vectors)), jnp.asarray(index)

    def _bias_vector(
        self, bias_tokens: Sequence[str], bias_value: float
    ) -> Optional[np.ndarray]:
        if not bias_tokens:
            return None
        vector = np.zeros((self.config.vocab_size,), np.float32)
        for text in bias_tokens:
            key = text
            if key not in self._bias_id_cache:
                self._bias_id_cache[key] = tuple(
                    self.tokenizer.token_ids_containing(text)
                )
            for token_id in self._bias_id_cache[key]:
                vector[token_id] += bias_value
        return vector

    @staticmethod
    def _fold_of(*parts) -> int:
        # Stable across processes (Python's hash() is salted per process).
        digest = hashlib.blake2b(repr(parts).encode(), digest_size=4).digest()
        return int.from_bytes(digest, "big") % (2**31)

    def _fold_seed(self, *parts) -> jax.Array:
        return jax.random.fold_in(
            jax.random.PRNGKey(self.base_seed), self._fold_of(*parts))

    def _row_keys(self, kind: str, seeds: Sequence[Optional[int]]) -> jnp.ndarray:
        """Per-row PRNG keys. Seeded rows fold only their own seed (batch-
        composition independent, VERDICT r1 #7).  Unseeded rows must stay
        DIVERSE — identical unseeded prompts in one batch (best_of_n drafts,
        habermas candidates) each need a distinct stream — so they fold their
        row index plus a per-backend nonce instead.  The folds are hashed
        here and folded into the base key by one program for all the rows
        (``_fold_keys``): row for row what ``_fold_seed`` returns."""
        folds = []
        for row, seed in enumerate(seeds):
            if seed is None:
                with self._nonce_lock:
                    self._unseeded_calls += 1
                    nonce = self._unseeded_calls
                folds.append(self._fold_of(kind, "unseeded", row, nonce))
            else:
                folds.append(self._fold_of(kind, seed))
        return _fold_keys(self.base_seed, np.asarray(folds, np.uint32))

    # -- generate ------------------------------------------------------------

    def generate(self, requests: Sequence[GenerationRequest]) -> List[GenerationResult]:
        # The wide slice exists for the SHARED-TRUNK path: its prefill is 1
        # row and its per-step state is (B, V) logits + the KV tail, so a
        # co-batched sweep cell's hundreds of identical-prompt drafts ride
        # ONE decode dispatch instead of ceil(B/32) sequential ones (each
        # with its own dispatch overhead).  The classic path
        # re-caps itself at max_batch_rows (its B-row prefill still
        # materializes per-layer (B, g, r, S, T) fp32 attention logits —
        # the transient max_batch_rows exists to bound).
        return self._sliced(requests, self._generate_impl, limit=256)

    def generate_stream(
        self,
        requests: Sequence[GenerationRequest],
        decode_steps: int = 1,
        speculative: bool = False,
    ) -> "_PagedGenerateStream":
        """Multi-token decode stream (engine ``decode_steps`` seam).

        Prefills the cohort into a private page pool and then serves it in
        K-step windows of ``models/stepper.py:paged_decode_steps``:
        ``dispatch()`` enqueues one window and returns without fetching
        (jax async dispatch — on TPU the host is free while the device
        decodes), ``collect()`` fetches the window's token/emitted arrays
        and finalizes rows that froze inside it with the exact
        ``_finish_generation`` semantics.  Sampling replays the sequential
        per-row key-split schedule, so emitted tokens are independent of K.

        With ``speculative=True`` each window instead drafts K tokens per
        row from an n-gram self-proposer and verifies them in ONE
        ``paged_verify_steps`` dispatch — ``1 + accepted`` real tokens per
        window instead of 1 per scan step, byte-identical token streams
        (exact sequential PRNG replay).
        """
        if self.config.has_ssm:
            raise RecurrentStateUnsupported(
                "generate_stream (the engine's decode_steps)",
                STREAM_NEEDS_STATE,
            )
        if self.config.has_layer_kinds:
            raise LayerKindsUnsupported(
                "generate_stream (the engine's decode_steps)", NEEDS_ONE_KIND)
        return _PagedGenerateStream(
            self, list(requests), decode_steps, speculative=speculative
        )

    def _seg_len_for(self, max_new: int) -> Optional[int]:
        """Segment length for a decode budget, or None for monolithic.

        Short budgets keep the monolithic single-dispatch program.  The
        fused pallas decode-attention kernel has no frozen-operand variant,
        so the two options are mutually exclusive — with use_decode_attention
        set, segmentation would silently drop the kernel for every segment
        after the first (code review r3).  The length must divide the
        bucketed budget: the {1,1.5}x-pow2 ladder makes 128 fit 256/384/
        512/768/1024 and 96 catch the 192 bucket (best_of_n's 150-token
        statements).

        Cold-compile cost, stated honestly: each frozen width (seg_len,
        2*seg_len, ... max_new - seg_len) is its own _decode_segment
        program — a 768 budget compiles ~6 decode programs per (rows, ctx)
        bucket where the monolithic path compiled 1.  The persistent
        compilation cache (utils/compile_cache.py) makes that a one-time
        cost per checkout.  The step-time gain of segmenting is not
        measured on this toolchain.
        """
        if not self.segmented_decode or self.config.use_decode_attention:
            return None
        for seg_len in (self.decode_segment_len, 96, 64):
            if max_new >= 2 * seg_len and max_new % seg_len == 0:
                if seg_len != self.decode_segment_len:
                    # Tell the operator (once per budget) their configured
                    # length was unusable for this bucket — tuning runs need
                    # to know which length actually served it (ADVICE r3).
                    if max_new not in self._seg_len_fallbacks:
                        self._seg_len_fallbacks.add(max_new)
                        logger.info(
                            "segmented decode: budget %d is not a multiple of "
                            "decode_segment_len=%d >= 2x; using seg_len=%d",
                            max_new, self.decode_segment_len, seg_len,
                        )
                return seg_len
        return None

    def _segmented_rows_allowed(
        self, prompt_width: int, max_new: int, seg_len: int
    ) -> int:
        """Row allowance for a SEGMENTED decode.

        Per-row KV columns at peak: the prompt trunk, the single-buffered
        frozen blocks (max_new − seg_len columns — blocks append to a LIST,
        so round 3's 2x concatenate transient is gone), the double-buffered
        seg_len live tail, and one seg_len of compaction-gather transient
        (old + gathered block rows coexist briefly).  With ``kv_quant``
        the frozen blocks AND the live tail are int8+scale — bytes halve,
        plus seg_len/8 of margin for the f32 scale planes (4 bytes per
        hd=256 int8 lane group ≈ 1.6%) — and the classic-layout prompt
        trunk is int8 too, so its decode-time cost halves; the binding
        moment for wide prompts becomes the prefill→quantize transient
        (bf16 + int8 trunks alive together, 1.5x the bf16 trunk).
        """
        gen_cols = (max_new - seg_len) + 2 * seg_len + seg_len
        if self.kv_quant:
            # seg_len//4 margin covers the f32 scale planes plus compiler
            # temps.
            q_cols = (gen_cols + 1) // 2 + seg_len // 4
            effective = max(
                prompt_width + prompt_width // 2 + 2 * seg_len,
                (prompt_width + 1) // 2 + prompt_width // 16 + q_cols,
            )
        else:
            effective = prompt_width + gen_cols
        return self._generate_rows_allowed(effective - 2 * seg_len, seg_len)

    def _generate_rows_allowed(self, prompt_width: int, max_new: int) -> int:
        """Largest decode batch whose KV cache fits HBM next to the weights.
        The prompt trunk is a scan closure constant (single-buffered); only
        the max_new-column tail rides the loop carry, budgeted at two
        copies (old and new value of the carry live together)."""
        c = self.config
        itemsize = jnp.dtype(self.params["embed"].dtype).itemsize
        unit = int(c.kv_bytes_per_token(itemsize)) // self._shard_count
        # A recurrent layer's state rides the loop's carry beside the tail:
        # two copies a row, whatever the prompt's length (0 bytes dense).
        per_row = (
            (prompt_width + 2 * max_new) * unit + 2 * self._recurrent_row_bytes()
        )
        # Live search sessions hold real HBM reservations from the same
        # non-weight slice — generate batches must fit BESIDE them.
        budget = (
            _HBM_BYTES - self._params_bytes - _ACTIVATION_RESERVE_BYTES
            - self._session_budget.used
        )
        allowed = max(1, budget // per_row)
        # Round DOWN to the {1, 1.5} x pow2 ladder so chunk shapes stay
        # reusable — all the way to 1: returning a floor of 8 when only 2
        # rows fit would reintroduce the OOM this guard exists to prevent.
        # The ladder matters: long-generation decode is parameter-read
        # bound, so 24-row chunks beat a pow2 floor of 16 by 1.5x.
        bucket = 1
        while bucket * 2 <= allowed:
            bucket *= 2
        if bucket >= 2 and bucket + bucket // 2 <= allowed:
            bucket += bucket // 2
        # Pure DP: batch rows shard over ``data``, so dp chips hold dp x the
        # rows.  Scaling the per-chip ladder keeps every chunk size divisible
        # by dp (so _place_batch can actually shard it).
        return bucket * self._dp

    def _generate_impl(
        self,
        requests: Sequence[GenerationRequest],
        token_lists: Optional[List[List[int]]] = None,
    ) -> List[GenerationResult]:
        """Route: LARGE groups of identical prompts take the shared-trunk
        decode (prefill once, per-step KV reads drop from B·(ctx+t) to
        ctx+B·t — the shape of best_of_n's N drafts and the habermas
        candidate phase); everything else takes the classic per-row path.

        The size threshold matters because long decodes are weight-read
        bound: a B-row shared decode pays the full ~5 ms/step weight read
        over only B rows, while small groups COMBINED into one classic
        batch amortize it over the whole chunk (measured 0.35-0.41
        ms/row·step at B=32-48 classic vs ~1.4 ms/row·step at B=4 shared).
        The habermas revision phase is the canonical case: 30 concurrent
        statements × min(nc,4) rows of 30 DISTINCT prompts — as 4-row
        shared groups that is 30 serial small decodes; as classic chunks
        it is ~4 warm 32-row batches (round-4 fix).  A group that IS the
        whole batch still takes the shared path at >=_SHARED_TRUNK_MIN_ROWS
        (nothing else to amortize weights with, and the 1-row prefill
        wins)."""
        if not requests:
            return []

        if token_lists is None:
            with span("backend.tokenize", rows=len(requests)) as tally:
                token_lists = [
                    self.token_ids(
                        self._render_prompt(r), add_bos=True, tally=tally)
                    for r in requests
                ]
        if self.shared_trunk_generation:
            groups: Dict[Tuple[int, ...], List[int]] = {}
            for i, ids in enumerate(token_lists):
                groups.setdefault(tuple(ids), []).append(i)

            def takes_shared_path(ids_t, idxs) -> bool:
                if not ids_t or len(idxs) < _SHARED_TRUNK_MIN_ROWS:
                    return False
                return (
                    len(idxs) >= _SHARED_TRUNK_SOLO_ROWS
                    or len(idxs) == len(requests)
                )

            if any(takes_shared_path(t, i) for t, i in groups.items()):
                results: List[Optional[GenerationResult]] = [None] * len(requests)
                classic: List[int] = []
                for ids_t, idxs in groups.items():
                    if takes_shared_path(ids_t, idxs):
                        sub = self._generate_shared(
                            [requests[i] for i in idxs], list(ids_t)
                        )
                        for i, result in zip(idxs, sub):
                            results[i] = result
                    else:
                        classic.extend(idxs)
                if classic:
                    sub = self._generate_classic(
                        [requests[i] for i in classic],
                        [token_lists[i] for i in classic],
                    )
                    for i, result in zip(classic, sub):
                        results[i] = result
                return results  # type: ignore[return-value]
        return self._generate_classic(requests, token_lists)

    def _prep_generation_rows(self, requests: Sequence[GenerationRequest], allowed: int):
        """Row bucketing + per-row sampling state shared by the classic and
        shared-trunk generate paths (they MUST stay in lockstep — a pad-row
        or eos-sentinel fix must hit both).

        Rows pad to a power-of-two bucket so XLA compiles a small, reused
        set of programs (decoders hand over varying candidate counts every
        step); dummy rows are never read.  The pad floor respects the HBM
        row allowance; dp-rounding keeps targets shardable.  The pinned-
        budget eos sentinel (-1: an id no tokenizer emits) disables the EOS
        early-exit in timing mode.
        """
        target = min(_bucket(len(requests), minimum=min(8, allowed)), allowed)
        if target % self._dp:  # dp > 8: pow-of-two buckets may undershoot
            target = min(-(-target // self._dp) * self._dp, allowed)
        pad_rows = target - len(requests)
        temperatures = jnp.asarray(
            [r.temperature for r in requests] + [1.0] * pad_rows, jnp.float32
        )
        # Repetition penalty: None (the overwhelmingly common case — no
        # paper config sets it) keeps the penalty-free decode programs; any
        # row >1 switches the batch to the presence-tracking variant.
        penalties = [getattr(r, "repetition_penalty", 1.0) for r in requests]
        rep_penalty = (
            jnp.asarray(penalties + [1.0] * pad_rows, jnp.float32)
            if any(abs(p - 1.0) > 1e-9 for p in penalties)
            else None
        )
        bias_table, bias_index = self._bias_table(requests)
        if bias_index is not None and pad_rows:
            bias_index = jnp.concatenate(
                [bias_index, jnp.zeros((pad_rows,), jnp.int32)]
            )
        keys = self._row_keys(
            "generate", [r.seed for r in requests] + [0] * pad_rows
        )
        eos_ids = (
            (-1,) if self.pin_generation_budget else self.tokenizer.eos_ids
        )
        return (target, pad_rows, temperatures, bias_table, bias_index,
                keys, eos_ids, rep_penalty)

    def _generate_shared(
        self, requests: Sequence[GenerationRequest], prompt_ids: List[int]
    ) -> List[GenerationResult]:
        """Decode all rows from ONE shared prompt trunk
        (models/generate.py:generate_tokens_shared_trunk)."""
        from consensus_tpu.models.generate import generate_tokens_shared_trunk

        max_new = _width_bucket(max(r.max_tokens for r in requests), minimum=16)
        # ONE trunk-width variant: the trunk is a single row, so padding its
        # prefill to max_context costs ~nothing — while letting its width
        # float over the {1,1.5}-pow2 ladder multiplies the compiled
        # program space by every ladder step a scenario's prompts touch.
        width = self.max_context
        prompt_ids = self._fit(prompt_ids, width)
        seg_len = self._seg_len_for(max_new)
        segmented = seg_len is not None
        # Tail-only per-row HBM (the trunk is one row, a closure constant):
        # rows are ~(ctx+2·max_new)/(2·max_new) times cheaper than classic.
        if segmented:
            allowed = self._segmented_rows_allowed(0, max_new, seg_len)
        else:
            allowed = self._generate_rows_allowed(0, max_new)
        if len(requests) > allowed:
            out: List[GenerationResult] = []
            for i in range(0, len(requests), allowed):
                out.extend(
                    self._generate_shared(requests[i : i + allowed], prompt_ids)
                )
            return out

        with span("backend.layout", rows=len(requests), width=width):
            self.call_counts["generate"] += len(requests)
            (target, pad_rows, temperatures, bias_table, bias_index, keys,
             eos_ids, rep_penalty) = self._prep_generation_rows(requests, allowed)
            self.instruments.record_padding(
                "generate_trunk", 1, width, len(prompt_ids)
            )
            self.instruments.record_launch(
                "generate_shared",
                (target, width, max_new, int(segmented), int(bias_table is not None)),
            )
            # The one trunk's prefill, then a step a token over trunk and tail.
            self._record_latent(1, width, width)
            self._record_latent(target, 1, width + max_new, steps=max_new)
            if self.config.has_ssm:  # one trunk's state, forked to every row
                self.instruments.record_state_fork(
                    "generate", target, target * self._recurrent_row_bytes())

            pad = self.tokenizer.pad_id
            tokens = np.full((1, width), pad, np.int32)
            valid = np.zeros((1, width), bool)
            tokens[0, width - len(prompt_ids):] = prompt_ids
            valid[0, width - len(prompt_ids):] = True

            # Bucket-pad rows start done (they'd otherwise sample real tokens
            # from the real prompt and pin the early exit at the full budget).
            init_done = np.zeros((target,), bool)
            init_done[len(requests):] = True
            kwargs = dict(
                max_new_tokens=max_new,
                temperature=temperatures,
                eos_ids=jnp.asarray(eos_ids, jnp.int32),
                bias_table=bias_table,
                bias_index=bias_index,
                pad_id=self.tokenizer.pad_id,
                init_done=jnp.asarray(init_done),
            )
            if rep_penalty is not None:
                kwargs["rep_penalty"] = rep_penalty
            if segmented:
                from consensus_tpu.models.generate import (
                    generate_tokens_shared_trunk_segmented as fn,
                )

                kwargs["seg_len"] = seg_len
                kwargs["dp_align"] = self._dp  # compaction keeps dp-divisible rows
                kwargs["kv_quant"] = self.kv_quant
            else:
                fn = generate_tokens_shared_trunk
        with span("backend.launch", program=fn.__name__):
            out = fn(
                self.params, self.config,
                jnp.asarray(tokens), jnp.asarray(valid), target, keys, **kwargs,
            )
        return self._finish_generation(requests, out, rows=target, max_new=max_new)

    def _generate_classic(
        self,
        requests: Sequence[GenerationRequest],
        token_lists: List[List[int]],
    ) -> List[GenerationResult]:
        # Classic-path batches keep the max_batch_rows activation bound:
        # the B-row prefill materializes per-layer (B, g, r, S, T) fp32
        # attention logits that the KV-only HBM allowance below does not
        # model (the generate() slice limit is wider only for the 1-row-
        # prefill shared-trunk path).
        if len(requests) > self.max_batch_rows:
            out: List[GenerationResult] = []
            for i in range(0, len(requests), self.max_batch_rows):
                out.extend(
                    self._generate_classic(
                        requests[i : i + self.max_batch_rows],
                        token_lists[i : i + self.max_batch_rows],
                    )
                )
            return out
        width = self._batch_width(token_lists)
        max_new = _width_bucket(max(r.max_tokens for r in requests), minimum=16)
        seg_len = self._seg_len_for(max_new)
        segmented = seg_len is not None
        if segmented:
            allowed = self._segmented_rows_allowed(width, max_new, seg_len)
        else:
            allowed = self._generate_rows_allowed(width, max_new)
        if len(requests) > allowed:
            # Long-generation batches re-chunk so the KV cache stays inside
            # the HBM budget (a 32-row x 2048-column cache double-buffered
            # is 13 GB — the habermas candidate phase OOM).  Token lists ride
            # along so chunks don't re-render/re-tokenize their prompts.
            out: List[GenerationResult] = []
            for i in range(0, len(requests), allowed):
                out.extend(
                    self._generate_classic(
                        requests[i : i + allowed],
                        token_lists[i : i + allowed],
                    )
                )
            return out

        with span("backend.layout", rows=len(requests), width=width):
            self.call_counts["generate"] += len(requests)
            (target, pad_rows, temperatures, bias_table, bias_index, keys,
             eos_ids, rep_penalty) = self._prep_generation_rows(requests, allowed)
            self.instruments.record_padding(
                "generate_prompt", target, width,
                sum(min(len(t), width) for t in token_lists),
            )
            self.instruments.record_launch(
                "generate",
                (target, width, max_new, int(segmented), int(bias_table is not None)),
            )
            self._record_latent(target, width, width)
            self._record_latent(target, 1, width + max_new, steps=max_new)
            token_lists = list(token_lists) + [[]] * pad_rows
            tokens, valid = self._left_pad_batch(token_lists)
            kwargs = dict(
                max_new_tokens=max_new,
                temperature=temperatures,
                eos_ids=jnp.asarray(eos_ids, jnp.int32),
                bias_table=bias_table,
                bias_index=bias_index,
                pad_id=self.tokenizer.pad_id,
            )
            if rep_penalty is not None:
                kwargs["rep_penalty"] = rep_penalty
            if segmented:
                from consensus_tpu.models.generate import (
                    generate_tokens_segmented as fn,
                )

                kwargs["seg_len"] = seg_len
                kwargs["dp_align"] = self._dp  # compaction keeps dp-divisible rows
                kwargs["kv_quant"] = self.kv_quant
            else:
                fn = generate_tokens
        with span("backend.launch", program=fn.__name__):
            out = fn(self.params, self.config, tokens, valid, keys, **kwargs)
        return self._finish_generation(requests, out, rows=target, max_new=max_new)

    def _record_moe(self, tally) -> None:
        """A program's tally of its routed layers (``transformer.MOE_TALLY``;
        None without routed experts) into the backend's counters."""
        if tally is None:
            return
        held, rows, passes, reached = (int(n) for n in np.asarray(tally))
        self.instruments.record_moe(
            held, rows * self.config.experts_per_token,
            passes * self.config.experts_held[1], reached)

    def _record_latent(
        self, rows: int, queries: int, keys: int, steps: int = 1
    ) -> None:
        """A launch of ``rows`` rows x ``queries`` query positions a row over
        ``keys`` gathered cache positions a row, ``steps`` times, into the
        latent layers' counters, from those shapes alone: the form is the
        one ``transformer.latent_form`` gives the program for them.  Nothing
        without latent layers."""
        if not self.config.has_latent:
            return
        from consensus_tpu.models.transformer import latent_form

        layers = steps * sum(
            n for name, n, _ in self.config.cache_kinds if name == "latent")
        form = latent_form(queries)
        self.instruments.record_mla(
            form, rows * queries * layers,
            rows * keys * layers if form == "expanded" else 0)

    def _finish_generation(
        self,
        requests: Sequence[GenerationRequest],
        out,
        rows: int,
        max_new: int,
    ) -> List[GenerationResult]:
        """Shared host-side post-processing: decode, EOS/stop semantics,
        token accounting."""
        generated, counts, hit_eos = self._fetch(
            out.tokens, out.num_generated, out.hit_eos
        )
        self._record_moe(out.moe_held)
        # Decode-grid padding efficiency from the tokens actually emitted:
        # EOS early exits and bucket-pad rows both show up as empty slots.
        self.instruments.record_padding(
            "generate_decode", rows, max_new, int(counts[: len(requests)].sum())
        )

        with span("backend.detokenize", rows=len(requests)):
            results = []
            for row, request in enumerate(requests):
                emitted = int(counts[row])
                ids = [int(t) for t in generated[row, :emitted]]
                ids = ids[: request.max_tokens]
                text = self.tokenizer.decode(ids)
                # "stop" only if EOS arrived within the request's OWN cap; an EOS
                # beyond max_tokens means the cap truncated the text ("length"),
                # even though the bucketed decode window saw an EOS later.
                finish = "stop" if (hit_eos[row] and emitted <= request.max_tokens) else "length"
                truncated = False
                if not self.pin_generation_budget:
                    for stop in request.stop:
                        idx = text.find(stop)
                        if idx >= 0:
                            text = text[:idx]
                            finish = "stop"
                            truncated = True
                if truncated:
                    # Keep token_ids consistent with the truncated text so token
                    # counts/ids downstream match what the caller sees.
                    ids = self.token_ids(text)
                self.token_counts["generated"] += len(ids)
                results.append(
                    GenerationResult(text=text, token_ids=tuple(ids), finish_reason=finish)
                )
        return results

    # -- score ---------------------------------------------------------------

    def _score_prefix(self, request: ScoreRequest) -> str:
        prefix = (
            f"{request.system_prompt}\n\n{request.context}"
            if request.system_prompt
            else request.context
        )
        if request.chat and request.role == "user":
            # Reference evaluation semantics (src/evaluation.py:182-193):
            # the eval template sits in the system slot and the statement
            # is scored INSIDE the user turn.
            parts = [p for p in (request.system_prompt, request.context) if p]
            prefix = self.tokenizer.user_turn_prefix("\n\n".join(parts) or None)
        elif request.chat:
            prefix = self.tokenizer.chat_prompt(request.context, request.system_prompt)
        return prefix

    def score(self, requests: Sequence[ScoreRequest]) -> List[ScoreResult]:
        """Teacher-forced scoring; requests sharing a context prefill it ONCE.

        best_of_n / evaluation score many candidates under the same agent
        context (reference best_of_n.py:266-321) — re-running the ~1k-token
        context forward per candidate is O(P·(C+L)).  With
        ``shared_context_scoring`` enabled, requests are grouped by rendered
        prefix; groups of >=4 that fit the window go through
        ``shared_context_token_logprobs`` (O(C + P·L), trunk broadcast).
        Default OFF: no end-to-end gain has been measured for it.
        """
        if not requests:
            return []
        if not self.shared_context_scoring:
            return self._sliced(requests, self._score_impl)
        prepared = []
        # A P-candidate group shares one identical ~1k-token context (the
        # workload this path dedupes): ``token_ids`` encodes it once, and
        # the group's rows share one list of its ids (ADVICE r2).
        prefix_ids: Dict[str, List[int]] = {}
        for request in requests:
            prefix = self._score_prefix(request)
            if prefix not in prefix_ids:
                prefix_ids[prefix] = self.token_ids(prefix, add_bos=True)
            prepared.append(
                (
                    prefix,
                    prefix_ids[prefix],
                    self.token_ids(request.continuation),
                )
            )
        by_prefix: Dict[str, List[int]] = {}
        for i, (prefix, _, _) in enumerate(prepared):
            by_prefix.setdefault(prefix, []).append(i)

        results: List[Optional[ScoreResult]] = [None] * len(requests)
        legacy: List[int] = []
        for prefix, idxs in by_prefix.items():
            ctx_ids = prepared[idxs[0]][1]
            conts = [prepared[i][2] for i in idxs]
            max_cont = max((len(c) for c in conts), default=0)
            # The suffix attention materializes per-layer fp32 logits of
            # (rows, heads, span, ctx+span) — unlike the classic path it has
            # no flash kernel, so bound that transient explicitly, and from
            # the widths _score_shared_group will actually ALLOCATE (pow2
            # continuation bucket, {1,1.5}-pow2 context bucket — up to ~2x
            # the unpadded sizes the guard previously used, ADVICE r2).
            # Chunk rows start at 4x max_batch_rows (suffix-only rows carry
            # no (B, S, S) transient — a co-batched cell's 256-candidate
            # group rides 2 dispatches instead of 8) and halve until the
            # transient fits.
            cont_width = self._shared_cont_width(max_cont)
            ctx_width = self.max_context  # matches _shared_prefill's padding
            rows_cap = max(self.max_batch_rows, 128)
            while rows_cap >= 8:
                attn_bytes = (
                    rows_cap * self.config.n_heads
                    * cont_width * (ctx_width + cont_width) * 4
                )
                if attn_bytes <= _SHARED_SCORE_ATTN_BYTES_CAP:
                    break
                rows_cap //= 2
            fits = (
                # >=4 rows: below that the single-row prefill + padded
                # suffix costs more than riding a wide legacy batch.
                len(idxs) >= 4
                and all(conts)
                and ctx_ids
                and len(ctx_ids) + max_cont <= self.max_context
                and attn_bytes <= _SHARED_SCORE_ATTN_BYTES_CAP
            )
            if not fits:
                legacy.extend(idxs)
                continue
            # Prefill the shared context ONCE for the whole group; every
            # row chunk scores against the same resident trunk (round 2
            # re-prefilled per 32-row chunk — VERDICT r2 #5).
            trunk_state = None
            for start in range(0, len(idxs), rows_cap):
                chunk = idxs[start : start + rows_cap]
                if len(chunk) < 4:  # sub-threshold tail: ride the wide batch
                    legacy.extend(chunk)
                    continue
                if trunk_state is None:
                    trunk_state = self._shared_prefill(ctx_ids)
                self._score_shared_group(trunk_state, chunk, prepared, results, rows_cap)
        if legacy:
            for start in range(0, len(legacy), self.max_batch_rows):
                chunk = legacy[start : start + self.max_batch_rows]
                chunk_results = self._score_impl(
                    [requests[i] for i in chunk],
                    prepared=[(prepared[i][1], prepared[i][2]) for i in chunk],
                )
                for i, result in zip(chunk, chunk_results):
                    results[i] = result
        return results  # type: ignore[return-value]

    def _shared_prefill(self, ctx_ids: List[int]):
        """Prefill one shared scoring context into a resident trunk.

        ONE width variant: the context is a single row, so padding to
        max_context is ~free, and the trunk's width is baked into every
        downstream suffix-scorer program shape — a floating width would
        multiply the compiled program space per scenario."""
        from consensus_tpu.models.transformer import shared_context_prefill

        ctx_width = self.max_context
        self.instruments.record_padding("score_trunk", 1, ctx_width, len(ctx_ids))
        self.instruments.record_launch("score_trunk", (1, ctx_width))
        self._record_latent(1, ctx_width, ctx_width)
        pad = self.tokenizer.pad_id
        ctx_tokens = np.full((1, ctx_width), pad, np.int32)
        ctx_tokens[0, : len(ctx_ids)] = ctx_ids
        ctx_valid = np.zeros((1, ctx_width), bool)
        ctx_valid[0, : len(ctx_ids)] = True
        return shared_context_prefill(
            self.params, self.config, jnp.asarray(ctx_tokens), jnp.asarray(ctx_valid)
        )

    def _score_shared_group(
        self,
        trunk_state,
        idxs: List[int],
        prepared,
        results,
        rows_cap: Optional[int] = None,
    ) -> None:
        from consensus_tpu.models.transformer import shared_context_cont_logprobs

        self.call_counts["score"] += len(idxs)
        conts = [prepared[i][2] for i in idxs]
        # Shape discipline: every program shape is a fresh compile, so the
        # variant space must stay SMALL: rows bucket on a coarse pow2
        # ladder from 32 up to rows_cap (a 5-candidate habermas group must
        # not pad 4x to a 128-row bucket), continuation width likewise.
        n_rows = min(
            rows_cap or max(self.max_batch_rows, 128),
            _bucket(len(idxs), minimum=32),
        )
        width = self._shared_cont_width(max(len(c) for c in conts))
        self.instruments.record_padding(
            "score_shared", n_rows, width, sum(len(c) for c in conts)
        )
        self.instruments.record_launch("score_shared", (n_rows, width))
        self._record_latent(n_rows, width - 1, self.max_context + width - 1)
        pad = self.tokenizer.pad_id
        cont_tokens = np.full((n_rows, width), pad, np.int32)
        cont_valid = np.zeros((n_rows, width), bool)
        for row, ids in enumerate(conts):
            cont_tokens[row, : len(ids)] = ids
            cont_valid[row, : len(ids)] = True
        cont_tokens_dev, cont_valid_dev = self._place_batch(cont_tokens, cont_valid)
        trunk, ctx_len, last_hidden = trunk_state
        logprobs = self._fetch(
            shared_context_cont_logprobs(
                self.params,
                self.config,
                trunk,
                ctx_len,
                last_hidden,
                cont_tokens_dev,
                cont_valid_dev,
            )
        )
        for row, i in enumerate(idxs):
            ids = conts[row]
            self.token_counts["scored"] += len(ids)
            results[i] = ScoreResult(
                tokens=tuple(self.tokenizer.token_str(t) for t in ids),
                logprobs=tuple(float(v) for v in logprobs[row, : len(ids)]),
            )

    def _score_impl(
        self,
        requests: Sequence[ScoreRequest],
        prepared: Optional[Sequence[Tuple[List[int], List[int]]]] = None,
    ) -> List[ScoreResult]:
        """Classic full-sequence batch scorer.  ``prepared`` carries
        already-encoded (context_ids, continuation_ids) so the shared-path
        router does not pay tokenization twice for its legacy fallbacks."""
        self.call_counts["score"] += len(requests)
        if not requests:
            return []

        rows = []
        spans = []  # (context_len, continuation_len) per row
        for i, request in enumerate(requests):
            if prepared is not None:
                context_ids, continuation_ids = prepared[i]
            else:
                context_ids = self.token_ids(
                    self._score_prefix(request), add_bos=True)
                continuation_ids = self.token_ids(request.continuation)
            rows.append(context_ids + continuation_ids)
            spans.append((len(context_ids), len(continuation_ids)))

        # Row bucketing (see _generate_impl): dummy all-pad rows are skipped
        # by the result loop below.
        rows += [[]] * (_bucket(len(rows), minimum=8) - len(rows))
        longest = min(max(len(r) for r in rows), self.max_context)
        width = min(_width_bucket(longest), self.max_context)
        pad = self.tokenizer.pad_id
        tokens = np.full((len(rows), width), pad, np.int32)
        valid = np.zeros((len(rows), width), bool)
        for i, ids in enumerate(rows):
            if len(ids) > width:
                # Drop the OLDEST context so the scored continuation (at the
                # end) survives; record how much context was cut.  If the cut
                # eats past the context into the continuation, shrink the
                # continuation span too so the returned logprobs cover only
                # the surviving continuation tokens.
                cut = len(ids) - width
                ids = self._fit(ids, width)
                ctx_len, cont_len = spans[i]
                new_ctx = max(ctx_len - cut, 0)
                new_cont = cont_len - max(cut - ctx_len, 0)
                if new_ctx == 0:
                    # Position 0 carries no conditioning — its token_logprobs
                    # slot is a padded 0.0, which would report probability 1
                    # for a real token.  Drop it from the scored span.
                    new_ctx, new_cont = 1, new_cont - 1
                spans[i] = (new_ctx, new_cont)
                if cut >= ctx_len:
                    logger.warning(
                        "score(): continuation truncated to %d tokens "
                        "(context window %d)", new_cont, width,
                    )
            tokens[i, : len(ids)] = ids  # RIGHT-padded for scoring
            valid[i, : len(ids)] = True

        scorer = (
            token_logprobs_streamed
            if self.config.vocab_size > _STREAMED_VOCAB_THRESHOLD
            else token_logprobs
        )
        self.instruments.record_padding(
            "score", len(rows), width,
            sum(min(len(r), width) for r in rows[: len(requests)]),
        )
        self.instruments.record_launch("score", (len(rows), width))
        self._record_latent(len(rows), width, width)
        tokens_dev, valid_dev = self._place_batch(tokens, valid)
        logprobs = self._fetch(
            scorer(self.params, self.config, tokens_dev, valid_dev)
        )

        results = []
        for i, (request, (ctx_len, cont_len)) in enumerate(zip(requests, spans)):
            end = min(ctx_len + cont_len, width)
            span_lp = logprobs[i, ctx_len:end]
            span_ids = tokens[i, ctx_len:end]
            self.token_counts["scored"] += len(span_lp)
            results.append(
                ScoreResult(
                    tokens=tuple(self.tokenizer.token_str(t) for t in span_ids),
                    logprobs=tuple(float(v) for v in span_lp),
                )
            )
        return results

    # -- fused (candidates x agents) utility matrix ---------------------------

    #: KV page width of the fused scoring pool.  Small pages keep the
    #: shared/private split fine-grained: everything up to the last full
    #: page of an agent context is shared read-only across all candidate
    #: rows; only the <=15-token tail plus the candidate re-runs per row.
    _SCORE_PAGE_SIZE = 16

    def score_matrix(self, requests) -> List:
        """Evaluate whole (candidates x agents) utility matrices on device.

        Each matrix runs as ONE logical program: per-agent context pages
        are prefilled once (deduped across agents sharing a rendered
        prefix) and shared READ-ONLY by every candidate row via block
        tables; the flattened candidate-major row batch is chunked under
        the live-session HBM budget and sharded over the dp mesh; per-row
        logprob reductions and the welfare fold happen on device
        (models/stepper.py: paged_score_chunk / utility_matrix).  Only the
        (C, A) utilities, the (C,) welfare vector, and the moments aux
        cross D2H — never a per-token logprob vector.  Requests whose
        rows would need the per-call scorer's truncation semantics fall
        back to it wholesale, keeping truncation behavior in one place.
        """
        from consensus_tpu.backends.score_matrix import (
            fallback_score_matrix_many,
            record_matrix,
            reduce_matrix,
        )

        out = []
        for request in requests:
            self.call_counts["score_matrix"] += 1
            self.matrix_stats["calls"] += 1
            if not request.candidates or not request.agents:
                out.append(reduce_matrix(request, [], path="fused"))
                continue
            result = self._score_matrix_fused(request)
            if result is None:  # needs per-call truncation semantics
                self.matrix_stats["fallbacks"] += 1
                result = fallback_score_matrix_many(self, [request])[0]
            else:
                record_matrix(
                    result,
                    len(request.agents),
                    welfare_rule=request.welfare_rule,
                )
            out.append(result)
        return out

    def _score_matrix_fused(self, request):
        from consensus_tpu.backends.score_matrix import ScoreMatrixResult
        from consensus_tpu.models.stepper import (
            make_page_state,
            paged_prefill_chunk,
            paged_score_chunk,
            utility_matrix,
        )

        ps = self._SCORE_PAGE_SIZE
        mesh = self.mesh_plan.mesh if self.mesh_plan is not None else None
        n_candidates = len(request.candidates)
        n_agents = len(request.agents)

        # Tokenize once per unique rendered agent prefix (agents routinely
        # share the issue framing) and once per candidate.
        with span("backend.tokenize", rows=n_candidates + n_agents) as tally:
            prefix_ids: Dict[str, List[int]] = {}
            agent_prefixes: List[str] = []
            for agent in request.agents:
                prefix = self._score_prefix(agent.to_score_request(""))
                if prefix not in prefix_ids:
                    prefix_ids[prefix] = self.token_ids(
                        prefix, add_bos=True, tally=tally)
                agent_prefixes.append(prefix)
            cont_ids = [
                self.token_ids(c, tally=tally) for c in request.candidates]
        max_cont = max(len(c) for c in cont_ids)
        if any(
            len(ids) + max_cont > self.max_context
            for ids in prefix_ids.values()
        ):
            return None  # per-call scorer owns truncation semantics

        with span("backend.layout", rows=n_candidates * n_agents):
            # Shared page layout: each unique context owns the pages below its
            # last full page boundary; the remaining 1..ps-token tail is
            # re-fed per row so the hidden state at the final context position
            # exists to teacher-force the first candidate token.
            shared: Dict[str, Tuple[int, int, int]] = {}  # prefix -> (first, npg, n0)
            next_page = 0
            for prefix, ids in prefix_ids.items():
                n0 = ((len(ids) - 1) // ps) * ps
                shared[prefix] = (next_page, n0 // ps, n0)
                next_page += n0 // ps
            shared_total = next_page

            # Flattened candidate-major rows; q block = context tail + all but
            # the last candidate token (targets are the NEXT stream token).
            rows = []  # (prefix, cont, q_len, n_private)
            max_q = 1
            max_private = 1
            max_blocks = 1
            for cont in cont_ids:
                for prefix in agent_prefixes:
                    ids = prefix_ids[prefix]
                    _, npg, n0 = shared[prefix]
                    q_len = (len(ids) - n0) + max(len(cont) - 1, 0)
                    n_private = (n0 + q_len - 1) // ps - n0 // ps + 1
                    rows.append((prefix, cont, q_len, n_private))
                    max_q = max(max_q, q_len)
                    max_private = max(max_private, n_private)
                    max_blocks = max(max_blocks, npg + n_private)
            max_blocks = self._table_blocks(max_blocks)

            # Chunk the row batch under the live-session HBM budget: pow2 row
            # buckets so the compiled-variant space stays small, halved until
            # the page pool (shared + per-row private + sink) fits.
            dtype = jnp.dtype(self.params["embed"].dtype)
            page_bytes = int(self.config.kv_bytes_per_token(dtype.itemsize)) * ps

            # With recurrent layers the pool holds, beside the pages, one
            # snapshot a unique context (at its page boundary) and every
            # row's fork of its context's, going into the row's layers and
            # coming out of them.
            state_bytes = self._recurrent_row_bytes()
            snapshot_rows = (
                _bucket(len(prefix_ids), minimum=8) if state_bytes else 0
            )

            def pool_pages(n_rows: int) -> int:
                pages = shared_total + n_rows * max_private
                if self.config.has_layer_kinds:
                    pages += -pages % _KINDS_POOL_STEP_PAGES
                return pages

            def pool_bytes(n_rows: int) -> int:
                return (
                    (pool_pages(n_rows) + 1) * page_bytes
                    + (snapshot_rows + 2 * n_rows) * state_bytes
                )

            width = _bucket(max_q, minimum=ps)

            def fits_beside_weights(n_rows: int) -> bool:
                """With recurrent layers or layers of more than one kind
                alone: the chunk's own temporaries
                (``_score_chunk_transient_bytes``) and its pool fit what the
                weights leave.  Such a model is large beside its cache, and
                the 3 GiB the budget leaves for temporaries is not what a
                wide chunk of it takes."""
                if not state_bytes and not self.config.has_layer_kinds:
                    return True
                return pool_bytes(n_rows) + self._score_chunk_transient_bytes(
                    n_rows, width, max_blocks * ps
                ) <= _HBM_BYTES - self._params_bytes

            total_rows = len(rows)
            chunk_rows = min(
                _bucket(total_rows, minimum=8),
                _bucket(max(self.max_batch_rows, 64), minimum=8),
            )
            budget = self._session_budget.cap
            while chunk_rows > 1 and (
                pool_bytes(chunk_rows) > budget
                or not fits_beside_weights(chunk_rows)
            ):
                chunk_rows //= 2
            if pool_bytes(chunk_rows) > budget:
                return None  # even one row over-commits; per-call path chunks finer
            chunk_rows = max(chunk_rows, self._dp)
            num_pages = pool_pages(chunk_rows)
            sink = num_pages

        nbytes = pool_bytes(chunk_rows)
        self._session_budget.acquire(nbytes)
        try:
            with span("backend.launch", program="make_page_state"):
                state = make_page_state(
                    self.config, num_pages, ps, dtype=dtype, mesh=mesh,
                    ssm_rows=snapshot_rows,
                )
            state = self._prefill_shared_pages(state, prefix_ids, shared, sink, mesh)
            chunk_stats = []
            for start in range(0, total_rows, chunk_rows):
                chunk = rows[start : start + chunk_rows]
                stats, state = self._score_matrix_chunk(
                    state, chunk, shared, prefix_ids, chunk_rows, width,
                    max_blocks, shared_total, max_private, sink, mesh,
                )
                chunk_stats.append(tuple(s[: len(chunk)] for s in stats))
                self.matrix_stats["chunks"] += 1
            with span("backend.launch", program="utility_matrix"):
                stats = tuple(
                    jnp.concatenate([cs[i] for cs in chunk_stats])
                    for i in range(4)
                )
                utilities, welfare_vals, aux = utility_matrix(
                    stats, n_candidates, n_agents,
                    stat=request.stat, rule=request.welfare_rule,
                    default=request.default,
                )
            fetched = self._fetch(
                *([utilities, welfare_vals] + ([aux] if aux is not None else []))
            )
            self._record_moe(state.moe_held)
        finally:
            self._session_budget.release(nbytes)
        utilities_np, welfare_np = fetched[0], fetched[1]
        aux_np = fetched[2] if aux is not None else None
        self.token_counts["scored"] += n_agents * sum(len(c) for c in cont_ids)
        d2h = utilities_np.nbytes + welfare_np.nbytes + (
            aux_np.nbytes if aux_np is not None else 0
        )
        return ScoreMatrixResult(
            utilities=utilities_np,
            welfare=welfare_np,
            best=int(np.argmax(welfare_np)) if welfare_np.size else 0,
            aux=aux_np,
            cells=n_candidates * n_agents,
            d2h_bytes=d2h,
            path="fused",
        )

    def _table_blocks(self, blocks: int) -> int:
        """Blocks a score matrix's tables name a row: the most a row needs,
        in steps with layers of more than one kind
        (``_KINDS_TABLE_STEP_BLOCKS``)."""
        if self.config.has_layer_kinds:
            blocks += -blocks % _KINDS_TABLE_STEP_BLOCKS
        return blocks

    def _score_chunk_transient_bytes(
        self, n_rows: int, width: int, keys: int
    ) -> int:
        """What a score chunk of ``n_rows`` x ``width`` over ``keys``
        gathered key positions holds at once, as the program is written.  One
        layer: the attention logits in float32 and their weights, the
        feed-forward's gate, up and product, and the mixer's input product,
        convolution, decay weights of a chunk, and the states at the chunks'
        boundaries going in and coming out.  And the head, which streams the
        vocabulary over all the chunk's positions: one float32 tile of
        ``stepper.score_vocab_tile`` columns, that tile's rows of the table
        and the table's rows at the targets.  (For 64 x 256 x 1600 at
        Falcon-H1's widths the TPU compiler's own count of the layer is
        6.5 GB where this gives 7.8; at 32 x 256 the head adds 0.26 GB
        here.)"""
        from consensus_tpu.models.stepper import score_vocab_tile

        c = self.config
        itemsize = jnp.dtype(self.params["embed"].dtype).itemsize
        cells = n_rows * width
        attention = cells * c.n_heads * keys * (4 + itemsize)
        ffn = cells * c.ffn_hidden * 3 * itemsize
        chunks = -(-width // c.ssm_chunk)
        layer_state = 4 * c.ssm_heads * c.ssm_head_dim * c.ssm_state
        mixer = (
            cells * (c.ssm_in_dim * itemsize + c.ssm_conv_dim * 8)
            + cells * min(width, c.ssm_chunk) * c.ssm_heads * 8
            + 2 * n_rows * chunks * layer_state
        )
        tile = min(score_vocab_tile(cells), c.vocab_size)
        head = cells * tile * 4 + (cells + tile) * c.d_model * itemsize
        if c.has_layer_kinds:
            # The keys and values gathered through the tables, at the kind
            # with the most heads; and a routed layer's block of rows: every
            # assignment's row gathered and returned, its gate, up and their
            # product, and the float32 rows the weighted sum reads.
            from consensus_tpu.models.transformer import (
                _MOE_BLOCK_ROWS,
                latent_form,
            )

            attention += n_rows * keys * itemsize * max(
                kv * sum(c.cache_widths(name)) for name, _, kv in c.cache_kinds)
            if c.has_latent and latent_form(width) == "expanded":
                attention += n_rows * keys * self._expanded_position_bytes()
            if c.swa_sink:  # the shares beside the sink's, float32 too
                attention += cells * c.n_heads * keys * 4
            if c.has_moe:
                sent = min(cells, _MOE_BLOCK_ROWS) * c.experts_per_token
                ffn = max(ffn, sent * (
                    (2 * c.d_model + 3 * c.expert_hidden) * itemsize
                    + 4 * c.d_model))
        return attention + ffn + mixer + head

    def _prefill_shared_pages(self, state, prefix_ids, shared, sink, mesh):
        """Ingest every unique agent context's full pages (one row per
        unique prefix, chunked along the sequence).  Rows padding the pow2
        batch bucket duplicate row 0 with writes routed to the sink."""
        from consensus_tpu.models.stepper import paged_prefill_chunk

        ps = self._SCORE_PAGE_SIZE
        pre = [p for p in prefix_ids if shared[p][1] > 0]
        if not pre:
            return state
        if self.config.has_ssm:
            # A row a context, in ``prefix_ids``' order: the rows of the
            # state table are the contexts' snapshots, which the score
            # chunk's ``ssm_rows`` index.  A context shorter than a page has
            # no column here and keeps zeros.
            pre = list(prefix_ids)
        n_rows = _bucket(len(pre), minimum=8)
        max_n0 = max(shared[p][2] for p in pre)
        chunk = min(256, _bucket(max_n0, minimum=ps))
        n_blocks = self._table_blocks(max(shared[p][1] for p in pre))
        n_pre = len(pre)
        placed_at = np.array([shared[p] for p in pre], np.int32)
        firsts, npgs, n0s = (placed_at[:, i : i + 1] for i in range(3))
        blocks = np.arange(n_blocks, dtype=np.int32)
        tables = np.full((n_rows, n_blocks), -1, np.int32)
        tables[:n_pre] = np.where(blocks < npgs, firsts + blocks, -1)
        tables[n_pre:] = tables[0]
        pad_id = self.tokenizer.pad_id
        for k in range(0, max_n0, chunk):
            with span("backend.layout", rows=n_rows, width=chunk):
                # Column j of the chunk is position k + j of every context:
                # live in a row while below the context's page boundary.
                pos = np.arange(k, k + chunk, dtype=np.int32)
                live = pos < n0s
                tokens = np.full((n_rows, chunk), pad_id, np.int32)
                valid = np.zeros((n_rows, chunk), bool)
                lengths = np.zeros((n_rows,), np.int32)
                write_pages = np.full((n_rows, chunk), sink, np.int32)
                write_offsets = np.zeros((n_rows, chunk), np.int32)
                valid[:n_pre] = live
                lengths[:n_pre] = np.minimum(n0s[:, 0], k + chunk)  # n0 at the end
                write_pages[:n_pre] = np.where(live, firsts + pos // ps, sink)
                write_offsets[:n_pre] = np.where(live, pos % ps, 0)
                for r, p in enumerate(pre):
                    piece = prefix_ids[p][k : lengths[r]]
                    tokens[r, : len(piece)] = piece
                # Pad rows ride row 0's shape (valid positions, table) but
                # write only to the sink — never a real page.
                tokens[n_pre:] = tokens[0]
                valid[n_pre:] = valid[0]
                lengths[n_pre:] = lengths[0]
                self.instruments.record_launch("score_matrix_prefill", (n_rows, chunk))
                self._record_latent(n_rows, chunk, n_blocks * ps)
            # lengths is rank-1: jit's in-program constraint shards it.
            placed = self._place_batch(
                tokens, valid, tables, write_pages, write_offsets
            )
            with span("backend.launch", program="paged_prefill_chunk"):
                _, state = paged_prefill_chunk(
                    self.params, self.config, placed[0], placed[1], state,
                    placed[2], jnp.asarray(lengths), placed[3], placed[4],
                    mesh=mesh,
                )
        return state

    def _score_matrix_chunk(
        self, state, chunk, shared, prefix_ids, n_rows, width,
        max_blocks, shared_total, max_private, sink, mesh,
    ):
        """One fused teacher-forced pass over a chunk of matrix rows."""
        from consensus_tpu.models.stepper import paged_score_chunk

        ps = self._SCORE_PAGE_SIZE
        pad_id = self.tokenizer.pad_id
        with span("backend.layout", rows=n_rows, width=width):
            # Row r of the chunk re-feeds its context's tail (from the page
            # boundary n0 on) and its candidate less the last token: column
            # j is position n0 + j of the row's stream, written into the
            # row's own pages and scored against the stream's next token.
            n_real = len(chunk)
            snapshot_of = {p: i for i, p in enumerate(prefix_ids)}
            plan = np.array(
                [(*shared[prefix], q_len, n_private)
                 for prefix, _, q_len, n_private in chunk], np.int32)
            firsts, npgs, n0s, q_lens, n_privates = (
                plan[:, i : i + 1] for i in range(5))
            bases = (shared_total + max_private
                     * np.arange(n_real, dtype=np.int32))[:, None]
            cols = np.arange(width, dtype=np.int32)
            live = cols < q_lens
            pos = n0s + cols
            blocks = np.arange(max_blocks, dtype=np.int32)

            tokens = np.full((n_rows, width), pad_id, np.int32)
            targets = np.zeros((n_rows, width), np.int32)
            score_mask = np.zeros((n_rows, width), bool)
            chunk_valid = np.zeros((n_rows, width), bool)
            tables = np.full((n_rows, max_blocks), -1, np.int32)
            lengths = np.zeros((n_rows,), np.int32)
            write_pages = np.full((n_rows, width), sink, np.int32)
            write_offsets = np.zeros((n_rows, width), np.int32)
            ssm_rows = np.zeros((n_rows,), np.int32)
            chunk_valid[:n_real] = live
            lengths[:n_real] = (n0s + q_lens)[:, 0]
            tables[:n_real] = np.where(
                blocks < npgs, firsts + blocks,
                np.where(blocks < npgs + n_privates, bases + blocks - npgs, -1))
            write_pages[:n_real] = np.where(
                live, bases + pos // ps - n0s // ps, sink)
            write_offsets[:n_real] = np.where(live, pos % ps, 0)
            for r, (prefix, cont, q_len, _) in enumerate(chunk):
                ids = prefix_ids[prefix]
                tail = ids[n0s[r, 0] :] + cont
                tokens[r, :q_len] = tail[:q_len]
                target = tail[1 : q_len + 1]  # a column short with no candidate
                targets[r, : len(target)] = target
                lo = len(ids) - 1 - n0s[r, 0]
                score_mask[r, lo : lo + len(cont)] = True
                ssm_rows[r] = snapshot_of[prefix]
            # Pad rows duplicate row 0 (well-defined positions/attention) but
            # write to the sink and score nothing.
            tokens[n_real:] = tokens[0]
            targets[n_real:] = targets[0]
            chunk_valid[n_real:] = chunk_valid[0]
            lengths[n_real:] = lengths[0]
            tables[n_real:] = tables[0]
            ssm_rows[n_real:] = ssm_rows[0]
            self.instruments.record_padding(
                "score_matrix", n_rows, width, int(q_lens.sum()))
            self.instruments.record_launch("score_matrix", (n_rows, width))
            self._record_latent(n_rows, width, int(tables.shape[1]) * ps)
            if self.config.has_ssm:  # each row starts from its context's state
                self.instruments.record_state_fork(
                    "score_matrix", n_real,
                    (state.ssm.h.shape[1] + n_rows) * self._recurrent_row_bytes())
        # lengths is rank-1: jit's in-program constraint shards it.
        placed = self._place_batch(
            tokens, targets, score_mask, chunk_valid, tables,
            write_pages, write_offsets,
        )
        with span("backend.launch", program="paged_score_chunk"):
            return paged_score_chunk(
                self.params, self.config, placed[0], placed[1], placed[2],
                placed[3], state, placed[4], jnp.asarray(lengths), placed[5],
                placed[6], mesh=mesh,
                ssm_rows=jnp.asarray(ssm_rows) if self.config.has_ssm else None,
            )

    # -- next-token distribution ----------------------------------------------

    def next_token_logprobs(
        self, requests: Sequence[NextTokenRequest]
    ) -> List[List[TokenCandidate]]:
        return self._sliced(requests, self._next_token_impl)

    def _next_token_impl(
        self, requests: Sequence[NextTokenRequest]
    ) -> List[List[TokenCandidate]]:
        self.call_counts["next_token"] += len(requests)
        if not requests:
            return []
        self.token_counts["scored"] += len(requests)

        token_lists = [
            self.token_ids(self._render_prompt(r), add_bos=True)
            for r in requests
        ]
        # Row bucketing (see _generate_impl): beam/MCTS candidate counts
        # vary per step; dummy rows keep compiled shapes stable.
        pad_rows = _bucket(len(requests), minimum=8) - len(requests)
        token_lists += [[]] * pad_rows
        tokens, valid = self._left_pad_batch(token_lists)

        bias_table, bias_index = self._bias_table(requests)
        if bias_index is not None and pad_rows:
            bias_index = jnp.concatenate(
                [bias_index, jnp.zeros((pad_rows,), jnp.int32)]
            )
        # k buckets too (widths vary little; candidates slice their own k).
        k = _bucket(
            max(min(r.k, self.config.vocab_size) for r in requests), minimum=4
        )
        k = min(k, self.config.vocab_size)
        temperatures = jnp.asarray(
            [r.temperature for r in requests] + [1.0] * pad_rows, jnp.float32
        )
        gumbel_rows = [
            r.mode != "topk" and r.temperature > 0 for r in requests
        ] + [False] * pad_rows
        if any(gumbel_rows):
            keys = self._row_keys(
                "next_token", [r.seed for r in requests] + [0] * pad_rows
            )
        else:
            # Pure-topk batches are deterministic: don't burn the unseeded
            # nonce (keeps unrelated unseeded generate() calls reproducible).
            keys = jnp.zeros((len(requests) + pad_rows, 2), jnp.uint32)
        width = int(tokens.shape[1])
        self.instruments.record_padding(
            "next_token", len(token_lists), width,
            sum(min(len(t), width) for t in token_lists[: len(requests)]),
        )
        self.instruments.record_launch(
            "next_token",
            (len(token_lists), width, k, int(bias_table is not None)),
        )
        self._record_latent(len(token_lists), width, width)
        # Device-side selection: only (B, k) ids+logprobs cross the wire
        # (VERDICT r1 #6) — never the (B, 256k) logit matrix.
        ids, logprobs = next_token_topk(
            self.params, self.config, tokens, valid, keys,
            k, temperatures, jnp.asarray(gumbel_rows, bool),
            bias_table, bias_index, with_gumbel=any(gumbel_rows),
        )
        ids, logprobs = self._fetch(ids, logprobs)

        out: List[List[TokenCandidate]] = []
        for row, request in enumerate(requests):
            # Take this request's k in score order (the without-replacement
            # sample), then present best-first by true logprob (reference
            # orders candidates by -logprob).
            row_k = min(request.k, self.config.vocab_size)
            pairs = sorted(
                zip(ids[row, :row_k], logprobs[row, :row_k]),
                key=lambda p: -p[1],
            )
            out.append(
                [
                    TokenCandidate(
                        token=self.tokenizer.token_str(int(t)),
                        token_id=int(t),
                        logprob=float(lp),
                    )
                    for t, lp in pairs
                ]
            )
        return out

    # -- token-search sessions -------------------------------------------------

    def open_fused_token_search(self, spec):
        """Incremental KV-cache search session (models/stepper.py): one fused
        device program per emitted token instead of re-running every prefix.
        Raises FusedSessionUnavailable when the persistent caches wouldn't
        fit alongside the weights (the session sizes its cache from the
        ACTUAL tokenized prefix width, so the check happens in its
        constructor, not on a pessimistic pre-tokenize bound) — the factory
        then builds the full-prefix fallback over the CALLING backend.

        A configuration with recurrent layers is refused by name, and not
        handed to the fallback: a search reorders and rolls back rows, and
        neither session keeps a state to gather or to restore."""
        if self.config.has_ssm:
            raise RecurrentStateUnsupported(
                "a token-search session (open_fused_token_search, "
                "search_prefill, search_step, the rollouts)",
                SEARCH_NEEDS_STATE,
            )
        if self.config.has_layer_kinds:
            raise LayerKindsUnsupported(
                "a token-search session (open_fused_token_search, "
                "search_prefill, search_step, the rollouts)",
                NEEDS_ONE_KIND,
            )
        return TPUTokenSearchSession(self, spec)

    # -- embeddings ------------------------------------------------------------

    def embed(self, texts: Sequence[str]) -> np.ndarray:
        limit = self._embed_rows_allowed(texts)
        pieces = [
            self._embed_impl(texts[i : i + limit], rows=limit)
            for i in range(0, len(texts), limit)
        ] or [np.zeros((0, self.config.d_model), np.float32)]
        return np.vstack(pieces)

    def _expanded_position_bytes(self) -> int:
        """What the expanded form of latent attention holds for one gathered
        position: every head's ``c W_kvb`` as it is made, then its keys with
        the rotary key beside them and its values (at 4,096 keys 84 MB a row
        to keep, 151 MB while they are made)."""
        c = self.config
        itemsize = jnp.dtype(self.params["embed"].dtype).itemsize
        return itemsize * c.n_heads * (
            c.qk_nope_dim + c.value_dim + c.head_dim + c.value_dim)

    def _dense_attention_bytes(self, rows: int, width: int, keys: int) -> int:
        """What the einsum attention of one layer holds at once for ``rows``
        x ``width`` queries over ``keys`` keys: float32 logits and their
        weights in the activations' type, for the query heads it takes at a
        time.  That is all of them, but with layers of more than one kind:
        ``forward`` then attends a key-value group at a time, and the widest
        group counts; with latent attention in the expanded form a head at a
        time, beside the keys and values made of the latents."""
        c = self.config
        itemsize = jnp.dtype(self.params["embed"].dtype).itemsize
        heads = c.n_heads
        if c.has_latent:
            from consensus_tpu.models.transformer import latent_form

            if latent_form(width) == "expanded":
                # A head is its own key-value group, and every head's keys
                # and values of the rows' latents stand beside its logits.
                return rows * keys * (
                    width * (4 + itemsize) + self._expanded_position_bytes())
            # Absorbed: one key-value head, every query head over it at once.
            return rows * width * heads * keys * (4 + itemsize)
        if c.has_layer_kinds:
            heads = c.n_heads // min(kv for _, _, kv in c.cache_kinds)
        return rows * width * heads * keys * (4 + itemsize)

    def _embed_rows_allowed(self, texts: Sequence[str]) -> int:
        """Rows one embedding batch takes.  ``max_batch_rows``, as ever, but
        with layers of more than one kind: their many query heads make the
        batch's attention logits the larger part of what the weights leave
        (32 rows x 1,536 of 64 heads: 19 GB), so the rows halve, down the
        powers of two, until the logits fit the reserve for temporaries."""
        if not self.config.has_layer_kinds or not texts:
            return self.max_batch_rows
        width = _width_bucket(
            min(max(len(t.encode("utf-8")) for t in texts) + 1, self.max_context))
        rows = _bucket(min(len(texts), self.max_batch_rows), minimum=1)
        while rows > 1 and self._dense_attention_bytes(
                rows, width, width) > _ACTIVATION_RESERVE_BYTES:
            rows //= 2
        return rows

    def _embed_impl(
        self, texts: Sequence[str], rows: Optional[int] = None
    ) -> np.ndarray:
        self.call_counts["embed"] += len(texts)
        with span("backend.tokenize", rows=len(texts)) as tally:
            token_lists = [
                self.token_ids(t, add_bos=True, tally=tally) for t in texts]
        with span("backend.layout", rows=len(texts)):
            # (A batch held to fewer than 8 rows is padded to those rows.)
            pad_rows = _bucket(
                len(texts), minimum=min(8, rows or 8)) - len(texts)
            token_lists += [[]] * pad_rows
            tokens, valid = self._left_pad_batch(token_lists)
            width = int(tokens.shape[1])
            self.instruments.record_padding(
                "embed", len(token_lists), width,
                sum(min(len(t), width) for t in token_lists[: len(texts)]),
            )
            self.instruments.record_launch("embed", (len(token_lists), width))
            self._record_latent(len(token_lists), width, width)
        with span("backend.launch", program="_embed_forward"):
            pooled = _embed_forward(self.params, self.config, tokens, valid)
        hidden = self._fetch(pooled)[: len(texts)]
        norms = np.linalg.norm(hidden, axis=1, keepdims=True)
        return hidden / np.maximum(norms, 1e-12)


@functools.partial(jax.jit, static_argnames=("base_seed",))
def _fold_keys(base_seed: int, folds):
    """``fold_in(PRNGKey(base_seed), fold)`` for every fold, as (rows, 2)
    ``uint32``: one program for all of a call's rows."""
    return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        jax.random.PRNGKey(base_seed), folds)


@functools.partial(jax.jit, static_argnames=("config",))
def _embed_forward(params, config: ModelConfig, tokens, valid):
    """Masked mean-pool of final hidden states -> (B, D) float32."""
    positions = jnp.maximum(jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1, 0)
    hidden, _ = forward(params, config, tokens, positions, valid, return_hidden=True)
    mask = valid[..., None].astype(jnp.float32)
    pooled = (hidden.astype(jnp.float32) * mask).sum(1) / jnp.maximum(
        mask.sum(1), 1.0
    )
    return pooled


#: Page size of the multi-token decode stream's private pool.  16 keeps the
#: per-cohort page count fine-grained enough that short requests don't
#: strand KV while staying a multiple of common TPU sublane tiles.
_STREAM_PAGE_SIZE = 16
#: Fixed prefill chunk width — ONE prefill program per (rows, pages) shape
#: instead of one per prompt-length bucket.
_STREAM_PREFILL_CHUNK = 64


@functools.partial(jax.jit, static_argnames=("config",))
def _stream_logits(params, config: ModelConfig, hidden):
    from consensus_tpu.models.transformer import project_logits

    return project_logits(params, config, hidden)


class _PagedGenerateStream:
    """One generate cohort served as K-step decode windows.

    Construction prefills every prompt into a PRIVATE page pool (contiguous
    block tables, fixed-width chunks) and projects the first sampling
    logits.  After that the protocol is the engine's stream seam:

    - ``dispatch()`` enqueues ONE ``paged_decode_steps`` window and returns
      without fetching anything — under jax async dispatch the host gets
      control back while the device runs, so the engine overlaps its
      sweep/admit/prefill phases with decode.
    - ``collect()`` fetches the pending window's small host-facing arrays
      (tokens / emitted / done / hit_eos), extends per-row ids, and returns
      ``(per_row_token_counts, {row: GenerationResult})`` for rows that
      froze inside the window, finalized with the exact
      ``_finish_generation`` semantics (max_tokens truncation, stop
      strings, token accounting).
    - ``finished`` / ``close()`` manage drain and teardown.

    Sampling state (keys, budgets, presence) comes from the SAME
    ``_prep_generation_rows`` the dense paths use, and the in-scan sampler
    replays the sequential key-split schedule — so emitted tokens match the
    dense paths for any ``decode_steps``, up to paged-vs-dense forward
    numerics.  Rows/pages/blocks are bucketed so cohort shape variety maps
    to a small reused program set.
    """

    def __init__(
        self,
        backend: "TPUBackend",
        requests: List[GenerationRequest],
        decode_steps: int,
        speculative: bool = False,
    ):
        from consensus_tpu.models import stepper
        from consensus_tpu.models.generate import _prompt_presence

        self._stepper = stepper
        be = backend
        self.backend = be
        self.requests = requests
        self.decode_steps = max(1, int(decode_steps))
        self.speculative = bool(speculative)
        self._mesh = be.mesh_plan.mesh if be.mesh_plan is not None else None
        self._pending = None
        self._closed = False
        self._finished_rows: set = set()
        self._results: Dict[int, GenerationResult] = {}

        be.call_counts["generate"] += len(requests)
        tok = be.tokenizer
        prompt_ids = [
            be._fit(be.token_ids(be._render_prompt(r), add_bos=True),
                    be.max_context)
            for r in requests
        ]
        (target, pad_rows, temperatures, bias_table, bias_index, keys,
         eos_ids, rep_penalty) = be._prep_generation_rows(
            requests, allowed=_bucket(len(requests), minimum=8)
        )
        self._n_rows = len(requests)
        self._ids: List[List[int]] = [[] for _ in requests]

        # Contiguous block tables over a bucketed private pool: each row
        # reserves ceil((prompt + max_tokens) / page) pages AT DISPATCH TIME
        # — every page the in-scan cursor can reach exists before the first
        # window runs.  The eos-check token never needs one (sink).
        ps = _STREAM_PAGE_SIZE
        pages_per = [
            -(-(len(ids) + r.max_tokens) // ps)
            for ids, r in zip(prompt_ids, requests)
        ] + [1] * pad_rows
        max_blocks = _bucket(max(pages_per), minimum=8)
        num_pages = min(
            _width_bucket(sum(pages_per), minimum=16),
            target * max_blocks,
        )
        tables = np.full((target, max_blocks), -1, np.int32)
        off = 0
        for row, n in enumerate(pages_per):
            tables[row, :n] = np.arange(off, off + n)
            off += n
        be.instruments.record_launch(
            "generate_stream",
            (target, num_pages, max_blocks, self.decode_steps),
        )

        state = stepper.make_page_state(
            be.config, num_pages, ps,
            dtype=jnp.dtype(be.params["embed"].dtype), mesh=self._mesh,
        )
        sink = num_pages
        tables_j = jnp.asarray(tables)

        # Fixed-width chunked prefill; per-row final-prompt hidden is
        # accumulated with a last-chunk mask so ragged prompts share the
        # same program.
        chunk = _STREAM_PREFILL_CHUNK
        maxlen = max(len(ids) for ids in prompt_ids)
        lengths = np.zeros(target, np.int32)
        final_hidden = None
        for start in range(0, maxlen, chunk):
            ctok = np.zeros((target, chunk), np.int32)
            cval = np.zeros((target, chunk), bool)
            wp = np.full((target, chunk), sink, np.int32)
            wo = np.zeros((target, chunk), np.int32)
            is_last = np.zeros(target, bool)
            for row, ids in enumerate(prompt_ids):
                piece = ids[start : start + chunk]
                if not piece:
                    continue
                ctok[row, : len(piece)] = piece
                cval[row, : len(piece)] = True
                pos = start + np.arange(len(piece))
                wp[row, : len(piece)] = tables[row, pos // ps]
                wo[row, : len(piece)] = pos % ps
                lengths[row] = start + len(piece)
                is_last[row] = start + len(piece) >= len(ids)
            hid, state = stepper.paged_prefill_chunk(
                be.params, be.config, *be._place_batch(ctok, cval), state,
                tables_j, jnp.asarray(lengths),
                *be._place_batch(wp, wo), mesh=self._mesh,
            )
            mask = jnp.asarray(is_last)[:, None]
            final_hidden = (
                jnp.where(mask, hid, final_hidden)
                if final_hidden is not None
                else hid
            )
        be.instruments.record_padding(
            "generate_trunk", target, -(-maxlen // chunk) * chunk,
            int(sum(len(ids) for ids in prompt_ids)),
        )

        self._logits = _stream_logits(
            be.params, be.config, final_hidden.astype(jnp.float32)
        )
        self._state = state
        self._tables = tables_j
        self._lengths = jnp.asarray(lengths)
        self._keys = keys
        # Bucket-pad rows start done with budget 0: they sample pad ids into
        # the sink forever and never show up in collect().
        row_pad = np.zeros(target, bool)
        row_pad[len(requests) :] = True
        self._done = jnp.asarray(row_pad)
        self._budgets = jnp.asarray(
            [r.max_tokens for r in requests] + [0] * pad_rows, jnp.int32
        )
        self._hit_eos = jnp.zeros(target, bool)
        self._temperatures = temperatures
        self._eos_ids = jnp.asarray(eos_ids, jnp.int32)
        self._bias_table = bias_table
        self._bias_index = bias_index
        self._rep_penalty = rep_penalty
        if rep_penalty is not None:
            width = max(maxlen, 1)
            ptok = np.full((target, width), tok.pad_id, np.int32)
            pval = np.zeros((target, width), bool)
            for row, ids in enumerate(prompt_ids):
                ptok[row, width - len(ids) :] = ids
                pval[row, width - len(ids) :] = True
            self._presence = _prompt_presence(
                jnp.asarray(ptok), jnp.asarray(pval), be.config.vocab_size
            )
        else:
            self._presence = None

        #: Cumulative draft accounting the engine reads after collect().
        self.spec_proposed = 0
        self.spec_accepted = 0
        if self.speculative:
            from consensus_tpu.backends.speculative import NGramProposer

            # One n-gram self-draft table per row, seeded from the row's
            # OWN prompt; emitted tokens feed it at collect() — the
            # lookup-decoding seam generate traffic was missing.
            self._proposers = [NGramProposer() for _ in requests]
            self._ctx: List[List[int]] = []
            for proposer, ids in zip(self._proposers, prompt_ids):
                proposer.observe(ids)
                self._ctx.append(list(ids))
            self._target = target
            self._pending_tok = jnp.zeros(target, jnp.int32)
            self._has_pending = False
            reg = be.instruments.registry
            self._obs_spec_proposed = reg.counter(
                "spec_draft_proposed_tokens_total",
                "Draft tokens proposed for speculative rollout verification",
                ("backend",),
            ).labels(be.name)
            self._obs_spec_verified = reg.counter(
                "spec_draft_verified_tokens_total",
                "Draft tokens accepted by the parallel verify pass",
                ("backend",),
            ).labels(be.name)

    @property
    def finished(self) -> bool:
        return self._closed or len(self._finished_rows) >= self._n_rows

    def dispatch(self) -> None:
        """Enqueue one K-step window.  Returns without fetching — the
        device arrays stay in flight until ``collect()``."""
        if self._closed or self._pending is not None or self.finished:
            return
        if self.speculative:
            self._dispatch_verify()
            return
        (tokens, emitted, self._logits, self._state, self._lengths,
         self._keys, self._done, self._budgets, self._hit_eos,
         self._presence) = self._stepper.paged_decode_steps(
            self.backend.params, self.backend.config, self._logits,
            self._state, self._tables, self._lengths, self._keys,
            self._done, self._budgets, self._hit_eos,
            temperature=self._temperatures, eos_ids=self._eos_ids,
            num_steps=self.decode_steps,
            bias_table=self._bias_table, bias_index=self._bias_index,
            pad_id=self.backend.tokenizer.pad_id,
            presence=self._presence, rep_penalty=self._rep_penalty,
            mesh=self._mesh,
        )
        self._pending = (tokens, emitted, None, self._done, self._hit_eos)

    def _dispatch_verify(self) -> None:
        """Speculative window: draft K tokens per live row on the host,
        verify them in ONE ``paged_verify_steps`` dispatch.  The drafts
        ride the same async-dispatch seam — drafting happens between
        collect() and dispatch(), so the double-buffer overlap of the
        plain stream is preserved."""
        k = self.decode_steps
        drafts = np.zeros((self._target, k), np.int32)
        live = 0
        for row in range(self._n_rows):
            if row in self._finished_rows:
                continue
            drafts[row] = self._proposers[row].draft(self._ctx[row], k)
            live += 1
        self.spec_proposed += live * k
        self._obs_spec_proposed.inc(live * k)
        (tokens, emitted, accepted, self._pending_tok, self._state,
         self._lengths, self._keys, self._done, self._budgets,
         self._hit_eos, self._presence) = self._stepper.paged_verify_steps(
            self.backend.params, self.backend.config, self._logits,
            self._state, self._tables, self._lengths, self._keys,
            self._done, self._budgets, self._hit_eos,
            temperature=self._temperatures,
            draft_tokens=jnp.asarray(drafts), pending=self._pending_tok,
            eos_ids=self._eos_ids, num_steps=k,
            bias_table=self._bias_table, bias_index=self._bias_index,
            pad_id=self.backend.tokenizer.pad_id,
            presence=self._presence, rep_penalty=self._rep_penalty,
            has_pending=self._has_pending, mesh=self._mesh,
        )
        # The carried prefill logits are consumed by the FIRST window;
        # every later first-decision sample re-derives its logits from the
        # pending column's hidden on device.
        self._logits = None
        self._has_pending = True
        self._pending = (tokens, emitted, accepted, self._done,
                         self._hit_eos)

    def collect(self) -> Tuple[List[int], Dict[int, GenerationResult]]:
        """Block on the pending window; return (per-row emitted counts,
        {row: result}) for rows that froze inside it."""
        if self._pending is None:
            raise RuntimeError("collect() before dispatch()")
        be = self.backend
        tokens, emitted, accepted = self._pending[:3]
        if accepted is None:
            tokens, emitted, done, hit = be._fetch(*self._pending[:2],
                                                   *self._pending[3:])
        else:
            tokens, emitted, accepted, done, hit = be._fetch(*self._pending)
        self._pending = None
        row_tokens = [0] * self._n_rows
        newly_finished: Dict[int, GenerationResult] = {}
        for row in range(self._n_rows):
            if row in self._finished_rows:
                continue
            ids = [int(t) for t, e in zip(tokens[row], emitted[row]) if e]
            self._ids[row].extend(ids)
            row_tokens[row] = len(ids)
            if accepted is not None and ids:
                self._proposers[row].observe(ids)
                self._ctx[row].extend(ids)
            if bool(done[row]):
                self._finished_rows.add(row)
                result = self._finish_row(row, bool(hit[row]))
                self._results[row] = result
                newly_finished[row] = result
        if accepted is not None:
            window_accepted = int(
                sum(int(accepted[row]) for row in range(self._n_rows))
            )
            self.spec_accepted += window_accepted
            self._obs_spec_verified.inc(window_accepted)
        if self.finished:
            be.instruments.record_padding(
                "generate_decode", self._n_rows,
                max((r.max_tokens for r in self.requests), default=0),
                sum(len(ids) for ids in self._ids),
            )
        return row_tokens, newly_finished

    def _finish_row(self, row: int, hit_eos: bool) -> GenerationResult:
        """Per-row ``_finish_generation``: same truncation, stop-string,
        finish-reason, and token-accounting semantics."""
        be = self.backend
        request = self.requests[row]
        emitted = len(self._ids[row])
        ids = self._ids[row][: request.max_tokens]
        text = be.tokenizer.decode(ids)
        finish = (
            "stop" if (hit_eos and emitted <= request.max_tokens) else "length"
        )
        truncated = False
        if not be.pin_generation_budget:
            for stop in request.stop:
                idx = text.find(stop)
                if idx >= 0:
                    text = text[:idx]
                    finish = "stop"
                    truncated = True
        if truncated:
            ids = be.token_ids(text)
        be.token_counts["generated"] += len(ids)
        return GenerationResult(
            text=text, token_ids=tuple(ids), finish_reason=finish
        )

    def results(self) -> List[GenerationResult]:
        """All results in request order (valid once ``finished``)."""
        return [self._results[row] for row in range(self._n_rows)]

    def close(self) -> None:
        self._closed = True
        self._pending = None
        self._state = None
        self._logits = None
        if self.speculative:
            self._pending_tok = None


class TPUTokenSearchSession:
    """Incremental token search over persistent per-(slot x role) KV caches.

    Rows are beam-major: slot b occupies rows [b*(1+A), (b+1)*(1+A)) with
    role 0 = reference policy and roles 1..A = agent policies.  Each
    ``advance_and_propose`` is ONE fused device call (models/stepper.py):
    gather surviving parents' cache rows, append the chosen token id,
    forward one position, Gumbel-top-k the reference rows, and gather the
    proposal ids from the agents' log-softmax — O(T) total model work where
    the full-prefix data flow is O(T^2).

    State is token *ids* (the true token-level-MDP state); the decoded
    strings in returned candidates are for host-side semantics (EOS sets,
    dedup, display).
    """

    def __init__(self, backend: "TPUBackend", spec):
        self.backend = backend
        self.spec = spec
        tok = backend.tokenizer
        prefixes = [tok.raw_prompt(spec.ref_user, spec.ref_system)] + [
            tok.raw_prompt(a_user, a_system)
            for a_system, a_user in spec.agent_prompts
        ]
        token_lists = [backend.token_ids(p, add_bos=True) for p in prefixes]
        max_prefix = backend.max_context - spec.max_steps
        if max_prefix < 16:
            # A negative/zero budget would flip the slice below into keeping
            # the WRONG end (and silently lose the generation-slot reserve).
            raise ValueError(
                f"max_steps={spec.max_steps} leaves no prefix room inside "
                f"max_context={backend.max_context}"
            )
        token_lists = [backend._fit(ids, max_prefix) for ids in token_lists]
        self._tokens, self._valid = backend._left_pad_batch(token_lists)
        self._w0 = int(self._tokens.shape[1])
        self.n_roles = len(prefixes)
        c = backend.config
        n_rows = spec.n_slots * self.n_roles
        itemsize = jnp.dtype(backend.params["embed"].dtype).itemsize
        # Trunk once per role + per-(slot x role) tails — the prefix is
        # SHARED, never replicated per slot (models/stepper.py).  Per-chip
        # bytes (caches shard with the weights under tensor parallelism).
        # Trunk sessions (n_slots=1) reserve 2x: every tree expansion and
        # rollout materializes one transient trunk+tail scratch copy
        # (stepper._scratch_cache).
        cache_bytes = (
            2 * c.n_layers
            * (self.n_roles * self._w0 + n_rows * spec.max_steps)
            * c.n_kv_heads * c.head_dim * itemsize
        ) // backend._shard_count
        if spec.n_slots == 1:
            cache_bytes *= 2
        # Compare against the backend's LIVE budget (HBM minus weights and
        # activation reserve) — a session bigger than the whole budget would
        # otherwise block in acquire() forever.
        if cache_bytes > backend._session_budget.cap:
            from consensus_tpu.backends.session import FusedSessionUnavailable

            logger.warning(
                "fused session unavailable: %d-row x %d-wide cache "
                "(~%.1f GB) over the %.1f GB session budget",
                n_rows, self._w0 + spec.max_steps, cache_bytes / 1e9,
                backend._session_budget.cap / 1e9,
            )
            raise FusedSessionUnavailable(
                f"{n_rows}-row x {self._w0 + spec.max_steps}-wide session "
                f"cache (~{cache_bytes / 1e9:.1f} GB) over budget"
            )
        # Reserve HBM for the lifetime of the session (blocks while other
        # threads' sessions hold the budget); close() releases it.  The
        # reservation is recorded only AFTER acquire succeeds: an exception
        # inside a blocked acquire must not let __del__ release bytes that
        # were never granted.
        backend._session_budget.acquire(cache_bytes)
        self._budget_bytes = cache_bytes
        self._step = 0
        self._state = None
        #: Fused device programs launched by this session (each one is one
        #: host->device round trip).  Decoders read the delta per statement
        #: for the obs dispatch counters.
        self.dispatch_count = 0
        bias = backend._bias_vector(spec.bias_against_tokens, spec.bias_value)
        self._ref_bias = jnp.asarray(bias) if bias is not None else None
        # One base key per session; per-(step, slot) keys fold in-device so a
        # step ships no key material.  Unseeded sessions draw a fresh nonce
        # (each session serves exactly one statement).
        if spec.seed is None:
            with backend._nonce_lock:
                backend._unseeded_calls += 1
                nonce = backend._unseeded_calls
            self._base_key = backend._fold_seed(
                "search", "unseeded", nonce
            )
        else:
            self._base_key = backend._fold_seed("search", spec.seed)
        self._temperature = jnp.asarray(spec.temperature, jnp.float32)
        #: Speculative rollout verification (backends/speculative.py +
        #: models/stepper.rollout_verify_many): an n-gram self-draft
        #: proposer seeded from the reference prompt + trunk advances.
        self._proposer = None
        if getattr(spec, "speculative", False):
            from consensus_tpu.backends.speculative import NGramProposer

            self._proposer = NGramProposer()
            self._proposer.observe(token_lists[0])
            #: Trunk token ids (ref-role prompt + advances) — the drafting
            #: context every rollout continues from.
            self._trunk_ids = list(token_lists[0])
            reg = backend.instruments.registry
            label = backend.name
            self._obs_spec_proposed = reg.counter(
                "spec_draft_proposed_tokens_total",
                "Draft tokens proposed for speculative rollout verification",
                ("backend",),
            ).labels(label)
            self._obs_spec_verified = reg.counter(
                "spec_draft_verified_tokens_total",
                "Draft tokens accepted by the parallel verify pass",
                ("backend",),
            ).labels(label)

    # -- protocol ------------------------------------------------------------

    def propose(self) -> List[List["ScoredCandidate"]]:
        from consensus_tpu.models.stepper import search_prefill

        self._check_open()
        spec = self.spec
        # k candidates x (n_roles - 1) agent evaluations per slot.
        self.backend.token_counts["scored"] += (
            spec.n_slots * spec.k * (self.n_roles - 1)
        )
        self.dispatch_count += 1
        out = search_prefill(
            self.backend.params, self.backend.config,
            self._tokens, self._valid,
            spec.n_slots, self.n_roles,
            self._base_key, self._temperature,
            spec.k, spec.sample, spec.max_steps,
            ref_bias=self._ref_bias,
        )
        return self._finish(out)

    def advance_and_propose(
        self, parents: Sequence[int], chosen: Sequence
    ) -> List[List["ScoredCandidate"]]:
        from consensus_tpu.models.stepper import search_step

        self._check_open()
        spec = self.spec
        if len(parents) != spec.n_slots or len(chosen) != spec.n_slots:
            raise ValueError(
                f"expected {spec.n_slots} (parent, token) pairs, got "
                f"{len(parents)}/{len(chosen)}"
            )
        if self._step >= spec.max_steps:
            raise ValueError(f"session exhausted its {spec.max_steps} steps")
        self._step += 1
        self.backend.token_counts["generated"] += spec.n_slots
        self.backend.token_counts["scored"] += (
            spec.n_slots * spec.k * (self.n_roles - 1)
        )
        # One packed H2D array and one packed D2H fetch per step: a
        # transfer per scalar would put several host<->device round trips
        # into every step of the search.
        advance = np.stack(
            [
                np.asarray(list(parents), np.int32),
                np.asarray([c.token_id for c in chosen], np.int32),
            ]
        )
        step_meta = np.asarray([self._step, self._step - 1], np.int32)
        if self._proposer is not None:
            self._proposer.observe([c.token_id for c in chosen])
            self._trunk_ids.extend(c.token_id for c in chosen)
        self.dispatch_count += 1
        out = search_step(
            self.backend.params, self.backend.config,
            self._state,
            jnp.asarray(advance), jnp.asarray(step_meta),
            spec.n_slots, self.n_roles,
            self._base_key, self._temperature,
            spec.k, spec.sample,
            ref_bias=self._ref_bias,
        )
        return self._finish(out)

    def propose_suffixes(
        self, suffixes: Sequence[Sequence], salt: int
    ) -> List[List["ScoredCandidate"]]:
        """Propose + score k candidates for each tree path (a suffix of
        candidates hanging off the trunk), sharing the trunk cache across
        all paths (models/stepper.py:suffix_propose).  Trunk sessions only
        (n_slots == 1); the trunk itself advances via advance_and_propose."""
        self._check_open()
        spec = self.spec
        if spec.n_slots != 1:
            raise ValueError("propose_suffixes requires an n_slots=1 session")
        if self._state is None:
            raise ValueError("call propose() before propose_suffixes()")
        if not suffixes:
            return []
        if any(len(s) == 0 for s in suffixes):
            raise ValueError("suffixes must be non-empty")
        # The fused kernel wants one uniform suffix length per call (the
        # shared-prefill shapes are static) — mixed-length callers (wave
        # MCTS selects leaves at different depths) are grouped by span,
        # one device call per distinct span, results re-ordered.
        groups: Dict[int, List[int]] = {}
        for i, suffix in enumerate(suffixes):
            groups.setdefault(len(suffix), []).append(i)
        multi = len(groups) > 1
        results: List[Optional[List["ScoredCandidate"]]] = [None] * len(suffixes)
        for span, idxs in groups.items():
            # Single-span calls keep the caller's salt verbatim (the only
            # historically legal shape — existing PRNG streams must not
            # move).  With several spans, each group folds its span into
            # the salt so no two groups replay identical per-row keys.
            group_salt = (salt ^ (span << 20)) if multi else salt
            rows = self._propose_suffix_group(
                [suffixes[i] for i in idxs], span, group_salt
            )
            for i, row in zip(idxs, rows):
                results[i] = row
        return results

    def _propose_suffix_group(
        self, suffixes: Sequence[Sequence], span: int, salt: int
    ) -> List[List["ScoredCandidate"]]:
        """One fused suffix_propose call over equal-length suffixes."""
        from consensus_tpu.models.stepper import suffix_propose

        spec = self.spec
        # Pad the path count to a bucket (repeating row 0) so XLA reuses a
        # small set of compiled (P, L) shapes across tree levels.
        # Each path re-evaluates its span under every agent and proposes k
        # scored candidates.
        self.backend.token_counts["scored"] += (
            len(suffixes) * (span + spec.k) * (self.n_roles - 1)
        )
        n_paths = _bucket(len(suffixes), minimum=4)
        tokens = np.zeros((n_paths, span), np.int32)
        for i, suffix in enumerate(suffixes):
            tokens[i] = [c.token_id for c in suffix]
        tokens[len(suffixes):] = tokens[0]

        self.dispatch_count += 1
        packed = np.asarray(
            suffix_propose(
                self.backend.params, self.backend.config,
                self._state, jnp.asarray(self._step, jnp.int32),
                jnp.asarray(tokens), jnp.asarray(salt, jnp.int32),
                self.n_roles, self._base_key, self._temperature,
                spec.k, spec.sample,
                ref_bias=self._ref_bias,
            )
        )[: len(suffixes)]
        return self._unpack(packed)

    def rollout_from(
        self, suffix: Sequence, depth: int, salt: int
    ) -> Tuple[List[int], str, List[float], bool]:
        """Continue ``depth`` reference-policy tokens past trunk+suffix and
        return (rollout token ids, rollout text, per-agent total logprob of
        the rollout tokens, ok) — the MCTS rollout + evaluation as ONE
        device call (models/stepper.py:rollout_scored).  Trunk sessions
        only.  The ids are authoritative (arbitrary sampled bytes need not
        survive a decode/encode round trip); the text is for display."""
        from consensus_tpu.models.stepper import rollout_scored

        self._check_open()
        spec = self.spec
        if spec.n_slots != 1:
            raise ValueError("rollout_from requires an n_slots=1 session")
        if self._state is None:
            raise ValueError("call propose() before rollout_from()")
        if not suffix:
            raise ValueError("rollout_from needs a non-empty suffix")
        self.dispatch_count += 1
        rows = np.asarray(
            rollout_scored(
                self.backend.params, self.backend.config,
                self._state, jnp.asarray(self._step, jnp.int32),
                jnp.asarray([c.token_id for c in suffix], jnp.int32),
                jnp.asarray(salt, jnp.int32),
                self.n_roles, len(suffix), depth,
                self._base_key, self._temperature,
                jnp.asarray(self.backend.tokenizer.eos_ids, jnp.int32),
            )
        )  # (depth, 2 + A)
        return self._rollout_result(rows, depth)

    def rollout_many(
        self, suffixes: Sequence[Sequence], depth: int, salts: Sequence[int]
    ) -> List[Tuple[List[int], str, List[float], bool]]:
        """Batched :meth:`rollout_from` over a wave of tree paths.  Paths
        are grouped by suffix length (the fused kernel's shared-prefill
        shapes are static per span); a singleton group delegates to
        ``rollout_from`` — bit-identical to the sequential path — while a
        multi-path group runs ONE ``rollout_scored_many`` program per HBM
        chunk (the wave width is capped by :meth:`_rollout_chunk_cap` so
        the per-(path x role) decode tails stay inside the session's
        reservation slack)."""
        from consensus_tpu.models.stepper import rollout_scored_many

        self._check_open()
        spec = self.spec
        if spec.n_slots != 1:
            raise ValueError("rollout_many requires an n_slots=1 session")
        if self._state is None:
            raise ValueError("call propose() before rollout_many()")
        if len(salts) != len(suffixes):
            raise ValueError(
                f"expected {len(suffixes)} salts, got {len(salts)}"
            )
        if not suffixes:
            return []
        if any(not s for s in suffixes):
            raise ValueError("rollout_many needs non-empty suffixes")
        if self._proposer is not None:
            return self._rollout_many_spec(suffixes, depth, salts)
        groups: Dict[int, List[int]] = {}
        for i, suffix in enumerate(suffixes):
            groups.setdefault(len(suffix), []).append(i)
        results: List[Optional[Tuple[List[int], str, List[float], bool]]] = (
            [None] * len(suffixes)
        )
        for span, idxs in groups.items():
            cap = self._rollout_chunk_cap(span, depth)
            for lo in range(0, len(idxs), cap):
                chunk = idxs[lo : lo + cap]
                if len(chunk) == 1:
                    i = chunk[0]
                    results[i] = self.rollout_from(
                        suffixes[i], depth, salts[i]
                    )
                    continue
                # Bucket the path count (padding repeats row 0 with its own
                # salt — identical compute, sliced away) for shape reuse.
                n_paths = _bucket(len(chunk), minimum=2)
                tokens = np.zeros((n_paths, span), np.int32)
                salt_arr = np.zeros((n_paths,), np.int32)
                for j, i in enumerate(chunk):
                    tokens[j] = [c.token_id for c in suffixes[i]]
                    salt_arr[j] = salts[i]
                tokens[len(chunk):] = tokens[0]
                salt_arr[len(chunk):] = salt_arr[0]
                self.dispatch_count += 1
                rows = np.asarray(
                    rollout_scored_many(
                        self.backend.params, self.backend.config,
                        self._state, jnp.asarray(self._step, jnp.int32),
                        jnp.asarray(tokens), jnp.asarray(salt_arr),
                        self.n_roles, span, depth,
                        self._base_key, self._temperature,
                        jnp.asarray(
                            self.backend.tokenizer.eos_ids, jnp.int32
                        ),
                    )
                )  # (n_paths, depth, 2 + A)
                for j, i in enumerate(chunk):
                    results[i] = self._rollout_result(rows[j], depth)
        return results

    def _rollout_many_spec(
        self, suffixes: Sequence[Sequence], depth: int, salts: Sequence[int]
    ) -> List[Tuple[List[int], str, List[float], bool]]:
        """Speculative rollout_many: draft each path's whole remaining
        rollout from the n-gram proposer and verify it in ONE parallel
        ``rollout_verify_many`` forward per round (all active paths ride
        the same dispatch).  Each round accepts every path's longest
        draft-matched prefix plus the first corrected token — standard
        rejection, so accepted token streams replay the sequential scan
        exactly, with agent totals agreeing to float tolerance (pinned in
        tests/test_speculative.py) — and a perfect draft finishes a
        depth-``d`` rollout in one round instead of ``d`` sequential
        decode steps."""
        from consensus_tpu.models.stepper import rollout_verify_many

        spec = self.spec
        results: List[Optional[Tuple[List[int], str, List[float], bool]]] = (
            [None] * len(suffixes)
        )
        groups: Dict[int, List[int]] = {}
        for i, suffix in enumerate(suffixes):
            groups.setdefault(len(suffix), []).append(i)
        n_agents = self.n_roles - 1
        for span, idxs in groups.items():
            cap = max(1, self._rollout_chunk_cap(span, depth))
            for lo in range(0, len(idxs), cap):
                chunk = idxs[lo : lo + cap]
                #: Per path: accepted rows [(token, counted, lps...)], and
                #: whether an EOS ended the counted stream.
                emitted: Dict[int, List[List[float]]] = {i: [] for i in chunk}
                finished: Dict[int, bool] = {i: False for i in chunk}
                contexts = {
                    i: self._trunk_ids + [c.token_id for c in suffixes[i]]
                    for i in chunk
                }
                while True:
                    active = [
                        i for i in chunk
                        if not finished[i] and len(emitted[i]) < depth
                    ]
                    if not active:
                        break
                    drafts: Dict[int, List[int]] = {}
                    for i in active:
                        accepted = [int(r[0]) for r in emitted[i]]
                        fresh = self._proposer.draft(
                            contexts[i] + accepted, depth - len(accepted)
                        )
                        self._obs_spec_proposed.inc(len(fresh))
                        drafts[i] = accepted + fresh
                    n_paths = _bucket(len(active), minimum=2)
                    tokens = np.zeros((n_paths, span), np.int32)
                    draft_arr = np.zeros((n_paths, depth), np.int32)
                    salt_arr = np.zeros((n_paths,), np.int32)
                    for j, i in enumerate(active):
                        tokens[j] = [c.token_id for c in suffixes[i]]
                        draft_arr[j] = drafts[i]
                        salt_arr[j] = salts[i]
                    tokens[len(active):] = tokens[0]
                    draft_arr[len(active):] = draft_arr[0]
                    salt_arr[len(active):] = salt_arr[0]
                    self.dispatch_count += 1
                    rows = np.asarray(
                        rollout_verify_many(
                            self.backend.params, self.backend.config,
                            self._state, jnp.asarray(self._step, jnp.int32),
                            jnp.asarray(tokens), jnp.asarray(draft_arr),
                            jnp.asarray(salt_arr),
                            self.n_roles, span, depth,
                            self._base_key, self._temperature,
                            jnp.asarray(
                                self.backend.tokenizer.eos_ids, jnp.int32
                            ),
                        )
                    )  # (n_paths, depth, 2 + A)
                    for j, i in enumerate(active):
                        t = len(emitted[i])
                        while t < depth:
                            chosen = int(rows[j, t, 0])
                            is_eos = rows[j, t, 1] > 0.5
                            counted = 0.0 if is_eos else 1.0
                            emitted[i].append(
                                [float(chosen), counted]
                                + [
                                    float(v) * counted
                                    for v in rows[j, t, 2:]
                                ]
                            )
                            matched = chosen == int(drafts[i][t])
                            if matched:
                                self._obs_spec_verified.inc()
                            t += 1
                            if is_eos:
                                # Post-EOS tokens are uncounted in the
                                # sequential scan and filtered from the
                                # result — stop generating them at all.
                                finished[i] = True
                                break
                            if not matched:
                                # chosen is the valid correction; rows past
                                # it were conditioned on the wrong draft.
                                break
                for i in chunk:
                    out = np.zeros((depth, 2 + n_agents), np.float32)
                    if emitted[i]:
                        got = np.asarray(emitted[i], np.float32)
                        out[: got.shape[0]] = got
                    results[i] = self._rollout_result(out, depth)
        return results

    def _rollout_chunk_cap(self, span: int, depth: int) -> int:
        """How many wave paths one rollout_scored_many call may carry: each
        path adds a (n_layers x n_roles x (span + depth)) decode tail on
        top of the scratch trunk copy, and the session's 2x reservation
        (constructor) only pre-books the scratch — cap the tails at 1/8 of
        the reservation so a wide wave degrades into chunks instead of
        blowing the budget."""
        c = self.backend.config
        itemsize = jnp.dtype(self.backend.params["embed"].dtype).itemsize
        per_path = (
            2 * c.n_layers * self.n_roles * (span + depth)
            * c.n_kv_heads * c.head_dim * itemsize
        ) // self.backend._shard_count
        allowance = self._budget_bytes // 8
        return max(1, int(allowance // max(per_path, 1)))

    def _rollout_result(
        self, rows: np.ndarray, depth: int
    ) -> Tuple[List[int], str, List[float], bool]:
        """Unpack one path's (depth, 2 + A) rollout rows + token accounting."""
        counted = rows[:, 1] > 0.5
        tok = self.backend.tokenizer
        ids = [int(rows[t, 0]) for t in range(depth) if counted[t]]
        self.backend.token_counts["generated"] += len(ids)
        self.backend.token_counts["scored"] += len(ids) * (self.n_roles - 1)
        text = "".join(tok.token_str(i) for i in ids)
        totals = [float(v) for v in rows[counted, 2:].sum(axis=0)]
        return ids, text, totals, True

    def close(self) -> None:
        """Drop the device caches and release the session's HBM reservation.
        Idempotent; also runs at garbage collection as a safety net."""
        # getattr: the constructor may raise before the reservation exists,
        # and __del__ still runs.
        if getattr(self, "_budget_bytes", 0):
            self._state = None
            self.backend._session_budget.release(self._budget_bytes)
            self._budget_bytes = 0

    def __del__(self):
        self.close()

    # -- internals -----------------------------------------------------------

    def _check_open(self) -> None:
        if not getattr(self, "_budget_bytes", 0):
            raise ValueError("session is closed")

    def _finish(self, out) -> List[List["ScoredCandidate"]]:
        self._state = out.state
        return self._unpack(np.asarray(out.packed))

    def _unpack(self, packed: np.ndarray) -> List[List["ScoredCandidate"]]:
        from consensus_tpu.backends.session import ScoredCandidate

        tok = self.backend.tokenizer
        results = []
        for row in range(packed.shape[0]):
            row_out = []
            for j in range(self.spec.k):
                token_id = int(packed[row, j, 0])
                row_out.append(
                    ScoredCandidate(
                        token=tok.token_str(token_id),
                        token_id=token_id,
                        ref_logprob=float(packed[row, j, 1]),
                        agent_logprobs=tuple(
                            float(v) for v in packed[row, j, 2:]
                        ),
                    )
                )
            results.append(row_out)
        return results
