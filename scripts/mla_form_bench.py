"""Microbench: the two forms of latent attention (MLA), program by program.

``transformer.latent_form`` picks the absorbed form for one query a row and
the expanded form for a span.  This script times each of the serving path's
programs in both forms at the new cell's shapes, on ONE latent layer at the
published widths (the leading dense layer: no experts, so the difference
between the arms is latent attention's; 2,048 paged keys, a 4,096-wide trunk,
8 x 1,024 embedded), by moving the rule's threshold
(``transformer._MLA_ABSORBED_QUERIES``) between the arms:

- the score chunk: 16 rows x 256 queries over 2,048 paged keys
- the paged prefill: 8 rows x 256 over 2,048 paged keys
- the trunk's prefill: 1 row x 4,096 into a dense cache
- the embedder: 8 rows x 1,024, no cache
- a decode step: 32 rows, one query each, over a 4,096-wide trunk and a
  64-column tail (``generate_tokens_shared_trunk``, 64 steps)

Usage: PYTHONPATH=. python scripts/mla_form_bench.py [out.json]   (on the chip;
``--rehearse`` runs the same arms at a toy size anywhere, to find faults)
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp

from consensus_tpu.models import transformer as tf
from consensus_tpu.models.config import ModelConfig
from consensus_tpu.models.generate import generate_tokens_shared_trunk
from consensus_tpu.models.stepper import (
    make_page_state,
    paged_prefill_chunk,
    paged_score_chunk,
)

#: One latent layer with a dense feed-forward at JoyAI-LLM-Flash's widths.
CONFIG = ModelConfig(
    name="mla-form-bench", vocab_size=129280, d_model=2048, n_layers=1,
    n_heads=32, n_kv_heads=32, head_dim=192, ffn_hidden=7168,
    activation="swiglu", rope_theta=32e6, rms_eps=1e-6, scale_embeddings=False,
    tie_lm_head=False, use_post_norms=False, rmsnorm_style="llama",
    hybrid_layer_pattern=(0,), moe_layer_freq=(0,), v_head_dim=128,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
    rope_interleave=True)
#: The same layer at a toy size, with toy shapes: ``--rehearse``.
TOY = dataclasses.replace(
    CONFIG, vocab_size=320, d_model=64, n_heads=4, n_kv_heads=4, head_dim=24,
    ffn_hidden=128, v_head_dim=16, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_dim=16, qk_rope_dim=8)
PAGE = 16
REPEATS = 5


def timed(run):
    """Median seconds of ``REPEATS`` runs after one that compiles."""
    jax.block_until_ready(run())
    seconds = []
    for _ in range(REPEATS):
        start = time.perf_counter()
        jax.block_until_ready(run())
        seconds.append(time.perf_counter() - start)
    return sorted(seconds)[len(seconds) // 2]


def paged_arm(program, config, params, rows, width, keys):
    blocks = keys // PAGE
    tokens = jnp.ones((rows, width), jnp.int32)
    valid = jnp.ones((rows, width), bool)
    # Every row reads the same context pages and writes pages of its own.
    own = width // PAGE
    shared = blocks - own
    tables = jnp.concatenate([
        jnp.broadcast_to(jnp.arange(shared, dtype=jnp.int32), (rows, shared)),
        shared + jnp.arange(rows * own, dtype=jnp.int32).reshape(rows, own)], axis=1)
    lengths = jnp.full((rows,), keys, jnp.int32)
    at = (keys - width) + jnp.arange(width, dtype=jnp.int32)[None, :]
    write_pages = jnp.take_along_axis(tables, at // PAGE, axis=1)
    write_offsets = jnp.broadcast_to(at % PAGE, (rows, width))
    pages = shared + rows * own

    def run():
        state = make_page_state(config, pages, PAGE, jnp.bfloat16)
        if program is paged_score_chunk:
            return paged_score_chunk(
                params, config, tokens, tokens, valid, valid, state, tables,
                lengths, write_pages, write_offsets)[0]
        return paged_prefill_chunk(
            params, config, tokens, valid, state, tables, lengths, write_pages,
            write_offsets)[0]

    return run


def main(out_path=None, rehearse=False):
    # Keys of the paged arms, the trunk's width, rows x queries of the embedder.
    config, keys, trunk_width, embedded = (
        (TOY, 512, 256, (2, 64)) if rehearse else (CONFIG, 2048, 4096, (8, 1024)))
    params = jax.jit(tf.init_params, static_argnums=(0, 2))(
        config, jax.random.PRNGKey(0), jnp.bfloat16)
    forward = jax.jit(tf.forward, static_argnames=("config", "return_hidden"))

    def trunk():
        tokens = jnp.ones((1, trunk_width), jnp.int32)
        cache = tf.make_cache(config, 1, trunk_width, jnp.bfloat16)
        return forward(params, config, tokens, jnp.arange(trunk_width)[None],
                       jnp.ones((1, trunk_width), bool), cache, 0, return_hidden=True)[0]

    def embed():
        tokens = jnp.ones(embedded, jnp.int32)
        return forward(params, config, tokens,
                       jnp.broadcast_to(jnp.arange(embedded[1]), embedded),
                       jnp.ones(embedded, bool), return_hidden=True)[0]

    def generate():
        return generate_tokens_shared_trunk(
            params, config, jnp.ones((1, trunk_width), jnp.int32),
            jnp.ones((1, trunk_width), bool), 32, jnp.zeros((32, 2), jnp.uint32),
            max_new_tokens=64, temperature=jnp.ones((32,)),
            eos_ids=jnp.asarray([-1], jnp.int32)).tokens

    arms = {
        "score_chunk_16x256": paged_arm(paged_score_chunk, config, params, 16, 256, keys),
        "paged_prefill_8x256": paged_arm(paged_prefill_chunk, config, params, 8, 256, keys),
        "trunk_prefill": trunk,
        "embed": embed,
        "generate_32rows_64steps": generate,
    }
    report = {"device": jax.devices()[0].device_kind, "layers": config.n_layers,
              "paged_keys": keys, "trunk": trunk_width, "embed": list(embedded),
              "seconds": {}}
    rule = tf._MLA_ABSORBED_QUERIES
    for form, threshold in (("absorbed", 1 << 30), ("expanded", 0)):
        tf._MLA_ABSORBED_QUERIES = threshold
        jax.clear_caches()
        for name, run in arms.items():
            report["seconds"].setdefault(name, {})[form] = timed(run)
            print(name, form, report["seconds"][name][form], flush=True)
    tf._MLA_ABSORBED_QUERIES = rule
    # The generation arm's prefill is in the arm's own form; a step's cost is
    # the arm less the trunk's prefill in that form, over 64 steps.
    for form in ("absorbed", "expanded"):
        whole = report["seconds"]["generate_32rows_64steps"][form]
        report["seconds"].setdefault("decode_step_32rows", {})[form] = (
            whole - report["seconds"]["trunk_prefill"][form]) / 64
    report["rule"] = {name: tf.latent_form(queries) for name, queries in (
        ("score_chunk", 256), ("paged_prefill", 256), ("trunk_prefill", 4096),
        ("embed", 1024), ("decode_step", 1))}
    print(json.dumps(report))
    if out_path:
        with open(out_path, "w") as handle:
            json.dump(report, handle, indent=1)


if __name__ == "__main__":
    main(*[arg for arg in sys.argv[1:] if not arg.startswith("--")][:1],
         rehearse="--rehearse" in sys.argv)
