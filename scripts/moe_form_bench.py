"""Microbench: a routed layer's experts as the masked product over every held
expert, against the grouped products that read only the experts a row
reached (``transformer.grouped_tiling``'s tile), at a decode step's rows.

One routed layer at each cell's published widths (no shared expert: it is
the same in every arm): JoyAI-LLM-Flash (256 held of 256, 2,048 -> 768) and
MiMo-V2-Flash (16 held of 256, 4,096 -> 2,048); 1, 8, 32, 64, 128 and 256
rows.  Arms: ``masked`` (PR 35's decode form: every held expert on every row
under the rows' weights, kept here to measure it), ``grouped`` (the rule's
tile), and the grouped product at the span's tile (``span``: what lowering
the masked form's threshold alone would give).
A launch runs the layer ``REPEATS_IN`` times in a chain (each pass reads the
experts again: 2.4 GB does not stay in any cache), so the host's dispatch
is spread thin; the median of five launches after the compile, over
``REPEATS_IN``, is the time of a layer.

Usage: PYTHONPATH=. python scripts/moe_form_bench.py [out.json]   (on the chip;
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
from consensus_tpu.models.config import get_model_config

ROWS = (1, 8, 32, 64, 128, 256)
REPEATS = 5
REPEATS_IN = 8
#: (name, d_model, expert width, experts held, n_experts): the published widths.
LAYERS = (("joyai", 2048, 768, 256, 256), ("mimo", 4096, 2048, 16, 256))
TOY = (("joyai", 64, 32, 8, 8), ("mimo", 64, 32, 4, 16))
#: The few-rows tile of each arm: the rule's own, or 0 for the span's tile.
ARMS = {"grouped": None, "span": 0}


def masked_block(c, lp, x):
    """PR 35's decode form (``_experts_masked``, deleted in PR 37): every held
    expert's gated product on every row, summed under the row's weights."""
    first, count = c.experts_held
    stacks, layer = lp["experts"]
    t = tf.rms_norm(x, lp["ffn_norm"], c.rms_eps, c.rmsnorm_style)
    chosen, weights = tf.route(c, lp, t)
    local = chosen - first
    held = (local >= 0) & (local < count)
    weights = weights * (c.routed_scaling_factor or 1.0)
    gate_w, up_w, down_w = (tf.layer_of(stacks[leaf], layer)
                            for leaf in tf.EXPERT_LEAVES)
    sent = held[:, :, None] & (local[:, :, None] == jnp.arange(count)[None, None, :])
    share = jnp.sum(jnp.where(sent, weights[:, :, None], 0.0), axis=1)
    gate = jax.nn.silu(jnp.einsum("nd,edf->enf", t, gate_w))
    up = jnp.einsum("nd,edf->enf", t, up_w)
    out = jnp.einsum("enf,efd->end", gate * up, down_w)
    return x + jnp.einsum("ne,end->nd", share, out.astype(jnp.float32)).astype(
        t.dtype)


def layer(name, d, f, held, experts):
    config = dataclasses.replace(
        get_model_config("tiny-joyai-flash"), d_model=d, expert_hidden=f,
        n_experts=experts, experts_per_token=8, experts_held=(0, held),
        n_shared_experts=0, routed_scaling_factor=2.5)
    keys = jax.random.split(jax.random.PRNGKey(d + held), 5)

    @jax.jit
    def make():
        def draw(key, shape, fan_in):
            return (jax.random.normal(key, shape, jnp.float32)
                    / fan_in ** 0.5).astype(jnp.bfloat16)
        stacks = {
            "experts_gate": draw(keys[0], (1, held, d, f), d),
            "experts_up": draw(keys[1], (1, held, d, f), d),
            "experts_down": draw(keys[2], (1, held, f, d), f),
        }
        return {"ffn_norm": jnp.ones((d,), jnp.bfloat16),
                "router": jax.random.normal(keys[3], (d, experts), jnp.float32) / d ** 0.5,
                "router_bias": jnp.zeros((experts,), jnp.float32),
                "experts": (stacks, 0)}

    return config, make()


def timed(run):
    jax.block_until_ready(run())
    seconds = []
    for _ in range(REPEATS):
        start = time.perf_counter()
        jax.block_until_ready(run())
        seconds.append(time.perf_counter() - start)
    return sorted(seconds)[len(seconds) // 2] / REPEATS_IN


def chained(block, c, lp, x):
    """A launch of ``REPEATS_IN`` passes; the weights are an argument (closed
    over, they would be the program's constants: 2.4 GB in each one)."""
    def passes(p, y):
        def body(_, y):
            out = block(c, p, y)
            return out[0] if isinstance(out, tuple) else out
        return jax.lax.fori_loop(0, REPEATS_IN, body, y)
    run = jax.jit(passes)
    return lambda: run(lp, x)


def main(out_path=None, rehearse=False):
    report = {"device": jax.devices()[0].device_kind, "repeats_in": REPEATS_IN,
              "ms": {}, "reached": {}, "tiling": {}}
    rule = tf._FEW_ROWS_TILE
    for name, d, f, held, experts in (TOY if rehearse else LAYERS):
        c, lp = layer(name, d, f, held, experts)
        for rows in ROWS:
            x = jax.random.normal(jax.random.PRNGKey(rows), (rows, d)).astype(
                jnp.bfloat16)
            key = f"{name}/{rows}"
            times = report["ms"][key] = {}
            times["masked"] = 1e3 * timed(chained(masked_block, c, lp, x))
            _, tally = jax.jit(lambda p, y: tf.moe_block(c, p, y))(lp, x)
            report["reached"][key] = int(tally[3]) / held
            m, run = rows * 8, rows * 8 / experts
            report["tiling"][key] = [tf.grouped_tiling(m, d, f, run),
                                     tf.grouped_tiling(m, f, d, run)]
            for arm, tile in ARMS.items():
                tf._FEW_ROWS_TILE = rule if tile is None else tile
                try:
                    times[arm] = 1e3 * timed(chained(tf.moe_block, c, lp, x))
                except Exception as error:  # a tile Mosaic refuses
                    times[arm] = f"{type(error).__name__}: {str(error)[:160]}"
                finally:
                    tf._FEW_ROWS_TILE = rule
            print(key, json.dumps(times), "reached", report["reached"][key],
                  flush=True)
    print(json.dumps(report))
    if out_path:
        with open(out_path, "w") as handle:
            json.dump(report, handle, indent=1)


if __name__ == "__main__":
    main(*[arg for arg in sys.argv[1:] if not arg.startswith("--")][:1],
         rehearse="--rehearse" in sys.argv)
