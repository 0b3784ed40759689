"""What every plain reference shares, whatever the architecture.

A reference imports nothing of ``consensus_tpu`` and takes nothing the
program made: it renders and tokenizes its own prompts (here: a copy of the
byte tokenizer's rules), draws its own weights from the seed (``seed_key``;
``weights_checksum`` holds the served tree to them leaf by leaf), and runs
its own float32 forward over each compared row.  The forward, the weights'
draws and the keys of a ``model`` block are the architecture's: they are a
file of their own, ``benchmark/references/<name>.py``, named by the
configuration file's ``"reference"`` (``dense`` where it names none).  This
module keeps the rest: the rows' way through a forward block by block
(``score_by_width``), the control's rounding (``fp8``) and ``Scored``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# -- tokenizer and prompt rendering (the serving tokenizer's rules) ------------

SPECIAL_TOKENS = (
    "<pad>", "<bos>", "<eos>", "<|eot_id|>", "<|end_of_text|>",
    "<end_of_turn>", "<start_of_turn>", "[SYS]", "[/SYS]", "[USER]",
    "[/USER]", "[ASSISTANT]",
)
N_SPECIAL = len(SPECIAL_TOKENS)
#: 256 bytes and the special strings: ids at or above this are never sampled.
BYTE_VOCAB = N_SPECIAL + 256
BOS_ID = 1
_MATCH_ORDER = sorted((s for s in SPECIAL_TOKENS if s != "<pad>"),
                      key=len, reverse=True)
_SPECIAL_ID = {s: i for i, s in enumerate(SPECIAL_TOKENS)}


def encode(text: str, add_bos: bool = False) -> List[int]:
    """UTF-8 bytes shifted past the specials; a special string is one id."""
    ids = [BOS_ID] if add_bos else []
    i = 0
    while i < len(text):
        for special in _MATCH_ORDER:
            if text.startswith(special, i):
                ids.append(_SPECIAL_ID[special])
                i += len(special)
                break
        else:
            ids.extend(N_SPECIAL + b for b in text[i].encode("utf-8"))
            i += 1
    return ids


def chat_prompt(user: str, system: Optional[str]) -> str:
    if system:
        return f"[SYS]{system}[/SYS]\n[USER]{user}[/USER]\n[ASSISTANT]"
    return f"[USER]{user}[/USER]\n[ASSISTANT]"


def raw_prompt(user: str, system: Optional[str]) -> str:
    return f"{system}\n\n{user}" if system else user


def score_prefix(context: str, system: Optional[str], chat: bool, role: str) -> str:
    """The text a continuation is scored after: as a reply to a chat turn,
    inside the user turn (the evaluator's layout), or after raw text."""
    if chat and role == "user":
        parts = [p for p in (system, context) if p]
        joined = "\n\n".join(parts)
        return f"[SYS]{joined}[/SYS]\n[USER]" if joined else "[USER]"
    if chat:
        return chat_prompt(context, system)
    return raw_prompt(context, system)


# -- weights: the seed's key, and the sums that hold two trees to each other ---


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


@jax.jit
def _leaf_checksum(x: jax.Array) -> jax.Array:
    bits = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    weight = (jnp.arange(bits.size, dtype=jnp.uint32).reshape(bits.shape)
              % jnp.uint32(65521)) + jnp.uint32(1)
    return jnp.sum(bits * weight, dtype=jnp.uint32)


def weights_checksum(weights: Dict[str, Any]) -> Dict[str, int]:
    """A position-weighted sum of every bfloat16 leaf's bits, by leaf path:
    two weight trees agree in these only if they are the same numbers."""
    flat, _ = jax.tree_util.tree_flatten_with_path(weights)
    return {jax.tree_util.keystr(path): int(_leaf_checksum(leaf))
            for path, leaf in flat}


# -- a forward's rows ------------------------------------------------------------


def fp8(x: jax.Array) -> jax.Array:
    """The control's rounding: float8 (e4m3) and back to float32."""
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


#: Sequence widths the forward is compiled at, and the rows one call takes at
#: each, so that the (rows, heads, S, S) float32 attention logits stay near a
#: gigabyte.  A handful of programs whatever the prompts' lengths.
_WIDTHS = ((512, 8), (1024, 8), (2048, 2), (3072, 1), (4096, 1), (8192, 1))
_SCORED_BUCKET = 64


class Scored:
    """One row's readings at its scored positions (numpy, float32)."""

    def __init__(self, logprob, target_logit, best_logit, best_id):
        self.logprob = logprob
        self.target_logit = target_logit
        self.best_logit = best_logit
        self.best_id = best_id


def score_by_width(forward: Callable[..., Any], cfg: Any, weights: Any,
                   rows: Sequence[Tuple], precision: str) -> List[Scored]:
    """``rows`` are (token ids, how many trailing ids are scored) or, to read
    other ids' logits at those same positions, (ids, count, target ids).
    Each row's whole sequence runs through the model once, in its width's
    block: ``forward(cfg, weights, tokens (B, S) right-padded, lengths (B,),
    targets (B, T), n_scored=T, precision=)`` returns, for each row's last T
    real positions, the target's log-probability over the whole vocabulary,
    the target's logit, the best logit among sampleable ids, and that id."""
    out: List[Optional[Scored]] = [None] * len(rows)
    by_shape: Dict[Tuple[int, int, int], List[int]] = {}
    for index, row in enumerate(rows):
        ids, n_scored = row[0], row[1]
        if not 0 < n_scored < len(ids):
            raise ValueError(f"row {index}: {n_scored} scored of {len(ids)} ids")
        for width, block in _WIDTHS:
            if len(ids) <= width:
                break
        else:
            raise ValueError(f"row {index}: {len(ids)} ids pass {_WIDTHS[-1][0]}")
        scored = -(-n_scored // _SCORED_BUCKET) * _SCORED_BUCKET
        by_shape.setdefault((width, block, scored), []).append(index)
    for (width, block, scored), indices in by_shape.items():
        for start in range(0, len(indices), block):
            chunk = indices[start:start + block]
            tokens = np.zeros((block, width), np.int32)
            lengths = np.full((block,), scored + 1, np.int32)
            targets = np.zeros((block, scored), np.int32)
            for slot, index in enumerate(chunk):
                ids, n_scored = rows[index][0], rows[index][1]
                tokens[slot, :len(ids)] = ids
                lengths[slot] = len(ids)
                targets[slot, scored - n_scored:] = (
                    rows[index][2] if len(rows[index]) > 2
                    else ids[len(ids) - n_scored:])
            result = forward(cfg, weights, jnp.asarray(tokens),
                             jnp.asarray(lengths), jnp.asarray(targets),
                             n_scored=scored, precision=precision)
            logprob, target, best, best_id = (np.asarray(a) for a in result)
            for slot, index in enumerate(chunk):
                n_scored = rows[index][1]
                out[index] = Scored(logprob[slot, -n_scored:],
                                    target[slot, -n_scored:],
                                    best[slot, -n_scored:],
                                    best_id[slot, -n_scored:])
    return out  # type: ignore[return-value]
