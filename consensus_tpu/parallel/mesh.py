"""Device mesh + sharding layout for the transformer runtime.

Layout philosophy (scaling-book recipe: pick a mesh, annotate shardings, let
XLA insert collectives):

* Mesh axes ``("data", "model")``.  The experiment workload — (seeds ×
  scenarios × candidates × agents) forward passes — is embarrassingly
  data-parallel, so ``data`` is the large axis; ``model`` carries tensor
  parallelism for models that don't fit (or aren't fast enough) per chip.
* Tensor-parallel params follow the Megatron layout expressed as
  PartitionSpecs: attention q/k/v projections and the FFN up/gate split
  their *output* features over ``model``; the o-projection and FFN down
  split their *input* features, so each layer needs exactly one psum
  (XLA inserts it from the shardings).
* The embedding shards its vocab rows over ``model``; logits come out
  sharded over vocab and argmax/softmax reductions ride ICI collectives.

The reference has no counterpart to any of this — its concurrency is a
thread pool over HTTP calls (src/experiment.py:283-322).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """A mesh plus the axis sizes it was built with."""

    mesh: Mesh
    dp: int
    tp: int

    @property
    def n_devices(self) -> int:
        return self.dp * self.tp


def make_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    tp: int = 1,
    dp: Optional[int] = None,
) -> MeshPlan:
    """Build a ``(data, model)`` mesh over the given (default: all) devices.

    ``tp`` is the tensor-parallel degree; remaining devices become data
    parallel.  ``tp=1`` (pure DP, model replicated) is the right default for
    the 2B/9B models of the reference workload (SURVEY §5.8).  An explicit
    ``dp`` smaller than ``n // tp`` uses the first ``dp * tp`` devices.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n % tp != 0:
        raise ValueError(f"tp={tp} does not divide device count {n}")
    dp = dp if dp is not None else n // tp
    if dp * tp > n:
        raise ValueError(f"dp*tp = {dp * tp} > device count {n}")
    grid = np.array(devices[: dp * tp]).reshape(dp, tp)
    return MeshPlan(mesh=Mesh(grid, (DATA_AXIS, MODEL_AXIS)), dp=dp, tp=tp)


#: Regex partition rules (match_partition_rules style): first rule whose
#: pattern ``re.search``-matches a ``/``-joined param path wins.  Layer-
#: stacked leaves carry a leading layer axis (never sharded — it is scanned
#: over).  Every param path of every supported model family (gemma2 AND
#: llama3 tiers) must match a rule: :func:`match_partition_rules` raises on
#: any unmatched path, so a new param added to the runtime without a layout
#: decision fails loudly instead of silently replicating (pinned in
#: tests/test_mesh_serving.py against both tiny models).
#: A configuration with layers of more than one kind keeps a stack of leaves
#: a kind, ``layers/<kind>/<leaf>``: the same rule serves the leaf under
#: either path.  (Such a configuration is refused on a mesh today, by name,
#: for its caches; its leaves have their rules all the same, so that the
#: coverage check names every one.)
_KIND = r"(?:\w+/)?"
PARTITION_RULES: Tuple[Tuple[str, P], ...] = (
    # Norm vectors replicate (tiny; every shard needs them whole).
    (rf"^layers/{_KIND}(attn_norm|ffn_norm|post_attn_norm|post_ffn_norm)$",
     P(None, None)),
    # (L, D, H*hd): split heads (output features) over model.
    (rf"^layers/{_KIND}(wq|wk|wv)$", P(None, None, MODEL_AXIS)),
    # (L, H*hd, D): split input features — contraction psum follows.
    (rf"^layers/{_KIND}wo$", P(None, MODEL_AXIS, None)),
    # (L, D, F): split hidden features.
    (rf"^layers/{_KIND}(w_gate|w_up)$", P(None, None, MODEL_AXIS)),
    # (L, F, D): split input features.
    (rf"^layers/{_KIND}w_down$", P(None, MODEL_AXIS, None)),
    # A window layer's sinks (L, H), one a query head: with the heads.
    (rf"^layers/{_KIND}attn_sink$", P(None, MODEL_AXIS)),
    # The router (L, D, experts) and its bias replicate: every shard routes
    # over all the experts.
    (rf"^layers/{_KIND}(router|router_bias)$", P()),
    # Held experts (L, E, D, F) and (L, E, F, D): the expert axis whole on
    # every shard, an expert's hidden features split as a dense layer's.
    (rf"^layers/{_KIND}(experts_gate|experts_up)$",
     P(None, None, None, MODEL_AXIS)),
    (rf"^layers/{_KIND}experts_down$", P(None, None, MODEL_AXIS, None)),
    # A latent layer: the norms inside the two bottlenecks and the products
    # into them ((L, D, q rank), (L, D, latent + rotary key): every shard needs
    # the whole latent) replicate; the products out of them ((L, rank, H x
    # columns)) split their heads, as wq.
    (rf"^layers/{_KIND}(q_norm|kv_norm)$", P(None, None)),
    (rf"^layers/{_KIND}(w_qa|w_kva)$", P(None, None, None)),
    (rf"^layers/{_KIND}(w_qb|w_kvb)$", P(None, None, MODEL_AXIS)),
    # The shared expert (L, D, F) and (L, F, D): as a dense feed-forward.
    (rf"^layers/{_KIND}(shared_gate|shared_up)$", P(None, None, MODEL_AXIS)),
    (rf"^layers/{_KIND}shared_down$", P(None, MODEL_AXIS, None)),
    # (V, D): shard vocab rows; logits come out sharded over vocab.
    (r"^(embed|lm_head)$", P(MODEL_AXIS, None)),
    (r"^final_norm$", P(None)),
)


def _iter_param_paths(params: Dict[str, Any], prefix: str = ""):
    """Yield (``/``-joined path, leaf) pairs for a runtime param pytree.
    QTensor leaves (int8 weight + scale) count as ONE leaf — their layout
    derives from the full-precision weight's spec in :func:`_leaf_sharding`."""
    for name, value in params.items():
        path = f"{prefix}{name}"
        if isinstance(value, dict):
            yield from _iter_param_paths(value, path + "/")
        else:
            yield path, value


def match_partition_rules(
    params: Dict[str, Any],
    rules: Sequence[Tuple[str, P]] = PARTITION_RULES,
) -> Dict[str, P]:
    """PartitionSpec pytree for ``params`` from regex rules (SNIPPETS [3]).

    Returns the same nested-dict structure with a PartitionSpec per leaf.
    Scalars and single-element leaves are never partitioned (``P()``).
    Raises ``ValueError`` naming EVERY unmatched path — the coverage check
    the mesh serving tests pin, so partial layouts can't ship silently.
    """
    specs: Dict[str, Any] = {}
    unmatched: List[str] = []
    for path, leaf in _iter_param_paths(params):
        shape = getattr(leaf, "shape", None)
        if shape is None:  # int8 QTensor: layout follows the quantized weight
            shape = getattr(getattr(leaf, "q", None), "shape", ())
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            spec = P()  # scalars never partition
        else:
            for pattern, rule_spec in rules:
                if re.search(pattern, path) is not None:
                    spec = rule_spec
                    break
            else:
                unmatched.append(path)
                continue
        node = specs
        parts = path.split("/")
        for key in parts[:-1]:
            node = node.setdefault(key, {})
        node[parts[-1]] = spec
    if unmatched:
        raise ValueError(
            "no partition rule matches param path(s): "
            + ", ".join(sorted(unmatched))
            + " — add a rule to consensus_tpu.parallel.mesh.PARTITION_RULES"
        )
    return specs


def _leaf_sharding(leaf: Any, spec: P, mesh: Mesh) -> Any:
    """Sharding for one param leaf — plain array or int8 QTensor.

    A QTensor's ``q`` shards exactly like the full-precision weight.  Its
    ``scale`` keeps the weight's rank with the contraction axis squeezed to
    extent 1, so the scale inherits the weight's spec with ``None`` on every
    size-1 axis (a size-1 axis cannot split over a mesh axis; every shard
    needs the full scale vector anyway — wo/w_down shard their *input*
    features, whose scales are per-*output*-channel and must replicate).
    """
    from consensus_tpu.models.quant import QTensor

    if isinstance(leaf, QTensor):
        axes = tuple(spec) + (None,) * (leaf.scale.ndim - len(tuple(spec)))
        scale_spec = P(
            *[
                None if dim == 1 else axis
                for axis, dim in zip(axes, leaf.scale.shape)
            ]
        )
        return QTensor(
            q=NamedSharding(mesh, spec),
            scale=NamedSharding(mesh, scale_spec),
            compute_dtype=leaf.compute_dtype,
        )
    return NamedSharding(mesh, spec)


def param_shardings(params: Dict[str, Any], mesh: Mesh) -> Dict[str, Any]:
    """NamedSharding pytree matching a runtime param pytree (full-precision
    or int8-quantized leaves), resolved through :data:`PARTITION_RULES` —
    an unmatched param path raises rather than silently replicating."""
    specs = match_partition_rules(params)

    def resolve(value, spec):
        if isinstance(value, dict):
            return {k: resolve(v, spec[k]) for k, v in value.items()}
        return _leaf_sharding(value, spec, mesh)

    return {name: resolve(value, specs[name]) for name, value in params.items()}


def parse_mesh_spec(
    spec: Union[str, Dict[str, int], MeshPlan, None],
) -> Optional[Dict[str, int]]:
    """Normalise a mesh request to ``{"dp": N, "tp": M}``.

    Accepts the CLI string form (``"dp=4,tp=2"``, either key optional), a
    dict with ``dp``/``tp`` keys, an existing :class:`MeshPlan`, or ``None``
    (no mesh).  Unknown keys and non-positive sizes raise.
    """
    if spec is None:
        return None
    if isinstance(spec, MeshPlan):
        return {"dp": spec.dp, "tp": spec.tp}
    if isinstance(spec, str):
        parsed: Dict[str, int] = {}
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            key, sep, value = part.partition("=")
            if not sep:
                raise ValueError(
                    f"bad mesh spec {spec!r}: expected 'dp=N,tp=M', got {part!r}"
                )
            parsed[key.strip()] = int(value)
        spec = parsed
    unknown = set(spec) - {"dp", "tp"}
    if unknown:
        raise ValueError(
            f"bad mesh spec: unknown axis {sorted(unknown)} (want dp/tp)"
        )
    out = {"dp": int(spec.get("dp", 1)), "tp": int(spec.get("tp", 1))}
    if out["dp"] < 1 or out["tp"] < 1:
        raise ValueError(f"bad mesh spec: sizes must be >= 1, got {out}")
    return out


def shard_params(params: Dict[str, Any], mesh: Mesh) -> Dict[str, Any]:
    """Place a param pytree on the mesh with the TP layout."""
    return jax.device_put(params, param_shardings(params, mesh))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for (B, S) token/mask arrays: batch over ``data``."""
    return NamedSharding(mesh, P(DATA_AXIS, None))


def shard_batch(mesh: Mesh, *arrays: jax.Array):
    """Place batch-leading arrays on the mesh, sharded over ``data``."""
    sharding = batch_sharding(mesh)
    placed = tuple(jax.device_put(a, sharding) for a in arrays)
    return placed[0] if len(placed) == 1 else placed
