"""Transformer architecture configs for the on-device model runtime.

The reference never executes a model (every forward pass is an HTTPS call,
src/utils.py:70); the model families it *calls* are Gemma-2 and Llama-3
(configs/appendix/{gemma,llama}/...).  These presets describe the same
families for local TPU execution, plus tiny variants for tests.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple


class ConfigurationUnsupported(ValueError):
    """A program or method was asked to run a configuration it cannot run.
    ``what`` names it.  A ``ValueError``: asking again cannot succeed, so
    the scheduler does not retry, and the service answers a client error
    with this message."""

    #: What of the configuration the program cannot run.
    HAS = "this configuration"

    def __init__(self, what: str, why: str):
        super().__init__(f"{what} does not run {self.HAS}: {why}")
        self.what = what


class RecurrentStateUnsupported(ConfigurationUnsupported):
    """The program cannot carry a recurrent layer's state."""

    HAS = "a configuration with recurrent layers"


class LayerKindsUnsupported(ConfigurationUnsupported):
    """The program holds one cache for every layer and one stack of layer
    weights: it cannot run layers of more than one kind."""

    HAS = "a configuration with layers of more than one kind"


#: Why the two families of programs that cannot carry the state refuse.
SEARCH_NEEDS_STATE = ("beams reorder and rollouts roll back rows, which needs "
                      "a gather and a checkpoint of the recurrent state")
STREAM_NEEDS_STATE = ("the stream path's slots hold pages by position and no "
                      "recurrent state by row")
#: Why the same programs, the prefix cache, the Pallas attention kernels, the
#: int8 paths and a tensor-parallel mesh refuse layers of more than one kind.
NEEDS_ONE_KIND = ("it holds one cache of one shape for every layer, and this "
                  "configuration keeps a cache for each kind of attention (a "
                  "latent kind's is one buffer of latents, not keys and values)")
KERNEL_NEEDS_PLAIN_HEADS = ("the Pallas attention kernels take no sink logit "
                            "and no value heads narrower than the key heads")


class LayerKind(NamedTuple):
    """What one run of equal layers is, as static data of the layer loop."""

    name: str  # the key of the kind's stack of weights under ``layers``
    attention: str  # "full", "window" or "latent": the cache it addresses
    kv_heads: int
    rope_theta: float
    window: Optional[int]  # keys a query sees at most; None = all before it
    sink: bool  # a learned logit a head in the softmax's denominator
    routed: bool  # routed experts in the feed-forward's place


class LayerRun(NamedTuple):
    """Consecutive layers of one kind: ``count`` of them, from ``at`` of the
    kind's stack of weights and from ``cache_at`` of its attention kind's
    cache."""

    kind: LayerKind
    at: int
    cache_at: int
    count: int


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "tiny"
    vocab_size: int = 512
    d_model: int = 64
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    ffn_hidden: int = 128
    # "geglu" (Gemma: gelu-tanh gated) or "swiglu" (Llama: silu gated)
    activation: str = "geglu"
    rope_theta: float = 10_000.0
    # Llama-3.1 "llama3" rope scaling as (factor, low_freq_factor,
    # high_freq_factor, original_max_position_embeddings); None disables.
    rope_scaling: Optional[Tuple[float, float, float, int]] = None
    rms_eps: float = 1e-6
    # Gemma-2 style logit softcaps; None disables.
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    # Sliding-window size for local-attention layers; None = all global.
    sliding_window: Optional[int] = None
    # Pattern of local(=True)/global(=False) attention per layer, tiled.
    # Gemma-2 alternates local/global; Llama is all-global.
    local_layer_pattern: Tuple[bool, ...] = (False,)
    # Query scale: 1/sqrt(query_pre_attn_scalar). Gemma-2 uses d_model/n_heads
    # (2b/9b: 256), Llama uses head_dim.
    query_pre_attn_scalar: Optional[int] = None
    # Gemma multiplies token embeddings by sqrt(d_model).
    scale_embeddings: bool = True
    # Tie LM head to the embedding matrix (Gemma yes, Llama-3-8B no).
    tie_lm_head: bool = True
    # Gemma-2 adds post-attention/post-ffw RMSNorms; Llama has only pre-norms.
    use_post_norms: bool = True
    # RMSNorm scale convention: "gemma" computes x * (1 + w), "llama" x * w.
    rmsnorm_style: str = "gemma"
    # Use the pallas flash-attention kernel on the no-cache (teacher-forced
    # scoring) path instead of materializing (B, H, S, S) logits.
    use_flash_attention: bool = False
    # Use the pallas fused decode-attention kernel in the session step's
    # trunk-tail path (ops/decode_attention.py) instead of the einsum pair.
    use_decode_attention: bool = False
    # Ids at or above this are never sampled or proposed: the serving
    # tokenizer cannot decode them (models/sampling.py:ban_undecodable).
    # The backend derives it from its tokenizer; None = the whole
    # vocabulary.  Teacher-forced scoring ignores it and keeps the
    # full-vocabulary logsumexp.
    sample_vocab: Optional[int] = None
    # -- a Mamba-2 mixer beside attention in every block (Falcon-H1) --------
    # ``ssm_heads`` 0 means no mixer: a dense block, as before.  With heads,
    # every layer's normed input feeds the mixer and attention side by side
    # and both are added to the residual (transformer.ssm_mixer).
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    # Columns of the recurrent state a head keeps per channel (d_state).
    ssm_state: int = 0
    # B and C are shared by the heads of a group.
    ssm_groups: int = 1
    # Width of the causal depthwise convolution over x, B and C.
    ssm_conv: int = 4
    # Positions a chunk of the chunked scan holds.
    ssm_chunk: int = 128
    # Inner width (heads x head size); the gate z has it too.
    ssm_inner: int = 0
    # True: norm, then gate.  False (Falcon-H1): gate, then norm.
    ssm_norm_before_gate: bool = False
    # muP multipliers, each a multiplication where it stands; None = none.
    embedding_multiplier: Optional[float] = None
    attention_in_multiplier: Optional[float] = None
    attention_out_multiplier: Optional[float] = None
    key_multiplier: Optional[float] = None
    ssm_in_multiplier: Optional[float] = None
    # Over the slices [z | x | B | C | dt] of the mixer's input product.
    ssm_slice_multipliers: Optional[Tuple[float, ...]] = None
    ssm_out_multiplier: Optional[float] = None
    # (on the gate product, inside the activation; on the down product).
    mlp_multipliers: Optional[Tuple[float, float]] = None
    lm_head_multiplier: Optional[float] = None
    # -- layers of more than one kind (MiMo-V2-Flash) -------------------------
    # One entry a layer: 0 full attention, 1 window attention.  Empty: every
    # layer is of one kind, as before (``local_layer_pattern``'s flag picks a
    # mask and nothing else).  Not empty: each kind has its own stack of
    # weights and each kind of attention its own cache; the layer loop runs
    # the runs of equal layers one after the other (``layer_runs``).
    hybrid_layer_pattern: Tuple[int, ...] = ()
    # One entry a layer: 0 the dense feed-forward, 1 routed experts.
    moe_layer_freq: Tuple[int, ...] = ()
    # A window layer's key-value heads and rotary base; None: as full ones.
    swa_kv_heads: Optional[int] = None
    swa_rope_theta: Optional[float] = None
    # A learned logit a query head in every softmax of a window layer: it
    # takes mass and adds no value.
    swa_sink: bool = False
    # Width of a value head where it is not the key heads' (``head_dim``).
    v_head_dim: Optional[int] = None
    # Leading dimensions of a head that the rotary embedding turns; the rest
    # pass.  None: all of them.
    rotary_dim: Optional[int] = None
    # The value states are multiplied by this.
    value_scale: Optional[float] = None
    # Routed experts: the router's width (every expert of the model), how
    # many a token is sent to, an expert's hidden width, and which experts
    # this program holds, (first, count): it routes over all ``n_experts``
    # and computes the part of the result that its own experts give.
    n_experts: int = 0
    experts_per_token: int = 0
    expert_hidden: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    # -- latent attention and a shared expert (JoyAI-LLM-Flash) ---------------
    # ``kv_lora_rank`` 0: none, as before.  Set: every layer's attention is
    # latent (``hybrid_layer_pattern`` all zeros).  Queries come through a
    # bottleneck of ``q_lora_rank`` with a norm inside it; keys and values
    # from one latent of ``kv_lora_rank`` a token, with a norm, and one
    # rotary key of ``qk_rope_dim`` shared by all heads.  A head's keys are
    # ``qk_nope_dim`` columns made from the latent beside the rotary key
    # (``head_dim`` is their sum), its values ``v_head_dim`` columns made
    # from the latent.  A token leaves the latent and the rotary key behind
    # and nothing else (``cache_widths``).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    # The rotary embedding turns adjacent pairs (2i, 2i+1) of a latent
    # layer's rotary columns, not the pairs (i, i + half).
    rope_interleave: bool = False
    # Experts every row passes, beside the routed ones: one gated
    # feed-forward of ``n_shared_experts`` x ``expert_hidden``.
    n_shared_experts: int = 0
    # The routed experts' sum is multiplied by this; the shared expert's
    # part is not.
    routed_scaling_factor: Optional[float] = None

    def __post_init__(self):
        if self.hybrid_layer_pattern:
            if len(self.hybrid_layer_pattern) != self.n_layers or (
                    self.moe_layer_freq
                    and len(self.moe_layer_freq) != self.n_layers):
                raise ValueError(
                    "hybrid_layer_pattern and moe_layer_freq have one entry "
                    f"a layer ({self.n_layers})")
            if any(self.hybrid_layer_pattern) and self.sliding_window is None:
                raise ValueError("a window layer needs sliding_window")
            if (self.has_ssm or any(self.local_layer_pattern)
                    or self.use_post_norms):
                raise ValueError(
                    "layers of more than one kind come with no recurrent "
                    "mixer, no local_layer_pattern and no post-norms")
        elif self.moe_layer_freq or self.swa_sink or self.swa_kv_heads:
            raise ValueError(
                "moe_layer_freq and the swa_* keys need hybrid_layer_pattern")
        if any(self.moe_layer_freq):
            first, count = self.experts_held or (0, 0)
            if not (0 < self.experts_per_token <= self.n_experts
                    and self.expert_hidden > 0 and count > 0
                    and 0 <= first and first + count <= self.n_experts):
                raise ValueError(
                    "routed layers need n_experts, experts_per_token, "
                    "expert_hidden and experts_held = (first, count) inside "
                    "the router's width")
        latent = (self.q_lora_rank, self.kv_lora_rank, self.qk_nope_dim,
                  self.qk_rope_dim)
        if any(latent) or self.rope_interleave:
            if not all(latent) or not self.v_head_dim:
                raise ValueError(
                    "latent attention needs q_lora_rank, kv_lora_rank, "
                    "qk_nope_dim, qk_rope_dim and v_head_dim, all of them "
                    "(rope_interleave is a latent layer's)")
            if self.head_dim != self.qk_nope_dim + self.qk_rope_dim:
                raise ValueError(
                    f"head_dim={self.head_dim} is not qk_nope_dim + "
                    f"qk_rope_dim ({self.qk_nope_dim} + {self.qk_rope_dim})")
            if (not self.hybrid_layer_pattern or any(self.hybrid_layer_pattern)
                    or self.rotary_dim is not None or self.qk_rope_dim % 2
                    or self.value_scale is not None
                    or self.attn_softcap is not None):
                raise ValueError(
                    "latent attention comes as a hybrid_layer_pattern of "
                    "zeros (every layer latent), with an even qk_rope_dim "
                    "and no rotary_dim, value_scale or attn_softcap")
        if (self.n_shared_experts or self.routed_scaling_factor is not None
                ) and not any(self.moe_layer_freq):
            raise ValueError(
                "n_shared_experts and routed_scaling_factor need routed "
                "layers (moe_layer_freq)")
        if self.ssm_heads and self.ssm_inner != self.ssm_heads * self.ssm_head_dim:
            raise ValueError(
                f"ssm_inner={self.ssm_inner} is not ssm_heads x ssm_head_dim "
                f"({self.ssm_heads} x {self.ssm_head_dim})")
        if self.ssm_heads and (self.ssm_heads % self.ssm_groups
                               or self.ssm_inner % self.ssm_groups):
            raise ValueError("ssm_groups must divide ssm_heads and ssm_inner")

    @property
    def has_ssm(self) -> bool:
        """Recurrent layers: a state by row rides beside the KV cache."""
        return self.ssm_heads > 0

    @property
    def ssm_conv_dim(self) -> int:
        """Columns the convolution runs over: x, B and C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def ssm_in_dim(self) -> int:
        """Columns of the mixer's input product: [z | x | B | C | dt]."""
        return self.ssm_inner + self.ssm_conv_dim + self.ssm_heads

    @property
    def ssm_slice_widths(self) -> Tuple[int, ...]:
        """Widths of the slices [z | x | B | C | dt] of the input product,
        the order ``ssm_slice_multipliers`` is in."""
        gn = self.ssm_groups * self.ssm_state
        return (self.ssm_inner, self.ssm_inner, gn, gn, self.ssm_heads)

    def ssm_state_bytes(self, itemsize: int) -> int:
        """Bytes of one row's recurrent state over all layers: the float32
        matrix of every head and the convolution's window in the
        activations' type.  0 for a dense configuration."""
        if not self.has_ssm:
            return 0
        h = 4 * self.ssm_heads * self.ssm_head_dim * self.ssm_state
        conv = itemsize * (self.ssm_conv - 1) * self.ssm_conv_dim
        return self.n_layers * (h + conv)

    @property
    def has_layer_kinds(self) -> bool:
        """Layers of more than one kind: a stack of weights a kind, a cache
        a kind of attention."""
        return bool(self.hybrid_layer_pattern)

    @property
    def has_moe(self) -> bool:
        return any(self.moe_layer_freq)

    @property
    def has_latent(self) -> bool:
        """Latent attention: a token leaves one latent and one rotary key
        behind, in one buffer, and no values of their own."""
        return self.kv_lora_rank > 0

    @property
    def latent_dim(self) -> int:
        """What a token of a latent layer leaves behind: [latent | rotary
        key]."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def value_dim(self) -> int:
        """Width of a value head."""
        return self.v_head_dim or self.head_dim

    def cache_widths(self, attention: Optional[str]) -> Tuple[int, int]:
        """(columns of a head of the key buffer, columns of a head of the
        value buffer) of a kind of attention's cache.  A latent cache keeps
        no value buffer: its values are the keys' first ``kv_lora_rank``
        columns."""
        if attention == "latent":
            return self.latent_dim, 0
        return self.head_dim, self.value_dim

    @property
    def layer_kinds(self) -> Tuple[LayerKind, ...]:
        """Each layer's kind, for a configuration with ``has_layer_kinds``."""
        routed = self.moe_layer_freq or (0,) * self.n_layers
        kinds = []
        for window, moe in zip(self.hybrid_layer_pattern, routed):
            attention = "window" if window else "full"
            kv_heads = ((self.swa_kv_heads or self.n_kv_heads) if window
                        else self.n_kv_heads)
            if self.has_latent:  # one head of ``latent_dim`` in the cache
                attention, kv_heads = "latent", 1
            kinds.append(LayerKind(
                name=f"{attention}_{'moe' if moe else 'dense'}",
                attention=attention,
                kv_heads=kv_heads,
                rope_theta=float(
                    (self.swa_rope_theta or self.rope_theta) if window
                    else self.rope_theta),
                window=self.sliding_window if window else None,
                sink=bool(window and self.swa_sink),
                routed=bool(moe),
            ))
        return tuple(kinds)

    @property
    def layer_runs(self) -> Tuple[LayerRun, ...]:
        """The runs of equal layers, in the model's order."""
        runs, stacked, cached = [], {}, {}
        for kind in self.layer_kinds:
            if runs and runs[-1].kind == kind:
                runs[-1] = runs[-1]._replace(count=runs[-1].count + 1)
            else:
                runs.append(LayerRun(kind, stacked.get(kind.name, 0),
                                     cached.get(kind.attention, 0), 1))
            stacked[kind.name] = stacked.get(kind.name, 0) + 1
            cached[kind.attention] = cached.get(kind.attention, 0) + 1
        return tuple(runs)

    @property
    def kind_layers(self) -> Tuple[Tuple[LayerKind, int], ...]:
        """(kind, how many layers are of it), in order of first appearance."""
        counts = {}
        for kind in self.layer_kinds:
            counts[kind] = counts.get(kind, 0) + 1
        return tuple(counts.items())

    @property
    def cache_kinds(self) -> Tuple[Tuple[str, int, int], ...]:
        """(kind of attention, its layers, its key-value heads), in order of
        first appearance: the caches a configuration with ``has_layer_kinds``
        holds (``cache_widths`` has a head's columns; a latent kind is one
        head and no value buffer).  Without: one cache, named ``None``."""
        if not self.has_layer_kinds:
            return ((None, self.n_layers, self.n_kv_heads),)
        layers, heads = {}, {}
        for kind in self.layer_kinds:
            layers[kind.attention] = layers.get(kind.attention, 0) + 1
            heads[kind.attention] = kind.kv_heads
        return tuple((name, n, heads[name]) for name, n in layers.items())

    def kv_bytes_per_token(self, itemsize: float) -> float:
        """Bytes of one position's keys and values over all layers: the sum
        over the kinds of attention, each at its own heads and widths (a
        latent position once: it has no values of its own)."""
        return sum(
            n * heads * sum(self.cache_widths(name)) * itemsize
            for name, n, heads in self.cache_kinds)

    @property
    def q_scale(self) -> float:
        scalar = self.query_pre_attn_scalar or self.head_dim
        return scalar ** -0.5

    def layer_is_local(self, layer: int) -> bool:
        return self.local_layer_pattern[layer % len(self.local_layer_pattern)]

    @property
    def local_flags(self) -> Tuple[bool, ...]:
        return tuple(self.layer_is_local(i) for i in range(self.n_layers))


def _gemma2(name: str, **kw) -> ModelConfig:
    base = dict(
        activation="geglu",
        rope_theta=10_000.0,
        attn_softcap=50.0,
        final_softcap=30.0,
        sliding_window=4096,
        local_layer_pattern=(True, False),  # even layers local, odd global
        scale_embeddings=True,
        tie_lm_head=True,
        use_post_norms=True,
        rmsnorm_style="gemma",
    )
    base.update(kw)
    return ModelConfig(name=name, **base)


def _llama3(name: str, **kw) -> ModelConfig:
    base = dict(
        activation="swiglu",
        rope_theta=500_000.0,
        attn_softcap=None,
        final_softcap=None,
        sliding_window=None,
        local_layer_pattern=(False,),
        scale_embeddings=False,
        tie_lm_head=False,
        use_post_norms=False,
        rmsnorm_style="llama",
        rms_eps=1e-5,
    )
    base.update(kw)
    return ModelConfig(name=name, **base)


MODEL_CONFIGS = {
    # Gemma-2 2.6B (google/gemma-2-2b): 26 layers, d=2304, 8 q / 4 kv heads,
    # head_dim 256, ffn 9216, vocab 256128.
    "gemma2-2b": _gemma2(
        "gemma2-2b",
        vocab_size=256_128,
        d_model=2304,
        n_layers=26,
        n_heads=8,
        n_kv_heads=4,
        head_dim=256,
        ffn_hidden=9216,
        query_pre_attn_scalar=256,
    ),
    # Gemma-2 9B (google/gemma-2-9b-it) — the reference's AAMAS generation
    # model (configs/appendix/gemma/*): 42 layers, d=3584, 16 q / 8 kv heads.
    "gemma2-9b": _gemma2(
        "gemma2-9b",
        vocab_size=256_128,
        d_model=3584,
        n_layers=42,
        n_heads=16,
        n_kv_heads=8,
        head_dim=256,
        ffn_hidden=14336,
        query_pre_attn_scalar=224,
    ),
    # Llama-3.1 8B (meta-llama/Meta-Llama-3.1-8B-Instruct-Turbo in the
    # reference's main-body configs): 32 layers, d=4096, 32 q / 8 kv heads,
    # "llama3" rope scaling (HF config.json rope_scaling; certified against
    # transformers in tests/test_hf_numerics.py).
    "llama3-8b": _llama3(
        "llama3-8b",
        vocab_size=128_256,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        head_dim=128,
        ffn_hidden=14336,
        rope_scaling=(8.0, 1.0, 4.0, 8192),
    ),
    # Tiny variants for tests / CPU smoke runs.  Their vocabulary is the
    # byte tokenizer's 268 rows (models/tokenizer.py), the only tokenizer
    # they are ever served with.
    "tiny-gemma2": _gemma2(
        "tiny-gemma2",
        vocab_size=268,
        d_model=64,
        n_layers=4,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        ffn_hidden=128,
        sliding_window=16,
        query_pre_attn_scalar=16,
    ),
    "tiny-llama3": _llama3(
        "tiny-llama3",
        vocab_size=268,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        ffn_hidden=128,
    ),
    # Falcon-H1's block at a test's size: a Mamba-2 mixer beside grouped-
    # query attention, every multiplier at a value other than 1, an untied
    # head.  The published sizes live in benchmark/configs/.
    "tiny-falcon-h1": _llama3(
        "tiny-falcon-h1",
        vocab_size=512,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        ffn_hidden=128,
        rope_theta=1e11,
        ssm_heads=4,
        ssm_head_dim=16,
        ssm_state=16,
        ssm_groups=2,
        ssm_conv=4,
        ssm_chunk=8,
        ssm_inner=64,
        embedding_multiplier=5.5,
        attention_in_multiplier=0.9,
        attention_out_multiplier=0.04,
        key_multiplier=0.011,
        ssm_in_multiplier=0.25,
        ssm_slice_multipliers=(0.35, 0.25, 0.18, 0.5, 0.36),
        ssm_out_multiplier=0.09,
        mlp_multipliers=(0.18, 0.011),
        lm_head_multiplier=0.0078,
    ),
    # MiMo-V2-Flash's layers at a test's size: three kinds in seven layers
    # (full attention + a dense feed-forward; window attention with its own
    # key-value heads, sinks and routed experts; full attention + routed
    # experts), key heads wider than value heads, rotary on a third of a
    # head, 8 experts a token of 32 with 8 held.  The published sizes live in
    # benchmark/configs/.
    "tiny-mimo-v2": _llama3(
        "tiny-mimo-v2",
        vocab_size=320,
        d_model=64,
        n_layers=7,
        n_heads=8,
        n_kv_heads=2,
        head_dim=24,
        ffn_hidden=128,
        rope_theta=5e6,
        sliding_window=8,
        hybrid_layer_pattern=(0, 1, 1, 1, 1, 0, 1),
        moe_layer_freq=(0, 1, 1, 1, 1, 1, 1),
        swa_kv_heads=4,
        swa_rope_theta=1e4,
        swa_sink=True,
        v_head_dim=16,
        rotary_dim=8,
        value_scale=0.707,
        n_experts=32,
        experts_per_token=8,
        expert_hidden=32,
        experts_held=(8, 8),
    ),
    # JoyAI-LLM-Flash's layers at a test's size: latent attention in every
    # layer (queries through a bottleneck, one latent and one shared rotary
    # key a token, adjacent-pair rotary), a leading dense layer and two
    # routed ones with a shared expert and a factor on the routed sum, every
    # one of the 8 experts held.  The published sizes live in
    # benchmark/configs/.
    "tiny-joyai-flash": _llama3(
        "tiny-joyai-flash",
        vocab_size=320,
        d_model=64,
        n_layers=3,
        n_heads=4,
        n_kv_heads=4,
        head_dim=24,
        ffn_hidden=128,
        rope_theta=32e6,
        rms_eps=1e-6,
        hybrid_layer_pattern=(0, 0, 0),
        moe_layer_freq=(0, 1, 1),
        v_head_dim=16,
        q_lora_rank=48,
        kv_lora_rank=32,
        qk_nope_dim=16,
        qk_rope_dim=8,
        rope_interleave=True,
        n_experts=8,
        experts_per_token=2,
        expert_hidden=32,
        experts_held=(0, 8),
        n_shared_experts=1,
        routed_scaling_factor=2.5,
    ),
}


def get_model_config(name: str, **overrides) -> ModelConfig:
    """Look up a preset by name, optionally overriding fields."""
    if name not in MODEL_CONFIGS:
        raise ValueError(f"Unknown model config: {name!r}. Known: {sorted(MODEL_CONFIGS)}")
    config = MODEL_CONFIGS[name]
    if overrides:
        config = dataclasses.replace(config, **overrides)
    return config
