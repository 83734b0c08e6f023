"""Llama-family decoder-only transformer — the framework's flagship model.

No reference equivalent (Horovod 0.15.1 predates LLMs); required by the
baseline workload list (SURVEY.md §5.7: "Llama FSDP-style workload") and used
as the flagship for ``__graft_entry__.py`` because it exercises every
parallelism axis the framework supports: data, fsdp, tensor, sequence
(ring attention), pipeline, and expert (MoE variant).

TPU-first design:
* RMSNorm in fp32, everything else bf16 — including logits
  (``logits_dtype``): the loss upcasts per-tile inside its reductions,
  so no logits-sized f32 tensor is ever stored (ops/losses.py).
* RoPE applied on-the-fly (no position-embedding table to shard).
* GQA: ``num_kv_heads <= num_heads`` — shrinks the KV all-gather under
  tensor parallelism.
* SwiGLU MLP with fused gate+up projection (one [H, 2F] matmul).
* Pluggable ``attention_fn`` — ``horovod_tpu.parallel.ring_attention``
  substitutes a ppermute-ring blockwise kernel for sequence parallelism.
* One layer stack whose layers are what their ``LayerSpec`` says
  (``LlamaConfig.layers``, resolved once from the config's published keys):
  one of seven mixers (``MIXERS``: softmax attention of four classes, over
  the layer's own keys or another layer's; the gated delta rule; a gated
  short convolution; Mamba-2; Mamba-1; a gated memory unit), a dense SwiGLU
  or routed experts beside shared ones, or one of the two alone.
  The routed layer is told which experts it holds, routes over all of
  them, gathers its own experts' rows sorted by expert -- none dropped --
  and runs grouped products over them (``RoutedExperts``).
* Optional residual STREAMS (``hc_mult`` > 1, manifold-constrained
  hyper-connections): a token's state is ``hc_mult`` vectors that every
  sublayer reads as a learned mix and rewrites as another
  (``HyperConnection``); wiring around the sublayers, which stay what they
  are.
* Optional weight-shared passes over the stack (``total_ut_steps > 1``,
  the looped LM of Ouro / LoopLM) with a per-token exit gate, and
  recomputation of each layer in the backward pass (``remat``); see
  ``LlamaModel``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_policies

from horovod_tpu.common import scopes as _scopes
from horovod_tpu.common import trace_counts as _trace_counts
from horovod_tpu.ops import hyper_connection as _hc
from horovod_tpu.ops.gated_delta import (CHUNK, called_in_place,
                                         calls_in_place, gated_delta_rule,
                                         gated_delta_states,
                                         key_heads_copied, walks_rows)
from horovod_tpu.ops.gated_norm import gated_norm, norm_gated, skipped
from horovod_tpu.ops import kda as _kda
from horovod_tpu.ops.grouped_matmul import grouped_matmul
from horovod_tpu.ops.losses import batch_balance_loss, sequence_balance_loss
from horovod_tpu.ops import rope as _rope
from horovod_tpu.ops.selective_scan import (selective_scan,
                                            selective_scan_states)
from horovod_tpu.ops.short_conv import convolved
from horovod_tpu.ops.ssd import ssd_scan_rows, ssd_states
from horovod_tpu.ops.sparse_index import index_loss, select_keys

__all__ = ["LlamaConfig", "LlamaModel", "RMSNorm", "RopeParameters",
           "YarnScaling", "apply_rope", "causal_attention"]


REMAT_POLICIES = {
    # Nothing of a layer is kept but its input: the backward pass runs the
    # layer's forward again, the flash kernel's forward call included.
    "layer": None,
    # The flash kernel's output and row statistics are kept as well (34 MB
    # a layer at 8192 tokens x 2048), so its forward call is not repeated.
    "layer_keep_attention": checkpoint_policies.save_only_these_names(
        _scopes.FLASH_OUT_NAME, _scopes.FLASH_LSE_NAME),
    # And what learned sparse attention found: the selection (int8, S x S a
    # sequence), and the indexer's loss with the gradients its one walk
    # left, so that the recomputed forward neither scores nor selects nor
    # walks again.
    "layer_keep_selection": checkpoint_policies.save_only_these_names(
        _scopes.FLASH_OUT_NAME, _scopes.FLASH_LSE_NAME,
        _scopes.SPARSE_SELECTED_NAME, _scopes.SPARSE_INDEX_LOSS_NAME),
}

# The collection a bias-corrected router keeps its choice bias in: state the
# load moves and no gradient does (``RoutedExperts``).
ROUTER_STATE = "router_state"

# ``hybrid_override_pattern``'s characters, as Nemotron-H publishes them: a
# Mamba-2 layer, a routed feed-forward layer, a softmax attention layer.
MAMBA, EXPERTS, ATTENTION = PATTERN_KINDS = ("M", "E", "*")
DENSE_LAYER = "-"       # published too; no model here has one: refused
# The mixers of a decoder-hybrid-decoder stack (``LlamaConfig.mixer_of``): a
# Mamba-1 selective scan, attention over the layer's own keys, a gated memory
# unit on the shared scan output, cross-attention to the shared keys and
# values.  With the three below, every mixer a ``LayerSpec`` can name.
SCAN, SELF_ATTENTION, MEMORY_GATE, CROSS_ATTENTION = SHARING_MIXERS = (
    "mamba", "attention", "gated_memory", "cross_attention")
DELTA_RULE, SHORT_CONV, MAMBA2 = "linear_attention", "conv", "mamba2"
# Kimi Delta Attention: the delta rule whose decay is a number a channel.  No
# layer type names it: ``linear_attn_config`` lists its layers.
KDA = "kda"
# ``layer_types``' published names, and the mixer each gives its layer.  The
# TYPE ``"mamba"`` (Granite-4.0-H's name) is a Mamba-2 layer, ``MAMBA2``; the
# MIXER named ``"mamba"``, ``SCAN``, is Mamba-1's, which no type names.
TYPE_MIXERS = {"full_attention": SELF_ATTENTION,
               "linear_attention": DELTA_RULE,
               "sliding_attention": SELF_ATTENTION, "conv": SHORT_CONV,
               "mamba": MAMBA2, "attention": SELF_ATTENTION}
# The types whose layers have no softmax, so no entry in ``rope_parameters``.
NO_SOFTMAX_TYPES = ("linear_attention", "conv", "mamba")
LAYER_TYPES = tuple(TYPE_MIXERS)
DENSE, ROUTED = "dense", "routed"       # a ``LayerSpec``'s feed-forwards
ONE_NORM, TWO_NORMS = ("norm",), ("norm_attn", "norm_mlp")


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: ``0.1 mscale ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """``rope_scaling`` of type ``yarn``, under its published keys."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    attention_factor: Optional[float] = None    # stated: the table's scale

    def correction_range(self, dim: int, theta: float) -> tuple[int, int]:
        """(low, high): the rotary pairs between which YaRN blends from
        the published frequency (below ``low``) to the interpolated one
        (above ``high``)."""
        def pair_of(rotations):
            return (dim * math.log(self.original_max_position_embeddings
                                   / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(pair_of(self.beta_fast)), 0)
        high = min(math.ceil(pair_of(self.beta_slow)), dim - 1)
        return low, high

    @property
    def table_scale(self) -> float:
        """What multiplies cos and sin: ``attention_factor`` where the
        configuration states it, else the ratio of the two temperatures."""
        if self.attention_factor is not None:
            return self.attention_factor
        return (yarn_mscale(self.factor, self.mscale)
                / yarn_mscale(self.factor, self.mscale_all_dim))

    @property
    def softmax_scale(self) -> float:
        """What multiplies ``1 / sqrt(d_qk)``: m squared."""
        if not self.mscale_all_dim:
            return 1.0
        return yarn_mscale(self.factor, self.mscale_all_dim) ** 2


@dataclasses.dataclass(frozen=True)
class RopeParameters:
    """One entry of ``rope_parameters``, under its published keys: the
    rotation of one layer type.  ``partial_rotary_factor`` is the share of
    a head's width that turns (its first lanes; the rest pass untouched);
    ``scaling`` a ``YarnScaling`` over that rotary width, or None."""

    rope_theta: float
    scaling: Optional[YarnScaling] = None
    partial_rotary_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """What one layer of the stack IS: ``LlamaConfig.layers[i]``, resolved
    once a config by ``_layer_specs``; ``LlamaLayer`` and the accessors read
    it and derive nothing.

    ``mixer`` names the layer's mixer (a key of ``MIXERS``) or is None (a
    feed-forward alone); ``ffn`` is ``DENSE`` (a ``SwiGLU``), ``ROUTED``
    (``RoutedExperts``) or None.  ``heads`` query heads, the ``window`` of
    keys a query sees behind it (itself included; None: all of them) and
    the rotation ``rope`` of q and k (None: they do not turn) are those of
    the layer's published ``type`` (one of ``LAYER_TYPES``) whatever its
    mixer; an attention mixer reads them.  ``norms`` names the norms, one a
    sublayer: ``ONE_NORM``, or ``TWO_NORMS``, a mixer's and a feed-forward's.
    ``reads`` is the tensor of ``shared`` the mixer is handed beside its
    input and ``writes`` the one it leaves there for later layers
    (``"memory"``: a scan's output, ``"kv"``: keys and values), or None."""

    mixer: Optional[str]
    ffn: Optional[str]
    heads: int
    window: Optional[int]
    rope: Optional[RopeParameters]
    type: str = "full_attention"
    norms: tuple = TWO_NORMS
    reads: Optional[str] = None
    writes: Optional[str] = None


def _layer_specs(cfg: "LlamaConfig") -> tuple:
    """``cfg.layers``: the one reading of the fields that say what layer i
    is (``LlamaConfig``'s docstring has them, by ``LayerSpec`` field).
    Raises what they cannot mean: a mechanism's own values, what it needs of
    the other fields, and what does not go with it."""
    n = cfg.num_layers
    types, pattern = cfg.layer_types, cfg.hybrid_override_pattern
    naming = [name for name in ("layer_types", "hybrid_override_pattern",
                                "mb_per_layer") if getattr(cfg, name)]
    if len(naming) > 1:
        raise ValueError(
            f"{' and '.join(naming)} each name every layer's kind: at most "
            f"one of layer_types, hybrid_override_pattern and mb_per_layer")
    if cfg.linear_attn_config is not None and naming:
        raise ValueError(
            f"linear_attn_config lists every layer's mixer: "
            f"{' and '.join(naming)} do not go with it")
    heads = cfg.num_attention_heads_per_layer
    if heads is None:
        heads = (cfg.num_heads,) * n
    elif len(heads) != n or any(h < 1 or h % cfg.num_kv_heads
                                for h in heads):
        raise ValueError(
            f"num_attention_heads_per_layer is {heads!r}: a multiple of "
            f"the {cfg.num_kv_heads} key-value heads for each of {n} layers")
    if cfg.rope_parameters is None:
        ropes = dict.fromkeys(
            LAYER_TYPES, None if cfg.rope_theta is None
            else RopeParameters(cfg.rope_theta, cfg.rope_scaling))
    else:
        ropes = dict(cfg.rope_parameters)
    if cfg.mla_use_nope:
        # Latent attention whose shared lanes do not turn: no layer of the
        # stack rotates, and no table is made for it.
        ropes = dict.fromkeys(LAYER_TYPES, None)

    def spec(i, mixer, ffn, kind="full_attention", **wiring):
        window = cfg.sliding_window if kind == "sliding_attention" else None
        return LayerSpec(mixer, ffn, heads[i], window, ropes.get(kind), kind,
                         **wiring)

    if cfg.attention_kind == "differential" and (
            not cfg.mb_per_layer or cfg.rope_theta is not None
            or cfg.num_heads % 2 or cfg.num_kv_heads % 2
            or cfg.qk_norm or cfg.gating is not None):
        raise ValueError(
            "differential attention is built for a decoder-hybrid-decoder "
            "stack (mb_per_layer): it pairs adjacent heads (an even "
            "count of query and of key-value heads) and neither rotates "
            "(rope_theta=None), norms nor gates them")
    if cfg.mb_per_layer:
        if (cfg.mb_per_layer != 2 or n % 4 or not cfg.ssm_state_size
                or cfg.sliding_window is None or cfg.norm_placement != "pre"
                or cfg.num_experts > 1 or cfg.total_ut_steps != 1
                or cfg.attention_kind == "latent"):
            raise ValueError(
                "mb_per_layer is 2 (every second layer a Mamba-kind mixer) "
                "or 0: the placement needs num_layers % 4 == 0, "
                "ssm_state_size and sliding_window, and one pass over "
                "pre-norm dense layers")
        # Layer N / 2 is the last scan, whose output is the memory; layer
        # N / 2 + 1 the last to project keys and values.
        half = n // 2
        mixers = [
            (SCAN if i <= half else MEMORY_GATE) if i % 2 == 0
            else (SELF_ATTENTION if i <= half + 1 else CROSS_ATTENTION)
            for i in range(n)]
        specs = [spec(
            i, mixer, DENSE,
            "sliding_attention" if i < half else "full_attention",
            reads={MEMORY_GATE: "memory", CROSS_ATTENTION: "kv"}.get(mixer),
            writes={half: "memory", half + 1: "kv"}.get(i))
            for i, mixer in enumerate(mixers)]
    elif cfg.linear_attn_config is not None:
        # Kimi Linear's published key: the layers of each mixer by their
        # 1-indexed position, and the delta-rule layers' sizes.
        sizes = dict(cfg.linear_attn_config)
        kda, full = (tuple(sizes.get(key, ())) for key in (
            "kda_layers", "full_attn_layers"))
        if (set(sizes) - {"kda_layers", "full_attn_layers", "num_heads",
                          "head_dim", "short_conv_kernel_size"}
                or sorted(kda + full) != list(range(1, n + 1))
                or min(sizes.get(key, 0) for key in (
                    "num_heads", "head_dim", "short_conv_kernel_size")) < 1):
            raise ValueError(
                f"linear_attn_config is {cfg.linear_attn_config!r}: pairs "
                f"(key, value) with kda_layers and full_attn_layers, which "
                f"between them name each of the layers 1 to {n} once, and "
                f"num_heads, head_dim and short_conv_kernel_size of the "
                f"delta-rule layers, each at least 1")
        routed_from = cfg.first_dense_layers if cfg.num_experts > 1 else n
        specs = [spec(i, KDA if i + 1 in kda else SELF_ATTENTION,
                      ROUTED if i >= routed_from else DENSE,
                      "linear_attention" if i + 1 in kda
                      else "full_attention") for i in range(n)]
    elif pattern is not None:
        if DENSE_LAYER in pattern:
            raise ValueError(
                f"hybrid_override_pattern {pattern!r} holds a dense "
                f"feed-forward layer ({DENSE_LAYER!r}): a layer that is "
                f"a dense MLP alone is not built")
        if len(pattern) != n or set(pattern) - set(PATTERN_KINDS):
            raise ValueError(
                f"hybrid_override_pattern is {pattern!r}: one of "
                f"{PATTERN_KINDS} for each of {n} layers")
        if cfg.norm_placement != "pre" or cfg.first_dense_layers:
            raise ValueError(
                "hybrid_override_pattern names every layer's ONE sublayer "
                "behind a pre-norm: norm_placement='post' and "
                "first_dense_layers do not go with it")
        if EXPERTS in pattern and cfg.num_experts < 2:
            raise ValueError("an 'E' layer needs num_experts > 1")
        sublayer = {MAMBA: (MAMBA2, None), EXPERTS: (None, ROUTED),
                    ATTENTION: (SELF_ATTENTION, None)}
        specs = [spec(i, *sublayer[kind], norms=ONE_NORM)
                 for i, kind in enumerate(pattern)]
    else:
        if types is not None and (len(types) != n
                                  or set(types) - set(LAYER_TYPES)):
            raise ValueError(
                f"layer_types is {types!r}: one of {LAYER_TYPES} for each "
                f"of {n} layers")
        routed_from = cfg.first_dense_layers if cfg.num_experts > 1 else n
        specs = [spec(i, TYPE_MIXERS[kind],
                      ROUTED if i >= routed_from else DENSE, kind)
                 for i, kind in enumerate(types or ("full_attention",) * n)]
        if DELTA_RULE in (types or ()) and (
                not (cfg.linear_num_key_heads and cfg.linear_key_head_dim
                     and cfg.linear_value_head_dim)
                or cfg.linear_num_value_heads % cfg.linear_num_key_heads):
            raise ValueError(
                "linear attention needs linear_num_key_heads, "
                "linear_key_head_dim, linear_value_head_dim and "
                "linear_num_value_heads, a multiple of the key heads")
        if SHORT_CONV in (types or ()) and cfg.conv_L_cache < 1:
            raise ValueError(f"conv_L_cache is {cfg.conv_L_cache}: a "
                             f"\"conv\" layer's filter has at least one tap")
    # A Mamba-2 layer's sizes, however the layer was named.
    if any(spec.mixer == MAMBA2 for spec in specs) and (
            not (cfg.mamba_num_heads and cfg.mamba_head_dim
                 and cfg.ssm_state_size)
            or cfg.mamba_num_heads % cfg.n_groups
            or cfg.mamba_num_heads * cfg.mamba_head_dim % cfg.n_groups):
        raise ValueError(
            "an 'M' layer (a pattern's) or a \"mamba\" layer (layer_types') "
            "needs mamba_num_heads, mamba_head_dim and ssm_state_size, the "
            "heads a multiple of n_groups")
    in_use = {spec.type for spec in specs}
    sliding = "sliding_attention" in in_use
    if sliding != (cfg.sliding_window is not None) or (
            sliding and (cfg.sliding_window < 1 or cfg.attention_kind
                         not in ("full", "differential"))):
        raise ValueError(
            f"sliding_window is {cfg.sliding_window!r} and layer_types "
            f"{types!r}: a window of at least 1 goes with "
            f"'sliding_attention' layers (or mb_per_layer's) of "
            f"attention_kind 'full' or 'differential', and with nothing "
            f"else")
    used = in_use - set(NO_SOFTMAX_TYPES)
    if cfg.rope_parameters is not None and (
            not used <= set(ropes) <= set(LAYER_TYPES) or any(
                not 0.0 < r.partial_rotary_factor <= 1.0
                or int(r.partial_rotary_factor * cfg.head_dim) % 2
                for r in ropes.values() if r is not None)):
        raise ValueError(
            f"rope_parameters names {sorted(ropes)}: an entry for "
            f"each softmax layer type in use ({sorted(used)}), each "
            f"turning a whole number of pairs of a head, or None (the "
            f"type's layers do not rotate)")
    if cfg.router_input == "layer" and not all(
            spec.mixer is not None for spec in specs if spec.ffn == ROUTED):
        raise ValueError(
            "router_input is 'layer': the router reads the input of a layer "
            "that is a mixer AND routed experts, ahead of the mixer; a "
            "routed layer of hybrid_override_pattern is that sublayer alone")
    return tuple(specs)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    """Sizes and options of ``LlamaModel``.

    What layer i IS is ``layers[i]``, a ``LayerSpec`` resolved from the
    fields below once a config (``_layer_specs``), which the model and the
    accessors (``kind_of`` ... ``rope_of``) read.  At most one of
    ``layer_types`` (the published key of hybrid stacks),
    ``hybrid_override_pattern`` (Nemotron-H's: a string or a tuple, one
    character a layer) and ``mb_per_layer`` (the decoder-hybrid-decoder
    stack's, SambaY, arXiv:2507.06607) names the layers' kinds; with none of
    them every layer is ``attention_kind``'s mixer and a feed-forward.  Each
    class's docstring has its arithmetic; this one, which key sets what.

    ``LayerSpec.mixer``.  The attention class is the stack's,
    ``attention_kind``: ``"full"`` (``LlamaAttention``); ``"latent"``
    (``LatentAttention``, DeepSeek-V2's MLA: ``kv_lora_rank``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim`` and ``v_head_dim`` are the
    published keys, ``num_kv_heads`` is not read, ``rope_scaling``, a
    ``YarnScaling``, sets the rotary frequencies and the softmax scale, and
    ``q_lora_rank`` (None: one matrix ``wq``) makes the queries from a latent
    of that width with a norm of its own, DeepSeek-V3's);
    with ``mla_use_nope`` (Kimi Linear's key) the ``qk_rope_head_dim`` shared
    lanes are NOT rotated, position coming from the other layers' recurrences:
    no layer of the stack turns and no table is made;
    ``"sparse"`` (``SparseAttention``, DeepSeek-V3.2-Exp's DSA: an indexer of
    ``index_heads`` heads of ``index_head_dim`` picks ``index_topk`` of each
    query's causal keys); ``"differential"`` (``DifferentialAttention``,
    arXiv:2410.05258, built for a stack with ``mb_per_layer``: adjacent heads
    pair and it does not rotate).  ``qk_norm`` puts an RMSNorm with a learned
    scale before the rotation (full and sparse) over what ``qk_norm_over``
    says: each ``"head"`` of q and k (a ``[head_dim]`` scale) or ``"all"``
    heads of a token together (OLMo 2's: a ``[heads * head_dim]`` scale).
    ``gating`` (``"per-head"`` or ``"elementwise"``, arXiv:2505.06708)
    multiplies the attention output by a sigmoid gate before W_o.
    A name in ``layer_types`` gives the layer its mixer: ``"full_attention"``
    and ``"sliding_attention"`` ``attention_kind``'s; ``"linear_attention"``
    a ``GatedDeltaNet`` (sized by the six ``linear_*`` keys); ``"conv"``
    (LFM2's name) a ``GatedShortConv`` of ``conv_L_cache`` taps (the filter's
    length, which is also what a decode cache would hold; ``conv_bias``,
    published false, would give its projections and filter a bias: true is
    refused until a configuration has it); ``"mamba"`` and ``"attention"``
    (Granite-4.0-H's names) a ``Mamba2`` sized by the keys below, each WITH a
    feed-forward behind a norm of its own, and ``attention_kind``'s.  A
    character of ``hybrid_override_pattern`` likewise: ``"M"`` a ``Mamba2``
    (``mamba_num_heads``, ``mamba_head_dim``, ``ssm_state_size``,
    ``n_groups``, ``conv_kernel``, ``chunk_size``), ``"*"``
    ``attention_kind``'s, ``"E"`` none; ``"-"``, a dense feed-forward alone,
    is refused.  ``mb_per_layer`` = 2 places a mixer by the layer's index i
    among N = ``num_layers`` (N % 4 = 0): even i a ``Mamba1`` selective scan
    (``ssm_state_size``, ``mamba_expand``, ``conv_kernel``) up to i = N / 2
    and a ``GatedMemory`` unit behind it; odd i attention over the layer's
    own keys up to i = N / 2 + 1 and cross-attention behind it.

    ``linear_attn_config`` (Kimi Linear's published key, as pairs ``(key,
    value)``) names every layer's mixer in place of ``layer_types``: the
    1-indexed ``kda_layers`` a ``KimiDeltaAttention`` of ``num_heads`` heads of
    ``head_dim`` | ``head_dim`` behind filters of ``short_conv_kernel_size``
    taps, the ``full_attn_layers`` ``attention_kind``'s; a feed-forward
    behind each as without it.

    ``LayerSpec.ffn``.  A dense ``SwiGLU`` of ``intermediate_size``; with
    ``num_experts`` > 1, from layer ``first_dense_layers`` on (under a
    pattern: in the ``"E"`` layers, which are that alone),
    ``RoutedExperts``, whose docstring says what ``experts_per_token``,
    ``moe_intermediate_size`` (0: ``intermediate_size``), ``shared_experts``,
    ``moe_shared_expert_intermediate_size``, ``shared_expert_gate``,
    ``norm_topk_prob``, ``routed_scaling_factor``, ``scoring_func``,
    ``topk_method``, ``router_bias_update_rate``, ``mlp_hidden_act``
    (``"silu"``: SwiGLU experts; ``"relu"``: the gated ReLU, ReGLU;
    ``"relu2"``: ``relu(.)`` squared and no gate) and ``router_input``
    (``"experts"``: the router reads what the experts read, the
    feed-forward's normed input; ``"layer"``: the LAYER's input, ahead of the
    mixer and its norm, SmallThinker's router before attention) make of it.
    ``held_experts`` > 0 says this program holds that many of the experts,
    ids ``first_held_expert`` onwards (one chip's share under expert
    parallelism): only they have weights here.  ``balance_over`` says
    over what a routed layer's balance loss counts its assignments: each
    ``"sequence"`` (DeepSeek-V2's ``seq_aux``) or the whole ``"batch"``.

    ``LayerSpec.heads``, ``window``, ``rope``, ``type``.
    ``num_attention_heads_per_layer`` (a tuple; None: ``num_heads``
    everywhere) gives each layer its own count of query heads over the same
    ``num_kv_heads``.  A layer of type ``"sliding_attention"`` (in
    ``layer_types``; the layers before N / 2 under ``mb_per_layer``) is a
    softmax layer of ``attention_kind`` ``"full"`` or ``"differential"`` whose
    query t attends the keys ``0 <= t - s < sliding_window`` (the published
    key; ``attention_fn`` is handed ``window=``).  ``rope_parameters`` (pairs
    ``(layer type, RopeParameters)``; None: ``rope_theta`` and
    ``rope_scaling`` for every layer) gives each layer type its own theta,
    YaRN scaling and ``partial_rotary_factor``, or None in place of the
    ``RopeParameters``: the layers of that type do not rotate beside those of
    a type that does (SmallThinker's global layers, NoPE, between window
    layers that turn); ``rope_theta`` None: no softmax layer rotates
    (position comes from the other layers' recurrences and convolutions).

    ``LayerSpec.norms``, ``reads``, ``writes``.  A layer is a mixer behind
    ``"norm_attn"`` and a feed-forward behind ``"norm_mlp"``, each residual;
    ``norm_placement`` is ``"pre"`` (each sublayer reads the normed state:
    ``x + Mixer(Norm(x))``) or ``"post"`` (OLMo 2's: the norm is on each
    sublayer's OUTPUT inside the residual, ``x + Norm(Mixer(x))``).  Under
    ``hybrid_override_pattern`` it is ONE sublayer behind one pre-norm
    (``"norm"``) with one residual add: ``num_layers`` then counts
    sublayers, and ``remat``'s ``layer`` policies checkpoint one sublayer.
    Under ``mb_per_layer`` layer N / 2's scan output is the MEMORY that every
    ``GatedMemory`` behind it reads, and layer N / 2 + 1 (the one full
    attention layer that projects) leaves its keys and values SHARED: every
    cross-attention behind it attends to them.  The norms themselves:
    ``zero_centered_norm`` makes every ``RMSNorm`` of the stack (the block
    norms, the final norm, the QK-norms; not the delta rule's output norm)
    ``x / rms(x) * (1 + scale)`` with the scale from zeros: under a weight
    decay on every leaf that is another model, the decay pulls the
    multiplier to 1 and not to 0.  ``layer_norm_eps`` (a float; None:
    ``RMSNorm`` with ``rms_eps``) makes every norm of the stack a
    ``LayerNorm`` with a scale and a bias; ``tie_word_embeddings`` makes the
    head the embedding's transpose.

    Granite's four multipliers, each read in one place and each an identity
    at its default, so that a config without them traces what it traced:
    ``embedding_multiplier`` multiplies the embedding's output
    (``LlamaModel.__call__``); ``attention_multiplier`` (None: ``head_dim **
    -0.5``) is the softmax's scale IN PLACE of that root, handed to
    ``attention_fn`` as ``scale=`` (``LlamaAttention.attend``;
    ``attention_kind`` ``"full"`` alone); ``residual_multiplier`` multiplies
    each sublayer's output before it is added to the stream (``LlamaLayer``:
    both sublayers of every layer); ``logits_scaling`` DIVIDES the logits
    (``LlamaModel.head``).

    The residual path.  ``hc_mult`` n (the published key of manifold-
    constrained hyper-connections, mHC, arXiv:2512.24880, on hyper-connections,
    arXiv:2409.19606) is the number of residual STREAMS.  1 is the plain
    decoder: one ``[B, S, H]`` state and ``x + F(N(x))``, with no parameter,
    no scope and no operation of the streams' in the trace (a test pins the
    jaxpr).  With n > 1 a token's state is X ``[n, H]``: the embedding is
    copied into the n streams, every sublayer F with its pre-norm N reads
    ``x_in = h_pre X`` and writes ``X' = H_res X + h_post^T F(N(x_in))`` under
    three maps of its OWN that it makes from X token by token
    (``HyperConnection``, whose docstring has the equations:
    ``hc_sinkhorn_iters`` steps project ``exp`` of the residual logits,
    clamped to ``hc_res_clamp``, onto the doubly stochastic matrices;
    ``hc_eps`` is the epsilon of the maps' RMS and of Sinkhorn's divisions),
    and behind the last layer the streams are summed ahead of the final norm
    (hyper-connections' own convention at both ends).  The layers carry
    ``[B, S, n, H]``, which is also what ``remat`` keeps of a layer.  The
    streams are wiring: ``layers`` says what each sublayer IS exactly as
    without them.  They go with pre-norm sublayers in one pass over a stack
    that shares nothing and routes on the feed-forward's input; the rest is
    refused until a configuration has it.

    The stack as a whole.  ``total_ut_steps`` (the published key of Ouro's
    ``config.json``) is the number of weight-shared passes over it.  1 is the
    plain decoder: one walk over the layers, logits out.  With T > 1 the same
    ``num_layers`` modules (one parameter set) are applied T times, the final
    norm ends every pass, an exit gate reads every pass's output, and the
    model returns the T normalised hidden states and gate logits in place of
    logits (``LlamaModel``; the loss is ``ops.losses.expected_exit_loss``).
    ``ring_attention`` as ``attention_fn`` and the MoE block are untested
    with T > 1.  ``remat`` names what the backward pass recomputes:
    ``"none"``; ``"layer"`` (each layer application keeps its input alone);
    ``"layer_keep_attention"`` (and the flash kernel's output and row
    statistics); ``"layer_keep_selection"`` (and a sparse layer's selection
    and indexer loss).  A linear-attention layer keeps its input alone under
    each of them (its rule's chunk states are made again: at the size they
    were built for there is no room to keep them, PERF.md section 4).  Four
    passes hold four times one pass's activations, so a looped model at a
    long sequence needs one of these.

    ``models/generation.py``, the serve plane and the pipelined step keep a
    decoder layer of their own, the plain one: full attention that rotates,
    normed a head if at all, and a dense SwiGLU, both pre-norm, in one pass
    (a served looped model would keep a cache a pass; the pipelined step
    walks the stack once).  They refuse T > 1 and, by name, every other
    value of the fields above (``refuse_new_kinds``)."""

    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    intermediate_size: int = 11008
    max_seq_len: int = 8192
    rope_theta: Optional[float] = 10000.0     # None: no rotation
    rms_eps: float = 1e-5
    num_experts: int = 1          # >1 enables routed layers
    experts_per_token: int = 2
    held_experts: int = 0         # 0: all of them
    first_held_expert: int = 0
    moe_intermediate_size: int = 0
    shared_experts: int = 0
    first_dense_layers: int = 0
    norm_topk_prob: bool = True
    balance_over: str = "sequence"
    attention_kind: str = "full"
    attention_head_dim: int = 0   # 0: hidden_size / num_heads
    qk_norm: bool = False
    qk_norm_over: str = "head"
    norm_placement: str = "pre"
    layer_types: Optional[tuple] = None
    sliding_window: Optional[int] = None
    num_attention_heads_per_layer: Optional[tuple] = None
    gating: Optional[str] = None              # "per-head", "elementwise"
    rope_parameters: Optional[tuple] = None   # ((layer type, RopeParameters),)
    routed_scaling_factor: float = 1.0
    zero_centered_norm: bool = False
    shared_expert_gate: bool = False
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = False
    conv_L_cache: int = 3         # taps of a "conv" layer's filter
    conv_bias: bool = False
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    q_lora_rank: Optional[int] = None     # None: no query latent
    mla_use_nope: bool = False            # latent attention does not rotate
    linear_attn_config: Optional[tuple] = None    # ((key, value), ..)
    rope_scaling: Optional[YarnScaling] = None
    hc_mult: int = 1              # residual streams; 1: the plain decoder
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: tuple = (-30.0, 30.0)   # of the residual map's logits
    hybrid_override_pattern: Optional[str] = None     # "MEMEM*E.." or a tuple
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    n_groups: int = 1             # of a Mamba-2 layer's B and C
    conv_kernel: int = 4
    chunk_size: int = 128
    scoring_func: str = "softmax"                 # or "sigmoid"
    topk_method: str = "greedy"                   # or "noaux_tc"
    mlp_hidden_act: str = "silu"      # or "relu", "relu2" (the experts')
    router_input: str = "experts"     # or "layer": the layer's own input
    moe_shared_expert_intermediate_size: int = 0  # 0: shared_experts x F
    router_bias_update_rate: float = 1e-3
    mb_per_layer: int = 0         # 2: a decoder-hybrid-decoder stack
    mamba_expand: int = 2         # a Mamba-1 layer's channels over hidden
    layer_norm_eps: Optional[float] = None    # a float: LayerNorm, not RMS
    tie_word_embeddings: bool = False
    embedding_multiplier: float = 1.0     # Granite's four; identities here
    attention_multiplier: Optional[float] = None      # None: 1 / sqrt(D)
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    dtype: Any = jnp.bfloat16
    # Output-head compute dtype.  bf16 keeps every logits-sized tensor —
    # the forward residual AND the cross-entropy cotangent, 2 GB each in
    # f32 at B=8/S=2048/V=32k — in half the bytes; the loss
    # (ops/losses.py) upcasts per-tile inside its reductions, so lse and
    # loss stay f32-accurate.  Set to jnp.float32 to save f32 logits.
    logits_dtype: Any = jnp.bfloat16
    total_ut_steps: int = 1
    remat: str = "none"

    def __post_init__(self):
        if self.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps is {self.total_ut_steps}: "
                             f"at least one pass over the stack")
        if self.remat != "none" and self.remat not in REMAT_POLICIES:
            raise ValueError(f"remat is {self.remat!r}: 'none' or one of "
                             f"{sorted(REMAT_POLICIES)}")
        if self.attention_kind not in ATTENTION_KINDS:
            raise ValueError(f"attention_kind is {self.attention_kind!r}: "
                             f"'full', 'latent' or 'sparse', or "
                             f"'differential' in a stack with mb_per_layer")
        if self.attention_kind == "sparse" and not (
                self.index_heads and self.index_head_dim
                and self.index_topk):
            raise ValueError("sparse attention needs index_heads, "
                             "index_head_dim and index_topk")
        if self.balance_over not in ("sequence", "batch"):
            raise ValueError(f"balance_over is {self.balance_over!r}: "
                             f"'sequence' or 'batch'")
        if self.attention_kind == "latent" and not (
                self.kv_lora_rank and self.qk_nope_head_dim
                and self.qk_rope_head_dim and self.v_head_dim):
            raise ValueError("latent attention needs kv_lora_rank, "
                             "qk_nope_head_dim, qk_rope_head_dim, v_head_dim")
        if self.q_lora_rank is not None and (
                self.attention_kind != "latent" or self.q_lora_rank < 1):
            raise ValueError(
                f"q_lora_rank is {self.q_lora_rank!r}: the width of latent "
                f"attention's query latent (attention_kind='latent'), or "
                f"None")
        if self.mla_use_nope and (
                self.attention_kind != "latent" or self.rope_scaling
                is not None or self.rope_parameters is not None):
            raise ValueError(
                "mla_use_nope leaves latent attention's shared lanes "
                "unrotated (attention_kind='latent'): a rotation's scaling "
                "(rope_scaling, rope_parameters) does not go with it")
        low, high = self.hc_res_clamp
        if self.hc_mult < 1 or (self.hc_mult > 1 and (
                self.hc_sinkhorn_iters < 1 or not self.hc_eps > 0
                or not low < high)):
            raise ValueError(
                f"hc_mult is {self.hc_mult}: at least one residual stream, "
                f"and more than one with hc_sinkhorn_iters >= 1, hc_eps > 0 "
                f"and hc_res_clamp (low, high), low < high")
        if self.hc_mult > 1 and (
                self.norm_placement != "pre" or self.total_ut_steps != 1
                or self.mb_per_layer or self.router_input != "experts"):
            raise ValueError(
                f"hc_mult is {self.hc_mult}: residual streams are built "
                f"around pre-norm sublayers (x_in = h_pre X is what the norm "
                f"reads) in one pass over a stack whose layers share nothing "
                f"and whose routers read the feed-forward's input; "
                f"norm_placement='post', total_ut_steps > 1, mb_per_layer "
                f"and router_input='layer' do not go with them; not built")
        if not 0 <= self.first_held_expert <= (
                self.num_experts - self.experts_held):
            raise ValueError(
                f"experts {self.first_held_expert} to "
                f"{self.first_held_expert + self.experts_held - 1} are not "
                f"among {self.num_experts}")
        if self.norm_placement not in ("pre", "post"):
            raise ValueError(f"norm_placement is {self.norm_placement!r}: "
                             f"'pre' or 'post'")
        if self.qk_norm_over not in ("head", "all"):
            raise ValueError(f"qk_norm_over is {self.qk_norm_over!r}: "
                             f"'head' or 'all'")
        if self.conv_bias:
            raise ValueError(
                "conv_bias is True: a \"conv\" layer's projections and filter "
                "are built without a bias (LFM2 publishes false); not built")
        if self.gating not in (None, "per-head", "elementwise"):
            raise ValueError(f"gating is {self.gating!r}: 'per-head', "
                             f"'elementwise' or None")
        if self.gating == "elementwise" and self.attention_kind == "latent":
            raise ValueError("the element-wise gate is a half of a full or "
                             "sparse attention layer's wq; latent attention "
                             "has no such projection")
        if self.shared_expert_gate and not (self.num_experts > 1
                                            and self.shared_experts):
            raise ValueError("shared_expert_gate gates shared experts: "
                             "num_experts > 1 and shared_experts >= 1")
        if self.scoring_func not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring_func is {self.scoring_func!r}: "
                             f"'softmax' or 'sigmoid'")
        if self.topk_method not in ("greedy", "noaux_tc"):
            raise ValueError(f"topk_method is {self.topk_method!r}: 'greedy' "
                             f"or 'noaux_tc' (a bias corrects the choice)")
        if self.attention_multiplier is not None and (
                self.attention_kind != "full"):
            raise ValueError(
                f"attention_multiplier is {self.attention_multiplier}: the "
                f"softmax scale of attention_kind 'full' alone "
                f"({self.attention_kind!r} sets its own)")
        if self.mlp_hidden_act not in ("silu", "relu", "relu2"):
            raise ValueError(f"mlp_hidden_act is {self.mlp_hidden_act!r}: "
                             f"'silu' or 'relu' (gated) or 'relu2' (not "
                             f"gated)")
        if self.mlp_hidden_act == "relu" and (
                self.num_experts < 2 or self.shared_experts
                or self.first_dense_layers):
            raise ValueError(
                "mlp_hidden_act 'relu' gates the routed experts' products: a "
                "shared expert or a dense layer beside them would be a "
                "SiLU-gated SwiGLU (shared_experts and first_dense_layers "
                "are 0 with it, num_experts > 1); not built")
        if self.router_input not in ("experts", "layer") or (
                self.router_input == "layer" and self.num_experts < 2):
            raise ValueError(
                f"router_input is {self.router_input!r}: 'experts' (the "
                f"router reads what the experts read) or, with num_experts "
                f"> 1, 'layer' (the layer's input, ahead of its mixer)")
        # What each layer is, decided (or refused) once.  Not a field: configs
        # are equal, and ``dataclasses.replace`` copies, by the fields alone.
        object.__setattr__(self, "layers", _layer_specs(self))

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(vocab_size=128256, hidden_size=4096, num_layers=32,
                           num_heads=32, num_kv_heads=8,
                           intermediate_size=14336, max_seq_len=8192,
                           rope_theta=500000.0)

    @staticmethod
    def tiny(num_experts: int = 1) -> "LlamaConfig":
        """CI-sized config for tests, dry runs, and compile checks."""
        return LlamaConfig(vocab_size=512, hidden_size=64, num_layers=2,
                           num_heads=4, num_kv_heads=2, intermediate_size=128,
                           max_seq_len=256, num_experts=num_experts)

    @property
    def head_dim(self) -> int:
        return self.attention_head_dim or self.hidden_size // self.num_heads

    @property
    def rope_dim(self) -> int:
        """Width of what the rotary positions turn in a head."""
        return (self.qk_rope_head_dim if self.attention_kind == "latent"
                else self.head_dim)

    @property
    def experts_held(self) -> int:
        return self.held_experts or self.num_experts

    def _holds(self, mixer: str) -> bool:
        return any(spec.mixer == mixer for spec in self.layers)

    @property
    def layers_share(self) -> bool:
        """Whether some layer reads what another left beside its output
        (``LayerSpec.reads``, ``writes``): ``shared`` goes layer to layer."""
        return any(spec.reads or spec.writes for spec in self.layers)

    def kind_of(self, layer: int) -> Optional[str]:
        """``layer``'s one sublayer under ``hybrid_override_pattern``'s name
        for it (``"M"``, ``"E"`` or ``"*"``); None: a mixer and a
        feed-forward."""
        spec = self.layers[layer]
        return {MAMBA2: MAMBA, SELF_ATTENTION: ATTENTION, None: EXPERTS}[
            spec.mixer] if spec.norms == ONE_NORM else None

    def is_routed(self, layer: int) -> bool:
        return self.layers[layer].ffn == ROUTED

    @property
    def mamba_inner(self) -> int:
        """Channels of a Mamba-2 layer's u, z and output: heads x width."""
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def has_linear_layers(self) -> bool:
        return self._holds(DELTA_RULE)

    def is_linear(self, layer: int) -> bool:
        """Whether ``layer``'s mixer is the gated delta rule."""
        return self.layers[layer].mixer == DELTA_RULE

    @property
    def has_kda_layers(self) -> bool:
        return self._holds(KDA)

    def is_kda(self, layer: int) -> bool:
        """Whether ``layer``'s mixer is Kimi Delta Attention."""
        return self.layers[layer].mixer == KDA

    @property
    def has_conv_layers(self) -> bool:
        return self._holds(SHORT_CONV)

    def is_conv(self, layer: int) -> bool:
        """Whether ``layer``'s mixer is the double-gated short convolution."""
        return self.layers[layer].mixer == SHORT_CONV

    def layer_type(self, layer: int) -> str:
        return self.layers[layer].type

    def mixer_of(self, layer: int) -> Optional[str]:
        """``layer``'s mixer in a stack whose layers share tensors (one of
        ``SHARING_MIXERS``); None: the stack shares nothing."""
        return self.layers[layer].mixer if self.layers_share else None

    @property
    def scan_inner(self) -> int:
        """Channels of a Mamba-1 layer's u, z, memory and output."""
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        """Rank of a Mamba-1 layer's step projection: Mamba's ``"auto"``."""
        return -(-self.hidden_size // 16)

    def heads_of(self, layer: int) -> int:
        """Query heads of ``layer``'s softmax mixer."""
        return self.layers[layer].heads

    def window_of(self, layer: int) -> Optional[int]:
        """The keys a query of ``layer`` sees behind it, itself included;
        None: all of them."""
        return self.layers[layer].window

    def rope_of(self, layer: int) -> Optional[RopeParameters]:
        """The rotation of ``layer``'s q and k; None: they do not turn (a
        linear layer has no entry in ``rope_parameters``)."""
        return self.layers[layer].rope

    def refuse_new_kinds(self, who: str) -> None:
        """For the paths that keep a decoder layer of their own and have
        learned neither kind (ROADMAP.md D1): raise, naming the kind."""
        if self.layers_share:
            half = self.num_layers // 2
            raise NotImplementedError(
                f"{who} has no path for a decoder-hybrid-decoder stack "
                f"(mb_per_layer={self.mb_per_layer}): its cache would hold "
                f"a [{self.scan_inner}, {self.ssm_state_size}] float32 "
                f"state and the filter's last {self.conv_kernel - 1} inputs "
                f"for each of the {half // 2 + 1} Mamba-1 layers, a window "
                f"of {self.sliding_window} tokens for the layers before "
                f"{half}, ONE set of keys and values (layer {half + 1}'s) "
                f"for all {half // 2} layers that read it by "
                f"cross-attention, and a prefill would end at layer {half}'s "
                f"memory for the gated memory units; with differential "
                f"attention a decode step would attend twice a head pair, "
                f"keys {self.head_dim} wide and values {2 * self.head_dim}, "
                f"subtract under lambda and norm the pair's lanes; not built")
        if self.layer_norm_eps is not None:
            raise NotImplementedError(
                f"{who} has no path for LayerNorm (layer_norm_eps="
                f"{self.layer_norm_eps}): its layers norm by the root mean "
                f"square alone, without a mean or a bias; not built")
        if self.tie_word_embeddings:
            raise NotImplementedError(
                f"{who} has no path for a head tied to the embedding "
                f"(tie_word_embeddings=True): it keeps an lm_head of its "
                f"own [{self.hidden_size}, {self.vocab_size}]; not built")
        if self.has_kda_layers:
            sizes = dict(self.linear_attn_config)
            raise NotImplementedError(
                f"{who} has no path for Kimi Delta Attention layers "
                f"(linear_attn_config names kda_layers): such a layer has no "
                f"keys or values to cache, its cache would hold a recurrent "
                f"state of {sizes['head_dim']} x {sizes['head_dim']} float32 "
                f"for each of {sizes['num_heads']} heads, decayed a key "
                f"channel at a time, and the three filters' last "
                f"{sizes['short_conv_kernel_size'] - 1} inputs, and a decode "
                f"step would update them in place; not built")
        if self.mla_use_nope:
            raise NotImplementedError(
                f"{who} has no path for latent attention that does not "
                f"rotate (mla_use_nope=True): it turns q and k by one table "
                f"in every layer, and its cache would hold the "
                f"{self.kv_lora_rank}-wide latent beside "
                f"{self.qk_rope_head_dim} shared lanes kept unturned; not "
                f"built")
        if self.has_conv_layers:
            raise NotImplementedError(
                f"{who} has no path for double-gated short-convolution "
                f"layers (layer_types holds 'conv'): such a layer has no keys "
                f"or values, its cache would hold the filter's last "
                f"{self.conv_L_cache - 1} gated inputs B z, "
                f"{self.hidden_size} wide, and a decode step would shift "
                f"them in place; not built")
        pattern = self.hybrid_override_pattern
        if self._holds(MAMBA2):
            named = (f"hybrid_override_pattern holds {MAMBA!r}"
                     if pattern is not None else "layer_types holds 'mamba'")
            raise NotImplementedError(
                f"{who} has no path for Mamba-2 state-space layers "
                f"({named}): beside K and V "
                f"its cache would hold a [{self.mamba_head_dim}, "
                f"{self.ssm_state_size}] float32 state for each of "
                f"{self.mamba_num_heads} heads and the filter's last "
                f"{self.conv_kernel - 1} inputs, and a decode step would "
                f"update them in place; not built")
        if self.topk_method == "noaux_tc":
            raise NotImplementedError(
                f"{who} has no path for a bias-corrected router "
                f"(topk_method='noaux_tc'): it would read the "
                f"[{self.num_experts}] choice bias from a collection beside "
                f"the parameters and never update it; not built")
        if pattern is not None:
            raise NotImplementedError(
                f"{who} has no path for a layer pattern "
                f"(hybrid_override_pattern={pattern!r}): its layer is "
                f"always a mixer AND a feed-forward with two norms, and a "
                f"stage's layers share one shape; not built")
        if self.hc_mult > 1:
            raise NotImplementedError(
                f"{who} has no path for residual streams (hc_mult="
                f"{self.hc_mult}): its layer adds a sublayer's output to ONE "
                f"[B, S, {self.hidden_size}] state; it would carry "
                f"{self.hc_mult} streams a token, make three maps a sublayer "
                f"from them ({self.hc_sinkhorn_iters} Sinkhorn steps), read "
                f"and rewrite the streams around attention and the "
                f"feed-forward, and a decode step or a pipeline stage would "
                f"hand on {self.hc_mult} times the state; not built")
        if self.q_lora_rank is not None:
            raise NotImplementedError(
                f"{who} has no path for a query latent (q_lora_rank="
                f"{self.q_lora_rank}): its layer makes q by one matrix wq; "
                f"it would project x to {self.q_lora_rank} lanes, norm them "
                f"and project up to the heads, beside latent attention's "
                f"keys and values, which it has no path for either; not "
                f"built")
        if self.attention_kind == "latent":
            raise NotImplementedError(
                f"{who} has no path for latent attention "
                f"(attention_kind='latent'): its cache would hold the "
                f"{self.kv_lora_rank}-wide latent and the shared rotary "
                f"key, and prefill and decode differ; not built")
        if self.attention_kind == "sparse":
            raise NotImplementedError(
                f"{who} has no path for learned sparse attention "
                f"(attention_kind='sparse'): its cache would hold the "
                f"indexer's {self.index_head_dim}-wide key a token beside "
                f"K and V, and a decode step would pick {self.index_topk} "
                f"of the cached keys before it attends; not built")
        if self.router_input != "experts":
            raise NotImplementedError(
                f"{who} has no path for a router ahead of the mixer "
                f"(router_input={self.router_input!r}): its layer would "
                f"score the [{self.num_experts}] experts from the residual "
                f"stream as the layer receives it and carry the choice past "
                f"attention to the feed-forward; not built")
        if self.mlp_hidden_act == "relu":
            raise NotImplementedError(
                f"{who} has no path for ReLU-gated experts "
                f"(mlp_hidden_act='relu'): its feed-forward is a SiLU-gated "
                f"SwiGLU in every layer; not built")
        if self.shared_expert_gate:
            raise NotImplementedError(
                f"{who} has no path for a gated shared expert "
                f"(shared_expert_gate=True): it runs a dense feed-forward in "
                f"every layer, and none of them scales a SwiGLU's output by "
                f"a sigmoid of its input; not built")
        if self.num_experts > 1:
            raise NotImplementedError(
                f"{who} has no path for routed experts (num_experts="
                f"{self.num_experts}): it runs a dense feed-forward in "
                f"every layer")
        if self.has_linear_layers:
            raise NotImplementedError(
                f"{who} has no path for gated delta-rule linear attention "
                f"(layer_types holds 'linear_attention'): beside K and V "
                f"its cache would hold a recurrent state of "
                f"{self.linear_value_head_dim} x {self.linear_key_head_dim} "
                f"a head and the convolutions' last "
                f"{self.linear_conv_kernel_dim - 1} inputs, and a decode "
                f"step would update them in place; not built")
        if self.sliding_window is not None:
            raise NotImplementedError(
                f"{who} has no path for sliding-window layers (layer_types "
                f"holds 'sliding_attention', sliding_window="
                f"{self.sliding_window}): such a layer's cache would hold "
                f"its last {self.sliding_window} tokens alone, a geometry "
                f"of its own beside the full layers', and prefill would "
                f"mask the band; not built")
        if self.num_attention_heads_per_layer is not None:
            raise NotImplementedError(
                f"{who} has no path for a head count a layer "
                f"(num_attention_heads_per_layer="
                f"{self.num_attention_heads_per_layer}): its layers share "
                f"one shape of q and one stage's weights; not built")
        if self.gating == "elementwise":
            raise NotImplementedError(
                f"{who} has no path for the element-wise output gate "
                f"(gating='elementwise'): its layer's wq would be twice as "
                f"wide, a query and a gate of {self.head_dim} lanes a head, "
                f"and the attention output multiplied by the gate's sigmoid "
                f"lane by lane before W_o; not built")
        if self.gating is not None:
            raise NotImplementedError(
                f"{who} has no path for the {self.gating} output gate "
                f"(gating={self.gating!r}): its layer would project a gate "
                f"a head from the normed state and scale the attention "
                f"output before W_o; not built")
        multipliers = {
            name: getattr(self, name) for name, identity in (
                ("embedding_multiplier", 1.0), ("attention_multiplier", None),
                ("residual_multiplier", 1.0), ("logits_scaling", 1.0))
            if getattr(self, name) != identity}
        if multipliers:
            raise NotImplementedError(
                f"{who} has no path for Granite's multipliers "
                f"({multipliers}): its layer adds a sublayer's output as it "
                f"is, scales the scores by 1 / sqrt(head_dim), and neither "
                f"scales the embedding nor divides the logits; not built")
        if self.zero_centered_norm:
            raise NotImplementedError(
                f"{who} has no path for zero-centred norms "
                f"(zero_centered_norm=True): its layers multiply the "
                f"normed state by a scale from ones, not by 1 + a scale "
                f"from zeros; not built")
        still = [kind for kind, rope in self.rope_parameters or ()
                 if rope is None]
        if still:
            raise NotImplementedError(
                f"{who} has no path for a layer type that does not rotate "
                f"beside one that does (rope_parameters gives {still} None): "
                f"it turns q and k by one table in every layer, and a cached "
                f"key of such a layer would be kept unturned; not built")
        if self.rope_parameters is not None:
            raise NotImplementedError(
                f"{who} has no path for a rotation a layer type or a "
                f"partial one (rope_parameters names "
                f"{[kind for kind, _ in self.rope_parameters]}): it builds "
                f"one table for all layers and turns whole heads; not built")
        if (self.norm_placement != "pre" or self.qk_norm_over != "head"
                or self.rope_theta is None):
            raise NotImplementedError(
                f"{who} keeps a pre-norm layer of its own that rotates q "
                f"and k and norms them a head (norm_placement="
                f"{self.norm_placement!r}, qk_norm_over="
                f"{self.qk_norm_over!r}, rope_theta={self.rope_theta!r} "
                f"have no path there)")


class RMSNorm(nn.Module):
    """``x / rms(x) * scale`` in float32, the scale from ones; with
    ``zero_centered`` (``LlamaConfig.zero_centered_norm``) ``x / rms(x) * (1
    + scale)``, the scale from zeros: the same function at initialisation,
    another parameter under weight decay.  A stack's block norms and final
    norm are this one unless its config states ``layer_norm_eps``
    (``LayerNorm``, ``_stack_norm``)."""

    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    zero_centered: bool = False

    @nn.compact
    def __call__(self, x, scale_alone: bool = False):
        scale = self.param("scale", nn.initializers.zeros if
                           self.zero_centered else nn.initializers.ones,
                           (x.shape[-1],))
        if self.zero_centered:
            scale = 1.0 + scale
        if scale_alone:     # for a caller that norms in a pass of its own
            return scale
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1,
                                           keepdims=True) + self.eps)
        return (x32 * scale).astype(self.dtype)


class LayerNorm(nn.Module):
    """``(x - mean(x)) / sqrt(var(x) + eps) * scale + bias`` in float32, one
    rounding out; the scale from ones, the bias from zeros: the norm of a
    stack whose config states ``layer_norm_eps``."""

    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1,
                                           keepdims=True) + self.eps)
        return (x32 * scale + bias).astype(self.dtype)


def _stack_norm(cfg: "LlamaConfig", name: str, **kwargs):
    """The norm of a block, or the final one, as ``cfg`` says: ``LayerNorm``
    where it states ``layer_norm_eps``, else ``RMSNorm``."""
    if cfg.layer_norm_eps is not None:
        return LayerNorm(cfg.layer_norm_eps, cfg.dtype, name=name, **kwargs)
    return RMSNorm(cfg.rms_eps, cfg.dtype, cfg.zero_centered_norm, name=name,
                   **kwargs)


def rope_freqs(head_dim: int, seq_len: int, theta: float, offset=0,
               scaling: Optional[YarnScaling] = None,
               rotary_dim: Optional[int] = None
               ) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables [S, head_dim/2] in fp32.  ``offset`` may be a traced
    value (sequence-parallel shards pass ``axis_index * S_local``).

    With ``rotary_dim`` < ``head_dim`` (``partial_rotary_factor``) only the
    first ``rotary_dim`` lanes of a head turn, by frequencies (and YaRN
    ranges) reckoned over that width; the other pairs' rows of the tables
    are the identity, cos 1 and sin 0, which ``apply_rope`` and
    ``ops/rope.py``'s pass turn into the lanes' own bits (x * 1 - y * 0 in
    float32, one rounding back), so one table and one pass serve a head
    that rotates by half.

    With ``scaling`` (YaRN; Peng et al., arXiv:2309.00071, as DeepSeek-V2
    applies it): pair i turns at ``f_i = theta^(-2i/d)`` below
    ``low``, at ``f_i / factor`` above ``high`` and at a linear blend of
    the two between (``YarnScaling.correction_range``), and the tables are
    multiplied by ``table_scale``."""
    still = 0
    if rotary_dim is not None and rotary_dim != head_dim:
        still, head_dim = (head_dim - rotary_dim) // 2, rotary_dim
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    if scaling is not None:
        low, high = scaling.correction_range(head_dim, theta)
        ramp = jnp.clip(
            (jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
            / max(high - low, 1e-3), 0.0, 1.0)
        inv = inv / scaling.factor * ramp + inv * (1.0 - ramp)
    t = jnp.arange(seq_len, dtype=jnp.float32) + offset
    ang = jnp.outer(t, inv)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None and scaling.table_scale != 1.0:
        cos, sin = cos * scaling.table_scale, sin * scaling.table_scale
    if still:
        cos = jnp.pad(cos, ((0, 0), (0, still)), constant_values=1.0)
        sin = jnp.pad(sin, ((0, 0), (0, still)))
    return cos, sin


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array, *,
               in_place: bool = False, scale=None, eps=None) -> jax.Array:
    """Rotate pairs (x[..., ::2], x[..., 1::2]).  x: [B, S, H, D].  With
    ``scale [D]`` and ``eps`` each head is normed first, ``RMSNorm``'s
    arithmetic with that (effective) scale.

    ``ops/rope.py::rotate``: in ``jnp``, any width and any partitioning,
    unless ``in_place`` says that the trace may hold Mosaic calls on operands
    where they lie (q and k on their way to an ``attention_fn`` that reads
    them where the projections left them, ``_reads_in_place``) and the shape
    is one its Mosaic pass takes: the norm then joins the rotation's pass;
    the same bits either way, up to the order of the norm's float32 sum.  A
    Mosaic call is the caller's choice, as the flash kernel is: the
    partitioner cannot split one, so under a plain ``jit`` over several chips
    it needs a ``shard_map`` around it."""
    return _rope.rotate(x, cos, sin, in_place, scale, eps)


def _reads_in_place(attention_fn) -> bool:
    """Whether an ``attention_fn`` says that it hands q, k and v to Mosaic
    calls in the projections' own layout: an attribute ``in_place`` of the
    function, or of the one a ``functools.partial`` wraps
    (``ops/flash_attention.py::flash_attention_fn`` has it).  Only then is
    the rotation before it a Mosaic pass too: the model's own dense
    attention, ring attention and a caller's closure keep the ``jnp`` form,
    which the partitioner can split."""
    return bool(getattr(getattr(attention_fn, "func", attention_fn),
                        "in_place", False))


def causal_attention(q, k, v, *, q_offset: int = 0,
                     scale: Optional[float] = None, selected=None,
                     window: Optional[int] = None):
    """Default causal attention, fp32 logits, GQA-aware.

    q: [B, Sq, Hq, D]; k: [B, Sk, Hkv, D]; v: [B, Sk, Hkv, Dv] with
    Hq % Hkv == 0.
    ``q_offset``: global position of q[0] (for decode / sequence shards).
    ``scale`` multiplies the scores in place of ``1 / sqrt(D)``.
    ``selected [B, Sq, Sk]`` (nonzero = in) keeps those of a query's
    causal keys alone, for every head, and makes the result ``(out, lse
    [B, Hq, Sq])``, the log-sum-exp of each query's kept scores with no
    gradient: the seam's contract for learned sparse attention.
    ``window`` keeps the keys ``0 <= q_pos - k_pos < window`` alone (a
    sliding-window layer's band).
    """
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, group, D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    if scale is None:
        logits = logits / jnp.sqrt(jnp.asarray(D, jnp.float32))
    else:
        logits = logits * scale
    q_pos = jnp.arange(Sq) + q_offset
    k_pos = jnp.arange(k.shape[1])
    mask = q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
    mask = mask[None, None, None]
    if selected is not None:
        mask = mask & (selected != 0)[:, None, None]
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    out = out.reshape(B, Sq, Hq, v.shape[-1])
    if selected is None:
        return out
    lse = jax.nn.logsumexp(logits, axis=-1).reshape(B, Hq, Sq)
    return out, jax.lax.stop_gradient(lse)


class _Kernel(nn.Module):
    """A matrix under ``<name>/kernel``, where ``nn.Dense`` keeps its own,
    for a caller that multiplies by parts of it."""

    shape: tuple
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.lecun_normal(),
                          self.shape).astype(self.dtype)


class LlamaAttention(nn.Module):
    """Softmax attention over grouped-query heads.  What differs by layer
    the config says of the layer's ``index`` (``heads_of``, ``window_of``:
    views of its ``LayerSpec``): the count of query heads and the window its
    queries see (handed to ``attention_fn`` as ``window=``, under
    ``hvd.attn.window``); ``cos``, ``sin`` are the layer's own tables.  With
    ``gating`` ``"per-head"`` the gate ``sigmoid(x W_g)`` a head multiplies
    the attention's output (under ``hvd.attn.gate``).

    With ``gating`` ``"elementwise"`` ``wq`` is ``[hidden, heads * 2 * D]``,
    a head's columns its query and then its gate, and the output is
    multiplied by ``sigmoid(gate)`` lane by lane (under ``hvd.attn.gate``
    too).  x is multiplied by the query's columns and by the gate's apart
    (the weight is taken apart, 2 x ``hidden x heads x D``): each product
    then leaves ``[B, S, heads * D]`` where the flash calls and the multiply
    read it, and no activation is relaid to split a head's 2 D lanes."""

    config: LlamaConfig
    attention_fn: Callable = staticmethod(causal_attention)
    index: int = 0
    in_place: bool = False      # ``LlamaLayer``'s reading of attention_fn

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        B, S, _ = x.shape
        D = cfg.head_dim
        heads, window = cfg.heads_of(self.index), cfg.window_of(self.index)
        if cfg.gating == "elementwise":
            wq = _Kernel((x.shape[-1], heads * 2 * D), cfg.dtype,
                         name="wq")().reshape(-1, heads, 2, D)
            x_q = x.astype(cfg.dtype)
            q = jnp.dot(x_q, wq[:, :, 0].reshape(-1, heads * D))
            q = q.reshape(B, S, heads, D)
        else:
            q = nn.Dense(heads * D, use_bias=False, dtype=cfg.dtype,
                         name="wq")(x).reshape(B, S, heads, D)
        k = nn.Dense(cfg.num_kv_heads * D, use_bias=False, dtype=cfg.dtype,
                     name="wk")(x).reshape(B, S, cfg.num_kv_heads, D)
        v = nn.Dense(cfg.num_kv_heads * D, use_bias=False, dtype=cfg.dtype,
                     name="wv")(x).reshape(B, S, cfg.num_kv_heads, D)

        def norm(name):
            return RMSNorm(cfg.rms_eps, cfg.dtype, cfg.zero_centered_norm,
                           name=name)

        def normed_and_turned(x, name):
            """q or k normed as the config says and turned by the tables:
            a per-head norm goes to the rotation as its scale, so that the
            two can be one pass over x (``ops/rope.py::rotate``)."""
            scale = None
            if cfg.qk_norm and cfg.qk_norm_over == "all":
                _trace_counts.note(_rope.BODY, _rope.NORM_OVER_ALL)
                x = norm(name)(x.reshape(B, S, -1)).reshape(x.shape)
            elif cfg.qk_norm:
                scale = norm(name)(x, scale_alone=True)
            if cos is None and scale is None:
                return x
            return apply_rope(x, cos, sin, in_place=self.in_place,
                              scale=scale, eps=cfg.rms_eps)

        with (_scopes.scope(_scopes.ATTN_WINDOW) if window is not None
              else contextlib.nullcontext()):
            with (_scopes.scope(_scopes.QK_NORM) if cfg.qk_norm
                  else contextlib.nullcontext()):
                q = normed_and_turned(q, "q_norm")
                k = normed_and_turned(k, "k_norm")
            out = self.attend(x, q, k, v, cos, sin)
        out = out.reshape(B, S, heads * D)
        if cfg.gating == "per-head":
            with _scopes.scope(_scopes.ATTN_GATE):
                out = _gated_heads(out, nn.Dense(
                    heads, use_bias=False, dtype=cfg.dtype, name="wg")(x))
        elif cfg.gating == "elementwise":
            with _scopes.scope(_scopes.ATTN_GATE):
                out = _gated_lanes(out, jnp.dot(
                    x_q, wq[:, :, 1].reshape(-1, heads * D)))
        return nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                        name="wo")(out)

    def attend(self, x, q, k, v, cos, sin):
        # (A keyword only where the config states it: a caller's
        # ``attention_fn`` need take neither.)
        stated = {"window": self.config.window_of(self.index),
                  "scale": self.config.attention_multiplier}
        return self.attention_fn(q, k, v, **{
            name: value for name, value in stated.items()
            if value is not None})


def _gated_lanes(out, logits):
    """``out * sigmoid(logits)``, both ``[B, S, heads * D]``: the sigmoid in
    float32 and rounded once to the dtype of out, as ``_gated_heads``."""
    return out * jax.nn.sigmoid(logits.astype(jnp.float32)).astype(out.dtype)


def _gated_heads(out, logits):
    """``out [B, S, heads * D]`` with each head's D lanes multiplied by
    ``sigmoid(logits [B, S, heads])`` of its head, in the dtype of out.
    The sigmoid is taken in float32 and rounded once; a head's gate reaches
    its lanes by a product with the heads' 0/1 indicator ``[heads, heads *
    D]`` (exact: one term a lane), for the reason
    ``ops/short_conv.py::over_heads`` gives: a reshape to ``[.., heads, D]``
    and a broadcast have XLA:TPU relay the tensor."""
    heads = logits.shape[-1]
    gate = jax.nn.sigmoid(logits.astype(jnp.float32)).astype(out.dtype)
    of_head = (jnp.arange(heads)[:, None]
               == jnp.arange(out.shape[-1])[None, :] // (
                   out.shape[-1] // heads)).astype(out.dtype)
    # (At float32 the MXU's default is one bf16 pass: ask for all of it.)
    return out * jnp.matmul(
        gate, of_head, precision=jax.lax.Precision.HIGHEST
        if out.dtype == jnp.float32 else None)


class SparseAttention(LlamaAttention):
    """Learned sparse attention over grouped-query heads: DeepSeek-V3.2-
    Exp's DSA (its lightning indexer and its sparse training stage), as
    Keye-VL-2.0's ``sa_config`` sizes it.

    q, k and v as ``LlamaAttention`` makes them (``qk_norm`` and the
    rotation included).  Beside them an indexer reads the same normed
    state x WITHOUT a gradient back into it: n = ``index_heads`` queries
    and one key a token, d = ``index_head_dim`` wide, rotated like q and k
    by the first d / 2 of the layer's frequencies, and n weights::

        q_j = W_iq x   k = W_ik x   w = W_iw x
        I[t, s] = sum_j w[t, j] relu(q_j[t] . k[s])
        S_t = the index_topk keys s <= t of largest I[t, s]
        o[t, h] = sum_{s in S_t} softmax_{S_t}(q[t, h] . k[s, h // g]
                                               / sqrt(D)) v[s, h // g]

    ``ops/sparse_index.py`` finds S_t exactly (``select_keys``) and forms
    the indexer's loss ``KL(mean over heads of the attention's
    probabilities || softmax_{S_t}((n d)^(-1/2) I))`` with its gradients
    (``index_loss``); ``attention_fn(q, k, v, selected=...)`` attends and
    returns the row statistics the target needs.  The indexer's three
    matrices learn from that loss alone (sown as ``index_losses/kl``; the
    caller adds it to its loss), everything else from the caller's loss
    alone: the selection has no gradient.  Also sown, where the caller
    makes ``sparse_stats`` mutable: ``keys_taken [B, S]`` and ``selected
    [B, S, S]``.
    """

    def attend(self, x, q, k, v, cos, sin):
        cfg = self.config
        B, S, _ = x.shape
        n, d = cfg.index_heads, cfg.index_head_dim
        scale = (n * d) ** -0.5
        with _scopes.scope(_scopes.SPARSE_INDEX):
            u = jax.lax.stop_gradient(x)
            half = (cos[:, :d // 2], sin[:, :d // 2])
            q_i = apply_rope(nn.Dense(
                n * d, use_bias=False, dtype=cfg.dtype,
                name="index_wq")(u).reshape(B, S, n, d), *half)
            k_i = apply_rope(nn.Dense(
                d, use_bias=False, dtype=cfg.dtype,
                name="index_wk")(u)[:, :, None], *half)[:, :, 0]
            w_i = nn.Dense(n, use_bias=False, dtype=jnp.float32,
                           name="index_ww")(u.astype(jnp.float32))
        selected, lse_i, taken = select_keys(q_i, k_i, w_i, cfg.index_topk,
                                             scale=scale)
        out, lse = self.attention_fn(q, k, v, selected=selected)
        if not self.is_initializing():
            self.sow("index_losses", "kl", index_loss(
                q, k, lse, q_i, k_i, w_i, selected, lse_i,
                sm_scale=q.shape[-1] ** -0.5, scale=scale))
            self.sow("sparse_stats", "keys_taken", taken)
            self.sow("sparse_stats", "selected", selected)
        return out


def _lambda_init(key, shape, dtype=jnp.float32):
    """N(0, 0.1): arXiv:2410.05258's start of lambda's four vectors."""
    return 0.1 * jax.random.normal(key, shape, dtype)


class DifferentialAttention(LlamaAttention):
    """Differential attention (Ye et al., arXiv:2410.05258) over grouped
    heads that do not rotate.  Adjacent heads pair: query pair j is heads
    ``(2j, 2j + 1) = (q1, q2)``, key-value pair i heads ``(2i, 2i + 1) = (k1,
    k2), (v1, v2)``, and query pair j reads key-value pair ``j // (query
    pairs / key-value pairs)``.  With D = ``head_dim``::

        a1 = softmax(q1 k1^T / sqrt(D)) [v1 | v2]        2 D lanes, causal,
        a2 = softmax(q2 k2^T / sqrt(D)) [v1 | v2]        under the window
        lambda = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_init
        o = RMSNorm_2D(a1 - lambda a2) * g * (1 - lambda_init)

    ``lambda_init = 0.8 - 0.6 exp(-0.3 index)``, the four ``l`` learned
    ``[D]`` vectors, g a learned ``[2 D]`` scale.  Two ``attention_fn`` calls
    a layer, keys D wide and values 2 D (inside ``hvd.attn.window`` in a
    sliding layer); what follows them under ``hvd.attn.diff``.  Parameters:
    ``wqkv [hidden, (heads + 2 kv heads) D]`` (q, then k, then v) and ``wo``,
    each with a bias; ``lambda_q1 lambda_k1 lambda_q2
    lambda_k2 [D]``, ``subln [2 D]``.

    ``__call__(x, cos, sin, kv)`` returns ``(out, (k, v))``.  With ``kv``
    given (another layer's k and v ``[B, S, kv heads * D]``, as its
    projection left them) the layer projects a query alone (``wq``) and
    attends to THOSE: cross-attention."""

    @nn.compact
    def __call__(self, x, cos, sin, kv=None):
        cfg = self.config
        B, S, _ = x.shape
        D, heads, kv_heads = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads

        def dense(width, name):
            return nn.Dense(width, dtype=cfg.dtype, name=name)

        if kv is None:
            qkv = dense((heads + 2 * kv_heads) * D, "wqkv")(x)
            q = qkv[..., :heads * D]
            kv = (qkv[..., heads * D:(heads + kv_heads) * D],
                  qkv[..., (heads + kv_heads) * D:])
        else:
            q = dense(heads * D, "wq")(x)
        k, v = kv
        q = q.reshape(B, S, heads // 2, 2, D)
        k = k.reshape(B, S, kv_heads // 2, 2, D)
        pair_v = v.reshape(B, S, kv_heads // 2, 2 * D)
        with (_scopes.scope(_scopes.ATTN_WINDOW)
              if cfg.window_of(self.index) is not None
              else contextlib.nullcontext()):
            a1, a2 = (self.attend(x, q[:, :, :, i], k[:, :, :, i], pair_v,
                                  cos, sin) for i in range(2))
        with _scopes.scope(_scopes.ATTN_DIFF):
            lambda_init = 0.8 - 0.6 * math.exp(-0.3 * self.index)
            l_q1, l_k1, l_q2, l_k2 = (
                self.param(name, _lambda_init, (D,)) for name in (
                    "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
            lam = (jnp.exp(jnp.sum(l_q1 * l_k1))
                   - jnp.exp(jnp.sum(l_q2 * l_k2)) + lambda_init)
            diff = a1.astype(jnp.float32) - lam * a2.astype(jnp.float32)
            diff = diff * jax.lax.rsqrt(jnp.mean(
                diff * diff, axis=-1, keepdims=True) + cfg.rms_eps)
            out = (diff * self.param("subln", nn.initializers.ones, (2 * D,))
                   * (1.0 - lambda_init)).astype(cfg.dtype)
        return dense(cfg.hidden_size, "wo")(out.reshape(B, S, heads * D)), kv


class SwiGLU(nn.Module):
    config: LlamaConfig
    width: Optional[int] = None     # config.intermediate_size where None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        # Fused gate+up: one [H, 2F] matmul.
        gu = nn.Dense(2 * (self.width or cfg.intermediate_size),
                      use_bias=False, dtype=cfg.dtype, name="w_gate_up")(x)
        gate, up = jnp.split(gu, 2, axis=-1)
        return nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                        name="w_down")(nn.silu(gate) * up)


class Relu2MLP(nn.Module):
    """``relu(x W_up)^2 W_down``: the feed-forward of ``mlp_hidden_act``
    ``"relu2"``, two matrices and no gate."""

    config: LlamaConfig
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        up = nn.Dense(self.width, use_bias=False, dtype=cfg.dtype,
                      name="w_up")(x)
        return nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                        name="w_down")(_relu2(up))


class LatentAttention(nn.Module):
    """Multi-head latent attention (MLA) of DeepSeek-V2 (arXiv:2405.04434),
    on the training path: keys and values are made from one low-rank
    latent a token, and one rotary key serves all heads.

    With n heads, ``d_n = qk_nope_head_dim``, ``d_r = qk_rope_head_dim``,
    ``d_v = v_head_dim``, ``r = kv_lora_rank`` and x the normed input::

        q = x W_q            [n, d_n + d_r]  ->  q_n, q_r
        c = x W_kva          [r + d_r]       ->  c_kv, k_r  (k_r: all heads')
        [k_n | v] = RMSNorm(c_kv) W_kvb      [n, d_n + d_v]
        q_r, k_r <- RoPE (YaRN frequencies, ``rope_freqs``)
        scores = [q_n | q_r] . [k_n | k_r] (d_n + d_r)^(-1/2) m^2
        out = softmax_causal(scores) v W_o

    m squared is ``YarnScaling.softmax_scale``.  ``attention_fn`` is
    handed keys ``d_n + d_r`` wide, values ``d_v`` wide and the scale.

    With a query latent of ``r_q = q_lora_rank`` lanes (DeepSeek-V3,
    arXiv:2412.19437; None, DeepSeek-V2-Lite's null: the one matrix above)::

        q = RMSNorm(x W_qa) W_qb     W_qa [H, r_q], a scale [r_q], W_qb
                                     [r_q, n (d_n + d_r)]

    as ``wq_a``, ``q_norm`` and ``wq_b`` in place of ``wq``, under
    ``hvd.mla.latent`` like the keys' latent.

    With ``mla_use_nope`` (Kimi Linear's key) nothing turns: the scores are
    ``[q_n | q_r] . [k_n | k_r] (d_n + d_r)^(-1/2)`` on the lanes as the
    projections left them, k_r still one key for all heads.
    """

    config: LlamaConfig
    attention_fn: Callable = staticmethod(causal_attention)
    index: int = 0      # every mixer is told its layer; this one is the same in all
    in_place: bool = False      # and ``LlamaLayer``'s reading of attention_fn

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        B, S, _ = x.shape
        heads, rank = cfg.num_heads, cfg.kv_lora_rank
        d_n, d_r, d_v = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim)

        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=cfg.dtype,
                            name=name)

        if cfg.q_lora_rank is None:
            q = dense(heads * (d_n + d_r), "wq")(x)
        else:
            with _scopes.scope(_scopes.MLA_LATENT):
                c_q = RMSNorm(cfg.rms_eps, cfg.dtype, name="q_norm")(
                    dense(cfg.q_lora_rank, "wq_a")(x))
                q = dense(heads * (d_n + d_r), "wq_b")(c_q)
        q = q.reshape(B, S, heads, d_n + d_r)
        if not cfg.mla_use_nope:
            q = jnp.concatenate(
                [q[..., :d_n], apply_rope(q[..., d_n:], cos, sin,
                                          in_place=self.in_place)], axis=-1)
        with _scopes.scope(_scopes.MLA_LATENT):
            latent = dense(rank + d_r, "wkv_a")(x)
            c_kv = RMSNorm(cfg.rms_eps, cfg.dtype,
                           name="kv_norm")(latent[..., :rank])
            k_r = latent[..., None, rank:]
            if not cfg.mla_use_nope:
                k_r = apply_rope(k_r, cos, sin, in_place=self.in_place)
            kv = dense(heads * (d_n + d_v), "wkv_b")(c_kv).reshape(
                B, S, heads, d_n + d_v)
            k = jnp.concatenate(
                [kv[..., :d_n],
                 jnp.broadcast_to(k_r, (B, S, heads, d_r))], axis=-1)
            v = kv[..., d_n:]
        scale = (d_n + d_r) ** -0.5
        if cfg.rope_scaling is not None:
            scale *= cfg.rope_scaling.softmax_scale
        out = self.attention_fn(q, k, v, scale=scale)
        return dense(cfg.hidden_size, "wo")(out.reshape(B, S, heads * d_v))


def _float0(x):
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_of_tokens(x, assignments, position, k):
    """``x[assignments // k]``: row r of the result is the token of
    assignment ``assignments[r]`` (a token's k assignments are
    consecutive).  ``position [T * k]`` says where in the result each
    assignment's row is (outside ``[0, rows)``: nowhere), so the transpose
    gathers too, slot by slot into a float32 sum (``_rows_to_tokens``),
    where autodiff would scatter-add and XLA:TPU scatters row by row."""
    return x[assignments // k]


def _rows_of_tokens_fwd(x, assignments, position, k):
    return x[assignments // k], (assignments, position)


def _rows_of_tokens_bwd(k, res, g):
    assignments, position = res
    return (_rows_to_tokens(g, position, k).astype(g.dtype),
            _float0(assignments), _float0(position))


_rows_of_tokens.defvjp(*_scopes.rules(
    "_rows_of_tokens", _rows_of_tokens_fwd, _rows_of_tokens_bwd))


def _rows_to_tokens(rows, position, k, weights=None):
    """``[T, H]`` in float32: each token's k rows of ``rows`` added up in
    slot order, under ``weights [T, k]`` if given.  An assignment whose row
    is not among ``rows`` adds nothing.

    Slot by slot: the j-th rows of all tokens are gathered (``[T, H]``, the
    rows' dtype), cast, scaled and added to one float32 accumulator, which
    XLA:TPU compiles to k gathers and one fusion that reads them.  No
    ``[T * k, H]`` array exists: gathered whole and summed over a
    ``[T, k, H]`` reshape, it was written in float32 with k = 6 padded to
    8, 1.07 GB for 0.2 GB of rows that are there, and a pass took 5 ms of
    which the gather was 0.7 (PERF.md §6, PR 33).  No scatter-add of the
    rows either: XLA:TPU scatters them one by one (PR 32)."""
    position = position.reshape(-1, k)
    there = (position >= 0) & (position < rows.shape[0])
    scale = there.astype(jnp.float32) if weights is None else jnp.where(
        there, weights, 0.0)
    position = jnp.clip(position, 0, rows.shape[0] - 1)
    return functools.reduce(jnp.add, (
        rows[position[:, j]].astype(jnp.float32) * scale[:, j, None]
        for j in range(k)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _weighted_rows_to_tokens(rows, weights, assignments, position, k):
    """``_rows_to_tokens`` under the gates, with a transpose that gathers:
    row r's cotangent is its token's, times the gate of assignment
    ``assignments[r]``."""
    return _rows_to_tokens(rows, position, k, weights)


def _weighted_rows_to_tokens_fwd(rows, weights, assignments, position, k):
    return (_rows_to_tokens(rows, position, k, weights),
            (rows, weights, assignments, position))


def _weighted_rows_to_tokens_bwd(k, res, g):
    rows, weights, assignments, position = res
    g_rows = g[assignments // k]                              # [rows, H]
    gate_of_row = weights.reshape(-1)[assignments]
    d_rows = (g_rows * gate_of_row[:, None]).astype(rows.dtype)
    # d weights[t, j] = g[t] . rows[position[t, j]], formed row by row and
    # sent back to the assignment each row came from (a dead row's is 0).
    dots = jnp.sum(g_rows * rows.astype(jnp.float32), axis=-1)
    d_weights = jnp.zeros(weights.size, jnp.float32).at[assignments].add(
        dots).reshape(weights.shape).astype(weights.dtype)
    return d_rows, d_weights, _float0(assignments), _float0(position)


_weighted_rows_to_tokens.defvjp(*_scopes.rules(
    "_weighted_rows_to_tokens", _weighted_rows_to_tokens_fwd,
    _weighted_rows_to_tokens_bwd))


def _relu2(x):
    """``relu(x)`` squared (``mlp_hidden_act`` ``"relu2"``)."""
    return jnp.square(nn.relu(x))


def _row_chunk(assignments: int, share: float) -> int:
    """Rows of the row buffer: twice what a uniform router sends to a chip
    that holds ``share`` of the experts, in whole tiles of 512; all the
    assignments where that is no less."""
    return min(-(-int(2 * share * assignments) // 512) * 512, assignments)


@functools.partial(jax.jit, static_argnums=(9, 10, 12, 13), inline=True)
def _one_buffer(tokens, w_gu, w_down, weights, order, inverse, last,
                rows_per_expert, n_rows, chunk, k, first=0, act="silu",
                in_place=False):
    """The part of a routed layer's y that the sorted rows ``first`` to
    ``first + chunk`` give: gathered, through their experts, and back to
    their tokens under the gates.  ``[T, H]`` float32; all zeros, and so is
    every gradient, where no row is held from ``first`` on.  ``order`` the
    assignments sorted by held expert, ``inverse`` each assignment's place
    among them, ``last`` the running sum of ``rows_per_expert``.  ``act``
    ``"silu"``: ``w_gu`` is ``[held, H, 2F]``, a SwiGLU's gate and up;
    ``"relu"``: the same matrices and ``relu(gate) * up``, the gated ReLU;
    ``"relu2"``: it is ``w_up [held, H, F]`` and the rows between the two
    products are ``relu(.)`` squared, no gate.

    The two grouped products are ``ops/grouped_matmul.py``'s: XLA's
    ``ragged_dot``, or, where ``in_place`` (the trace is not partitioned)
    and the backend is a TPU, the Mosaic grouped matmul at stated tiles,
    the faster alone at every routed cell's own call; either reads the
    matrices as they are: nothing is padded, in the step or in the state.

    Under an inlined ``jit`` so that it is traced once a shape: a step
    calls it ten times a routed layer (the first buffer and the loop's, in
    the walk, its forward rule and twice under ``jax.vjp`` backward, and
    again where the layer is recomputed), which cost the ``laguna-s-2.1``
    cell's step a second of tracing at every start (PERF.md §6, PR 43).
    Inlined, the operations keep the names and scopes they have without
    it."""
    with _scopes.scope(_scopes.MOE_ROUTE):
        assignments = jax.lax.dynamic_slice(order, (first,), (chunk,))
        position = inverse - first
        # An expert's rows that fall into this buffer.
        sizes = (jnp.clip(last, first, first + chunk)
                 - jnp.clip(last - rows_per_expert, first, first + chunk))
        live = (first + jnp.arange(chunk) < n_rows)[:, None]
        rows = _rows_of_tokens(tokens, assignments, position, k)
        # Rows past the last group are no expert's: what a grouped
        # product leaves there is undefined, so it is cut off at
        # both ends (here for the gradient that comes back).
        rows = jnp.where(live, rows, 0)
    with _scopes.scope(_scopes.MOE_EXPERTS):
        if act == "relu2":
            rows = _relu2(grouped_matmul(rows, w_gu, sizes, in_place))
        else:
            gate, up = jnp.split(
                grouped_matmul(rows, w_gu, sizes, in_place), 2, axis=-1)
            rows = (nn.relu if act == "relu" else nn.silu)(gate) * up
        rows = grouped_matmul(rows, w_down, sizes, in_place)
    with _scopes.scope(_scopes.MOE_COMBINE):
        rows = jnp.where(live, rows, 0)
        return _weighted_rows_to_tokens(rows, weights, assignments,
                                        position, k)


def _over_live_buffers(of_buffer, n_rows, chunk):
    """``of_buffer(first)`` added up over the buffers that hold rows: the
    first whatever it holds, so that where one buffer does (the rule) the
    result is its own and no sum is started from zeros, and those behind
    it in a loop whose trip count is the data's."""
    return jax.lax.fori_loop(
        1, -(-n_rows // chunk),
        lambda i, total: jax.tree.map(jnp.add, total, of_buffer(i * chunk)),
        of_buffer(np.int32(0)))     # of the loop's type: one trace serves


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12))
def _live_buffers(tokens, w_gu, w_down, weights, order, inverse, last,
                  rows_per_expert, n_rows, chunk, k, act="silu",
                  in_place=False):
    """``_one_buffer`` summed over ``ceil(n_rows / chunk)`` buffers, forward
    and backward: a chip's share of the experts gets a fraction of the worst
    case's rows and pays for what it gets.

    The backward pass is its own because autodiff's is not that: it cannot
    cross a loop of a traced length, and through a ``scan`` of ``cond`` s
    over all the buffers it adds every buffer's cotangent of the weights,
    tokens and gates into accumulators, zeros it writes out for each buffer
    that was skipped (fifteen of sixteen at a 1/32 share: 108 ms of a 449
    ms step, PERF.md §6, PR 43).  It keeps the layer's inputs alone and runs
    each live buffer's forward again (``jax.vjp``): kept, the residuals of
    all buffers are the worst case's."""
    return _over_live_buffers(
        lambda first: _one_buffer(tokens, w_gu, w_down, weights, order,
                                  inverse, last, rows_per_expert, n_rows,
                                  chunk, k, first, act, in_place),
        n_rows, chunk)


def _live_buffers_fwd(*inputs):
    return _live_buffers(*inputs), inputs[:9]


def _live_buffers_bwd(chunk, k, act, in_place, inputs, g):
    differentiable, indices = inputs[:4], inputs[4:]

    def cotangents(first):
        return jax.vjp(lambda *operands: _one_buffer(
            *operands, *indices, chunk, k, first, act, in_place),
            *differentiable)[1](g)

    return (*_over_live_buffers(cotangents, indices[-1], chunk),
            *map(_float0, indices))


_live_buffers.defvjp(*_scopes.rules(
    "_live_buffers", _live_buffers_fwd, _live_buffers_bwd))


class RoutedExperts(nn.Module):
    """Top-k routed SwiGLU experts, of which this program holds
    ``config.experts_held`` (ids ``first_held_expert`` onwards), and the
    shared experts beside them (DeepSeekMoE, arXiv:2401.06066).

    For a token x, in float32 for the router (x and W_r cast up)::

        s = softmax(x W_r)                    over all num_experts
        e_1..e_K = the K largest of s;  g_k = s[e_k]
                   (divided by their sum if norm_topk_prob, then times
                   routed_scaling_factor)
        y = sum_{k: e_k held here} g_k E_{e_k}(x) + S(x)

    E_e a SwiGLU of width ``moe_intermediate_size``, S one SwiGLU of
    ``shared_experts`` times that width, times ``sigmoid(x w_s)`` (``w_s
    [H, 1]``, float32) where ``shared_expert_gate``.  What an absent expert
    would add is left out (the chip that holds it adds it, and the sum over
    chips is the whole layer); with every expert held nothing is.

    Static shapes and no dropped row, whatever the imbalance: the T * K
    assignments are sorted by held expert (absent ones last), the tokens'
    rows gathered in that order into a row buffer, and two grouped
    products (``ops/grouped_matmul.py``, group sizes the held experts' row
    counts) run over the rows that are there: ``jax.lax.ragged_dot``, which
    XLA:TPU makes a Mosaic call of its own that visits only tiles that hold
    rows; or, where ``in_place`` (``LlamaLayer``'s word that the trace is
    not partitioned) and the backend is a TPU, the Mosaic grouped matmul
    JAX ships, at tiles that module states from the call's shape: alone it
    takes 0.56-0.73 of ``ragged_dot``'s time at every routed cell's own
    call, and a fifth at Nemotron-3's F = 1856 (14.5 lane tiles, which
    XLA's calls run at a tenth of the MXU's peak).  Either reads the
    matrices as they are: nothing is padded, in the step or in the state,
    and parameters, gradients and optimizer state keep the published
    shapes.  Each token then takes its
    rows back by the inverse permutation and adds them up under its gates:
    slot by slot, K gathers of ``[T, H]`` into a float32 sum, and so does
    the gradient that comes back to the tokens (``_rows_to_tokens``; no
    ``[T, K, H]`` tensor and no scatter, PERF.md §6, PR 33).
    The buffer's worst case is T * K rows, every choice of every token held
    here; a chip that holds 8 of 64 experts expects an eighth of that, and
    gathers, elementwise passes and residuals over the dead seven eighths
    were 30 % of a step on the v5e (PERF.md, PR 32).  So the buffer has
    twice the expected rows (``_row_chunk``) and the sorted rows go through
    it buffer by buffer, as many times as there are rows for, forward and
    backward (``_live_buffers``: the first buffer, then a loop whose length
    is the data's, and a backward pass of its own that walks the same
    buffers and writes nothing for the others): once as a rule, as often
    as the worst case needs at worst, and with every expert held there is
    one buffer and no loop.
    Weights are ``w_gate_up [held, H, 2F]`` and ``w_down [held, F, H]``,
    which ``parallel/api.py`` shards over an ``expert`` axis.

    ``scoring_func`` ``"sigmoid"``: ``s = sigmoid(x W_r)``.  ``topk_method``
    ``"noaux_tc"``: ``e_1..e_K`` are the K largest of ``s + b`` under a
    ``stop_gradient`` and the gates stay ``s[e_k]``; ``b [num_experts]``
    float32 lives in the ``router_state`` collection, zeros at the start, and
    where the caller makes that collection mutable a call leaves ``b +
    router_bias_update_rate sign(mean_e c - c_e)`` there, c_e the call's
    assignments to expert e over ALL the experts (the router is full width
    on every chip).  That is one chip's update: ``make_train_step``'s
    ``has_aux`` path AVERAGES such state over a ``data`` axis, the mean of
    the chips' updates and not the update of the summed counts.
    ``mlp_hidden_act`` ``"relu2"``: E_e and S are ``relu(x W_up)^2 W_down``
    (``w_up [held, H, F]`` in place of ``w_gate_up``; S a ``Relu2MLP``), and
    S is ``moe_shared_expert_intermediate_size`` wide where that is set.
    ``"relu"``: E_e is the gated ReLU, ``(relu(x W_gate) * (x W_up))
    W_down`` over the same ``w_gate_up`` (ReGLU; no S goes with it).

    ``__call__(x, router_x=None)``: the router's scores are ``router_x W_r``
    where the caller hands another tensor than the experts' x for them
    (``router_input`` ``"layer"``: ``LlamaLayer`` hands the layer's input,
    ahead of the mixer and its norm, so the router's gradient enters the
    stream before attention); choice, gates, balance loss and counters are
    made from them in this one call, as from x's.

    Outside ``init`` it sows, where the caller makes the collection
    mutable: ``moe_stats/assignments_per_expert [num_experts]``,
    ``load_max_over_mean`` and (with a bias) ``bias_abs_max``, and always
    ``losses/balance`` (``ops.losses.sequence_balance_loss`` of
    this layer) and ``moe_stats/rows_per_expert [held]``,
    ``moe_stats/rows_dropped`` (assignments to held experts that are not
    in a buffer: 0 by construction) and ``moe_stats/row_buffers_run``
    (how many buffers held rows).
    """

    config: LlamaConfig
    in_place: bool = False      # ``LlamaLayer``'s reading of attention_fn

    @nn.compact
    def __call__(self, x, router_x=None):
        cfg = self.config
        B, S, H = x.shape
        if router_x is None:
            router_x = x
        E, K, held = cfg.num_experts, cfg.experts_per_token, cfg.experts_held
        F = cfg.moe_intermediate_size or cfg.intermediate_size
        T = B * S
        per_expert = nn.initializers.lecun_normal(batch_axis=(0,))
        act = cfg.mlp_hidden_act
        if act == "relu2":
            w_gu = self.param("w_up", per_expert, (held, H, F))
        else:
            w_gu = self.param("w_gate_up", per_expert, (held, H, 2 * F))
        w_gu = w_gu.astype(cfg.dtype)
        w_down = self.param("w_down", per_expert,
                            (held, F, H)).astype(cfg.dtype)
        corrected = cfg.topk_method == "noaux_tc"

        with _scopes.scope(_scopes.MOE_ROUTE):
            router = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                              name="router")(router_x.astype(jnp.float32))
            if cfg.scoring_func == "sigmoid":
                scores = jax.nn.sigmoid(router)                    # [B,S,E]
            else:
                scores = jax.nn.softmax(router, axis=-1)
            if corrected:
                # The bias moves the CHOICE; the gates are the scores.
                bias = self.variable(ROUTER_STATE, "bias", jnp.zeros, (E,),
                                     jnp.float32)
                _, chosen = jax.lax.top_k(jax.lax.stop_gradient(
                    scores + bias.value), K)
                gates = jnp.take_along_axis(scores, chosen, axis=-1)
            else:
                gates, chosen = jax.lax.top_k(scores, K)           # [B,S,K]
            if cfg.norm_topk_prob:
                gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
            if cfg.routed_scaling_factor != 1.0:
                gates = gates * cfg.routed_scaling_factor
            # Assignment a = (token, choice), flat; absent experts sort last.
            local = chosen.reshape(T * K) - cfg.first_held_expert
            here = (local >= 0) & (local < held)
            local = jnp.where(here, local, held)
            order = jnp.argsort(local, stable=True)
            inverse = jnp.argsort(order)
            rows_per_expert = jnp.sum(
                local[:, None] == jnp.arange(held)[None, :], axis=0,
                dtype=jnp.int32)
            n_rows = jnp.sum(rows_per_expert)
            weights = jnp.where(here.reshape(T, K), gates.reshape(T, K),
                                0.0)
            chunk = _row_chunk(T * K, held / E)
            n_chunks = -(-T * K // chunk)
            if not self.is_initializing():
                balance = (batch_balance_loss if cfg.balance_over == "batch"
                           else sequence_balance_loss)
                # (Sigmoid scores do not sum to one: the loss reads their
                # shares of a token's sum, DeepSeek-V3's form, 1 when even.)
                self.sow("losses", "balance", balance(
                    scores / jnp.sum(scores, axis=-1, keepdims=True)
                    if cfg.scoring_func == "sigmoid" else scores, chosen))
                self.sow("moe_stats", "rows_per_expert", rows_per_expert)
                self.sow("moe_stats", "rows_dropped",
                         jnp.maximum(n_rows - n_chunks * chunk, 0))
                self.sow("moe_stats", "row_buffers_run",
                         -(-n_rows // chunk))
                self._count_all(chosen, bias if corrected else None)

        order = jnp.pad(order, (0, n_chunks * chunk - T * K))
        operands = (x.reshape(T, H), w_gu, w_down, weights, order, inverse,
                    jnp.cumsum(rows_per_expert), rows_per_expert, n_rows,
                    chunk, K)
        if n_chunks == 1:
            y = _one_buffer(*operands, 0, act, self.in_place)
        else:
            y = _live_buffers(*operands, act, self.in_place)
        y = y.astype(cfg.dtype).reshape(B, S, H)

        if cfg.shared_experts:
            with _scopes.scope(_scopes.MOE_SHARED):
                width = (cfg.moe_shared_expert_intermediate_size
                         or cfg.shared_experts * F)
                shared = (Relu2MLP if act == "relu2" else SwiGLU)(
                    cfg, width=width, name="shared")(x)
                if cfg.shared_expert_gate:
                    # One logit a token, float32 as the router's.
                    gate = nn.Dense(1, use_bias=False, dtype=jnp.float32,
                                    name="shared_gate")(
                                        x.astype(jnp.float32))
                    shared = shared * jax.nn.sigmoid(gate).astype(
                        shared.dtype)
                y = y + shared
        return y

    def _count_all(self, chosen, bias):
        """The step's assignments to each of ALL the experts (what the
        choice bias learns from), sown with what it says of the load, and
        the bias moved by it where the caller made its collection mutable."""
        cfg = self.config
        wanted = self.is_mutable_collection("moe_stats")
        moving = bias is not None and self.is_mutable_collection(
            ROUTER_STATE)
        if not (wanted or moving):
            return
        counts = jnp.sum(chosen.reshape(-1, 1) == jnp.arange(
            cfg.num_experts)[None, :], axis=0, dtype=jnp.int32)
        mean = chosen.size / cfg.num_experts
        self.sow("moe_stats", "assignments_per_expert", counts)
        self.sow("moe_stats", "load_max_over_mean", jnp.max(counts) / mean)
        if bias is not None:
            self.sow("moe_stats", "bias_abs_max",
                     jnp.max(jnp.abs(bias.value)))
        if moving:
            bias.value = bias.value + cfg.router_bias_update_rate * jnp.sign(
                mean - counts.astype(jnp.float32))


def _conv_taps_init(key, shape, dtype=jnp.float32):
    """Uniform in +-1 / sqrt(K): a depthwise filter's fan-in is its K taps
    (what ``torch.nn.Conv1d`` draws)."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log A`` with A uniform in (0, 16): Mamba-2's and Gated DeltaNet's
    own default."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-3, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a step log-uniform in [0.001, 0.1] (the
    same defaults): ``softplus(dt_bias)`` is that step."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class GatedDeltaNet(nn.Module):
    """The gated delta-rule linear-attention mixer (Gated DeltaNet; Yang,
    Kautz, Hatamizadeh, arXiv:2412.06464) of a ``"linear_attention"``
    layer.  A head, with x the block's input and ``*`` the causal depthwise
    convolution of ``linear_conv_kernel_dim`` taps::

        q = l2norm(silu(conv_q * (x W_q))) d_k^-1/2    k = l2norm(silu(conv_k * (x W_k)))
        v = silu(conv_v * (x W_v))
        beta_t  = c sigmoid(x_t W_b)      c = 2 where linear_allow_neg_eigval, else 1
        alpha_t = exp(-exp(A_log) softplus(x_t W_a + dt_bias))        float32
        S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T    S_0 = 0
        o_t = S_t q_t
        y   = (RMSNorm(o) * silu(x W_g)) W_o        one learned [d_v] scale for all heads

    The recurrence runs chunk by chunk (``ops/gated_delta.py``: Mosaic
    calls on the rows of q, k and v where the heads are whole lane tiles
    and the ``attention_fn`` reads in place, else its ``jnp`` walk), its
    state ``[d_v, d_k]`` a head in float32.  The output norm and its gate are
    ``ops/gated_norm.py::norm_gated`` (under ``hvd.gdn.gates``, beside the
    ``wa`` / ``wb`` projections, which stay XLA's): one Mosaic pass each way
    where the model's ``attention_fn`` reads its operands in place and one,
    two or four heads are whole lane tiles (heads of 128: four a step; of
    192: two, the shared tile under a mask), else its ``jnp`` body, which is
    what this class held (``_gated_norm``).  With more value heads than key
    heads a key head serves ``value / key`` of them (q and k copied to
    the value heads under ``hvd.gdn.heads``).  Parameters: ``wq wk
    [H, key heads * d_k]``, ``wv wg [H, value heads * d_v]``, ``wa wb [H,
    value heads]``, ``conv_q conv_k conv_v [K, channels]``, ``a_log
    dt_bias [value heads]``, ``o_norm [d_v]``, ``wo``.

    Sown where the caller makes ``gdn_stats`` mutable: ``alpha_mean``,
    ``alpha_min``, ``beta_over_one`` (the share), ``state_max`` (the
    largest |S| a chunk started from) and ``out_max`` (the largest |o|).
    """

    config: LlamaConfig
    in_place: bool = False      # ``LlamaLayer``'s reading of attention_fn

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, S, _ = x.shape
        h_k, h_v = cfg.linear_num_key_heads, (cfg.linear_num_value_heads
                                              or cfg.linear_num_key_heads)
        d_k, d_v = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        taps = cfg.linear_conv_kernel_dim

        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=cfg.dtype,
                            name=name)(x)

        def conv(y, name, heads, scale):
            return convolved(y, self.param(
                name, _conv_taps_init, (taps, y.shape[-1])), heads, scale,
                self.in_place)

        q, k, v = dense(h_k * d_k, "wq"), dense(h_k * d_k, "wk"), dense(
            h_v * d_v, "wv")
        z = dense(h_v * d_v, "wg")
        with _scopes.scope(_scopes.GDN_CONV):
            q = conv(q, "conv_q", h_k, d_k ** -0.5).reshape(B, S, h_k, d_k)
            k = conv(k, "conv_k", h_k, 1.0).reshape(B, S, h_k, d_k)
            v = conv(v, "conv_v", h_v, None).reshape(B, S, h_v, d_v)
        with _scopes.scope(_scopes.GDN_GATES):
            x32 = x.astype(jnp.float32)
            a = nn.Dense(h_v, use_bias=False, dtype=jnp.float32,
                         name="wa")(x32)
            b = nn.Dense(h_v, use_bias=False, dtype=jnp.float32,
                         name="wb")(x32)
            a_log = self.param("a_log", _a_log_init, (h_v,))
            dt_bias = self.param("dt_bias", _dt_bias_init, (h_v,))
            g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
            beta = jax.nn.sigmoid(b) * (2.0 if cfg.linear_allow_neg_eigval
                                        else 1.0)
        # (Where the rule's walk is the Mosaic calls it reads q, k and v, and
        # writes o, as rows: the key heads' copies and the norm's read
        # follow it.)
        rows = walks_rows(d_k, d_v, h_v, self.in_place)
        if h_v != h_k:
            # Value head j reads key head j // (h_v / h_k): copied, for a
            # rule that takes as many of each.
            with _scopes.scope(_scopes.GDN_HEADS):
                q, k = (key_heads_copied(t, h_v // h_k, rows)
                        for t in (q, k))
        with _scopes.scope(_scopes.GDN_SCAN), calls_in_place(self.in_place):
            o = gated_delta_rule(q, k, v, g, beta)
        if (self.is_mutable_collection("gdn_stats")
                and not self.is_initializing()):
            alpha = jnp.exp(g)
            for name, value in (
                    ("alpha_mean", jnp.mean(alpha)),
                    ("alpha_min", jnp.min(alpha)),
                    ("beta_over_one", jnp.mean(beta > 1.0)),
                    ("state_max", jnp.max(jnp.abs(gated_delta_states(
                        q, k, v, g, beta)))),
                    ("out_max", jnp.max(jnp.abs(o.astype(jnp.float32))))):
                self.sow("gdn_stats", name, value)
        with _scopes.scope(_scopes.GDN_GATES):
            o = norm_gated(o.reshape(z.shape), z, self.param(
                "o_norm", nn.initializers.ones, (d_v,)), h_v, cfg.rms_eps,
                self.in_place, chunk=0 if rows else CHUNK)
        return nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                        name="wo")(o)


class KimiDeltaAttention(nn.Module):
    """The Kimi Delta Attention mixer (Kimi Linear, arXiv:2510.26692) of a
    layer that ``linear_attn_config.kda_layers`` names: the delta rule with a
    decay a key CHANNEL.  A head of H = ``num_heads`` heads of d =
    ``head_dim`` (keys and values alike), x the block's normed input, ``*``
    the causal depthwise filter of ``short_conv_kernel_size`` taps::

        q = l2norm(silu(conv_q * (x W_q))) d^-1/2    k = l2norm(silu(conv_k * (x W_k)))
        v = silu(conv_v * (x W_v))
        g_t    = -exp(A_log[h]) softplus((x_t W_fa) W_fb + dt_bias)    [H, d] float32
        beta_t = sigmoid(x_t W_b)                                       [H]
        S_t = S_{t-1} Diag(exp(g_t)) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T    S_0 = 0
        o_t = S_t q_t
        y   = (RMSNorm(o) w * sigmoid((x W_ga) W_gb)) W_o        one [d] scale for all heads

    The filters, SiLU and L2 norms are ``ops/short_conv.py::convolved`` (under
    ``hvd.kda.conv``); the rule ``ops/kda.py::kda_rule`` (``hvd.kda.scan``);
    the two low-rank projections (each through d lanes), softplus and beta,
    and behind the rule the output norm under its SIGMOID gate
    (``ops/gated_norm.py::norm_gated``), ``hvd.kda.gates``.  Parameters: ``wq
    wk wv [C, H d]``, ``conv_q conv_k conv_v [K, H d]``, ``a_log [H]``,
    ``dt_bias [H d]``, ``f_a g_a [C, d]``, ``f_b g_b [d, H d]`` (no bias),
    ``wb [C, H]``, ``o_norm [d]``, ``wo``.

    Sown where the caller makes ``kda_stats`` mutable: ``alpha_min`` (over
    channels), ``state_max`` (the largest |S| a chunk started from) and
    ``out_max`` (the largest |o|).
    """

    config: LlamaConfig
    in_place: bool = False      # ``LlamaLayer``'s reading of attention_fn

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, S, _ = x.shape
        sizes = dict(cfg.linear_attn_config)
        heads, d = sizes["num_heads"], sizes["head_dim"]
        taps = sizes["short_conv_kernel_size"]

        def dense(width, name, y=x, dtype=cfg.dtype):
            return nn.Dense(width, use_bias=False, dtype=dtype, name=name)(y)

        def conv(y, name, scale):
            return convolved(y, self.param(
                name, _conv_taps_init, (taps, y.shape[-1])), heads, scale,
                self.in_place).reshape(B, S, heads, d)

        q, k, v = (dense(heads * d, name) for name in ("wq", "wk", "wv"))
        z = dense(heads * d, "g_b", dense(d, "g_a"))
        with _scopes.scope(_scopes.KDA_CONV):
            q = conv(q, "conv_q", d ** -0.5)
            k = conv(k, "conv_k", 1.0)
            v = conv(v, "conv_v", None)
        with _scopes.scope(_scopes.KDA_GATES):
            x32 = x.astype(jnp.float32)
            f = dense(heads * d, "f_b", dense(d, "f_a", x32, jnp.float32),
                      jnp.float32)
            a_log = self.param("a_log", _a_log_init, (heads,))
            dt_bias = self.param("dt_bias", _dt_bias_init, (heads * d,))
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                f + dt_bias).reshape(B, S, heads, d)
            beta = jax.nn.sigmoid(dense(heads, "wb", x32, jnp.float32))
        with _scopes.scope(_scopes.KDA_SCAN), calls_in_place(self.in_place):
            o = _kda.kda_rule(q, k, v, g, beta)
        if (self.is_mutable_collection("kda_stats")
                and not self.is_initializing()):
            for name, value in (
                    ("alpha_min", jnp.exp(jnp.min(g))),
                    ("state_max", jnp.max(jnp.abs(_kda.kda_states(
                        q, k, v, g, beta)))),
                    ("out_max", jnp.max(jnp.abs(o.astype(jnp.float32))))):
                self.sow("kda_stats", name, value)
        with _scopes.scope(_scopes.KDA_GATES):
            o = norm_gated(o.reshape(z.shape), z, self.param(
                "o_norm", nn.initializers.ones, (d,)), heads, cfg.rms_eps,
                self.in_place, chunk=CHUNK, sigmoid=True)
        return dense(cfg.hidden_size, "wo", o)


class GatedShortConv(nn.Module):
    """The double-gated short convolution of a ``"conv"`` layer (LFM2; Liquid
    AI's technical report and the published ``lfm2`` modelling code).  With x
    the block's normed input, H = ``hidden_size`` and ``*`` the causal
    depthwise convolution of ``conv_L_cache`` taps, one filter a channel and
    zero history before position 0 of a row::

        [B | C | z] = x W_in            three thirds of H, in that order
        y = C (taps * (B z))            the two gates are the non-linearity
        out = y W_out

    No activation, no norm, no bias.  The gates and the filter are
    ``ops/short_conv.py::convolved``'s gated form: one Mosaic pass each way
    over ``in_proj``'s output where it lies, where the model's
    ``attention_fn`` reads its operands in place, else its ``jnp`` body
    (under ``hvd.lconv.conv``; the two projections under
    ``hvd.lconv.proj``).  Parameters: ``in_proj [H, 3 H]``, ``conv_w [K,
    H]``, ``out_proj [H, H]``."""

    config: LlamaConfig
    in_place: bool = False      # ``LlamaLayer``'s reading of attention_fn

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        hidden = cfg.hidden_size
        with _scopes.scope(_scopes.LCONV_PROJ):
            bcz = nn.Dense(3 * hidden, use_bias=False, dtype=cfg.dtype,
                           name="in_proj")(x)
        with _scopes.scope(_scopes.LCONV_CONV):
            y = convolved(bcz, self.param("conv_w", _conv_taps_init,
                                          (cfg.conv_L_cache, hidden)),
                          1, None, self.in_place, gated=True)
        with _scopes.scope(_scopes.LCONV_PROJ):
            return nn.Dense(hidden, use_bias=False, dtype=cfg.dtype,
                            name="out_proj")(y)


def _mamba_a_log_init(key, shape, dtype=jnp.float32):
    """``log A`` with A uniform in (1, 16): Mamba-2's ``A_init_range``."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class Mamba2(nn.Module):
    """The Mamba-2 state-space mixer (state-space duality; Dao & Gu,
    arXiv:2405.21060) of a pattern's ``"M"`` layer (Nemotron-H's: the layer's
    one sublayer, 8 groups) and of a ``"mamba"`` layer of ``layer_types``
    (Granite-4.0-H's: a SwiGLU behind it, ONE group, chunks of 256).  With
    H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``, a state of N =
    ``ssm_state_size`` a lane, B and C shared by the heads of each of G =
    ``n_groups`` groups (head h reads group ``h // (H / G)``), x the block's
    normed input and ``*`` the causal depthwise convolution of
    ``conv_kernel`` taps, with a bias::

        [z | xBC | dt] = x W_in               z [H P], xBC [H P + 2 G N], dt [H]
        [u | B | C] = silu(conv * xBC + b_conv)
        D_t = softplus(dt_t + dt_bias)    a_t = exp(-exp(A_log) D_t)     float32
        S_t = a_t S_{t-1} + D_t u_t B_t^T     [P, N] a head, float32, S_0 = 0
        y_t = S_t C_t + D u_t                 D one scalar a head
        out = (RMSNorm_G(y * silu(z)) * w) W_out

    The gate multiplies BEFORE the norm, which is over each group's ``H P /
    G`` lanes (all of them at G = 1).  The recurrence runs chunk by chunk
    (``ops/ssd.py``, ``chunk_size`` rows: where the model's ``attention_fn``
    reads its operands in place and the shapes are ones it takes, one
    Mosaic call each way that walks the chunks with the state in VMEM, reads
    u, B and C where the filter left them and leaves y as rows; else its
    ``jnp`` body on the three cut out); the two projections are under
    ``hvd.ssd.proj``; the skip, the gate and the norm are
    ``ops/gated_norm.py``'s (one Mosaic pass each way on u and z where the
    filter and ``in_proj`` left them, under the same condition, else its
    ``jnp`` body).  Parameters:
    ``in_proj [hidden, 2 H P + 2 G N + H]``, ``conv_w [K, H P + 2 G N]``,
    ``conv_b``, ``a_log dt_bias d [H]``, ``norm [H P]``, ``out_proj``.

    Sown where the caller makes ``ssd_stats`` mutable: ``decay_min``,
    ``decay_mean`` (of a_t), ``dt_mean``, ``state_max`` (the largest |S| a
    chunk started from) and ``out_max`` (the largest |y|).
    """

    config: LlamaConfig
    in_place: bool = False      # ``LlamaLayer``'s reading of attention_fn

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, S, _ = x.shape
        heads, width, state = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                               cfg.ssm_state_size)
        groups, inner = cfg.n_groups, cfg.mamba_inner
        bc = groups * state
        with _scopes.scope(_scopes.SSD_PROJ):
            projected = nn.Dense(2 * inner + 2 * bc + heads, use_bias=False,
                                 dtype=cfg.dtype, name="in_proj")(x)
        dt = projected[..., 2 * inner + 2 * bc:]
        with _scopes.scope(_scopes.SSD_CONV):
            # (The filter reads its channels where ``in_proj`` left them.)
            xbc = convolved(
                projected, self.param("conv_w", _conv_taps_init,
                                      (cfg.conv_kernel, inner + 2 * bc)),
                1, None, self.in_place,
                bias=self.param("conv_b", nn.initializers.zeros,
                                (inner + 2 * bc,)), first=inner)
        a_log = self.param("a_log", _mamba_a_log_init, (heads,))
        with _scopes.scope(_scopes.SSD_GATES):
            dt = jax.nn.softplus(dt.astype(jnp.float32) + self.param(
                "dt_bias", _dt_bias_init, (heads,)))
        with _scopes.scope(_scopes.SSD_SCAN):
            # (The scan reads u, B and C where the filter left them, and
            # leaves y as rows, which is what the gates read.)
            y = ssd_scan_rows(xbc, dt, a_log, heads, groups, state,
                              self.in_place, chunk=cfg.chunk_size)
        d = self.param("d", nn.initializers.ones, (heads,))
        if (self.is_mutable_collection("ssd_stats")
                and not self.is_initializing()):
            decay = jnp.exp(-jnp.exp(a_log) * dt)
            u = xbc[..., :inner]
            b, c = (xbc[..., at:at + bc].reshape(B, S, groups, state)
                    for at in (inner, inner + bc))
            for name, value in (
                    ("decay_min", jnp.min(decay)),
                    ("decay_mean", jnp.mean(decay)),
                    ("dt_mean", jnp.mean(dt)),
                    ("state_max", jnp.max(jnp.abs(ssd_states(
                        u.reshape(B, S, heads, width), dt, a_log, b, c,
                        chunk=cfg.chunk_size)))),
                    ("out_max", jnp.max(jnp.abs(skipped(
                        y, u, d).astype(jnp.float32))))):
                self.sow("ssd_stats", name, value)
        with _scopes.scope(_scopes.SSD_GATES):
            # (The skip, the gate and the norm read u and z where the
            # filter and ``in_proj`` left them: their first channels.)
            y = gated_norm(y, xbc, projected, d, self.param(
                "norm", nn.initializers.ones, (inner,)), groups, cfg.rms_eps,
                self.in_place)
        with _scopes.scope(_scopes.SSD_PROJ):
            return nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                            name="out_proj")(y)


def _state_a_log_init(key, shape, dtype=jnp.float32):
    """``log A`` with ``A[d, n] = n + 1``: Mamba's S4D-real start."""
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)),
                            shape)


def _dt_kernel_init(key, shape, dtype=jnp.float32):
    """Uniform in +- rank^-1/2: Mamba's ``dt_init="random"``."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _silu_gated(y, z):
    """``y * silu(z)`` in float32, rounded once to the dtype of z."""
    return (y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
            ).astype(z.dtype)


class Mamba1(nn.Module):
    """The Mamba selective-scan mixer (Gu & Dao, arXiv:2312.00752) of a
    decoder-hybrid-decoder stack's ``"mamba"`` layer.  With I =
    ``mamba_expand`` x hidden channels, N = ``ssm_state_size`` state entries a
    channel, R = ``dt_rank``, x the block's normed input and ``*`` the causal
    depthwise convolution of ``conv_kernel`` taps, with a bias::

        [u' | z] = x W_in                   u', z [I]
        u = silu(conv * u' + b_conv)
        [r | B | C] = u W_x                 r [R], B, C [N]
        D_t = softplus(r_t W_dt + b_dt)     [I], float32;  A = -exp(A_log)
        h_t[d, n] = exp(D_t[d] A[d, n]) h_{t-1}[d, n] + D_t[d] B_t[n] u_t[d]
        y_t[d] = sum_n C_t[n] h_t[d, n] + D[d] u_t[d]            h_0 = 0
        out = (y * silu(z)) W_out

    The recurrence is ``ops/selective_scan.py``'s (the state in float32, a
    decay a channel and a state entry).  ``__call__`` returns ``(out, y)``:
    y, with the skip and BEFORE the gate, is what the stack's last such
    layer shares as the memory.  Parameters: ``in_proj [hidden, 2 I]``,
    ``conv_w [K, I]``, ``conv_b``, ``x_proj [I, R + 2 N]``, ``dt_proj [R, I]``
    with its bias, ``a_log [I, N]``, ``d [I]``, ``out_proj``.

    Sown where the caller makes ``sscan_stats`` mutable: ``decay_min``,
    ``decay_mean`` (of ``exp(D_t A)``), ``dt_mean``, ``state_max`` (the
    largest |h| a chunk started from) and ``out_max`` (the largest |y|).
    """

    config: LlamaConfig
    in_place: bool = False      # ``LlamaLayer``'s reading of attention_fn

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        inner, state, rank = cfg.scan_inner, cfg.ssm_state_size, cfg.dt_rank
        projected = nn.Dense(2 * inner, use_bias=False, dtype=cfg.dtype,
                             name="in_proj")(x)
        z = projected[..., inner:]
        with _scopes.scope(_scopes.SSCAN_CONV):
            # (The filter reads u' where ``in_proj`` left it.)
            u = convolved(
                projected, self.param("conv_w", _conv_taps_init,
                                      (cfg.conv_kernel, inner)),
                1, None, self.in_place,
                bias=self.param("conv_b", nn.initializers.zeros, (inner,)),
                first=0)
        with _scopes.scope(_scopes.SSCAN_GATES):
            rbc = nn.Dense(rank + 2 * state, use_bias=False, dtype=cfg.dtype,
                           name="x_proj")(u)
            b, c = rbc[..., rank:rank + state], rbc[..., rank + state:]
            delta = jax.nn.softplus(nn.Dense(
                inner, dtype=jnp.float32, kernel_init=_dt_kernel_init,
                bias_init=_dt_bias_init, name="dt_proj")(
                    rbc[..., :rank].astype(jnp.float32)))
        a_log = self.param("a_log", _state_a_log_init, (inner, state))
        with _scopes.scope(_scopes.SSCAN_SCAN):
            y = selective_scan(u, delta, a_log, b, c, self.param(
                "d", nn.initializers.ones, (inner,)), self.in_place)
        if (self.is_mutable_collection("sscan_stats")
                and not self.is_initializing()):
            rate = jnp.exp(a_log)
            # The least decay is where the step and the rate are largest;
            # the mean, a state entry at a time ([B, S, I, N] is never made).
            for name, value in (
                    ("decay_min", jnp.exp(-jnp.max(
                        jnp.max(delta, axis=(0, 1)) * jnp.max(rate, axis=1)))),
                    ("decay_mean", jnp.mean(jax.lax.map(
                        lambda r: jnp.mean(jnp.exp(-delta * r)), rate.T))),
                    ("dt_mean", jnp.mean(delta)),
                    ("state_max", jnp.max(jnp.abs(selective_scan_states(
                        u, delta, a_log, b, c)))),
                    ("out_max", jnp.max(jnp.abs(y.astype(jnp.float32))))):
                self.sow("sscan_stats", name, value)
        with _scopes.scope(_scopes.SSCAN_GATES):
            gated = _silu_gated(y, z)
        return nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                        name="out_proj")(gated), y


class GatedMemory(nn.Module):
    """The gated memory unit of arXiv:2507.06607: ``(m * silu(x W_1)) W_2``,
    m ``[B, S, I]`` the memory another layer's scan left (``Mamba1``'s y), x
    this layer's normed input; ``in_proj [hidden, I]``, ``out_proj [I,
    hidden]``, no bias.  The whole unit runs under ``hvd.gmu``: XLA fuses the
    gate product into ``out_proj``'s matmul, and a fusion has one name."""

    config: LlamaConfig
    in_place: bool = False      # every mixer is told; this one has no pass

    @nn.compact
    def __call__(self, x, memory):
        cfg = self.config
        with _scopes.scope(_scopes.GMU):
            gate = nn.Dense(memory.shape[-1], use_bias=False,
                            dtype=cfg.dtype, name="in_proj")(x)
            return nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                            name="out_proj")(_silu_gated(memory, gate))


class HyperConnection(nn.Module):
    """The three maps of ONE hyper-connected sublayer (manifold-constrained
    hyper-connections, mHC, arXiv:2512.24880), made from a token's n =
    ``hc_mult`` streams X ``[n, H]``, in float32::

        u      = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)       [n H]
        a_pre  = g_pre  (u Phi_pre)  + b_pre                  [n]
        a_post = g_post (u Phi_post) + b_post                 [n]
        A_res  = g_res  mat(u Phi_res) + B_res                [n, n]
        h_pre  = sigmoid(a_pre);    h_post = 2 sigmoid(a_post)
        M_0    = exp(clip(A_res, hc_res_clamp))
        M_t    = rows(cols(M_{t-1})),  t = 1..hc_sinkhorn_iters
        H_res  = M_last             rows sum to 1, columns nearly

    ``cols`` divides every column by its sum + ``hc_eps``, ``rows`` every
    row: Sinkhorn's projection onto the doubly stochastic matrices, a convex
    mix of the streams that neither grows nor shrinks their sum.  ``g_*`` are
    learned scalars; there is no learned scale in the RMS (Phi absorbs one).
    ``LlamaLayer`` then reads ``x_in = h_pre X`` (``_hc_read``), runs its
    sublayer F behind its norm N, and writes ``X' = H_res X + h_post^T
    F(N(x_in))`` (``_hc_write``).

    ``__call__(x [B, S, n, H])`` returns ``(h_pre [n, T], h_post [n, T],
    H_res [n, n, T])`` over the T = B S tokens; with ``read=True``, the
    sublayer's one way in, ``(x, x_in [B, S, H], h_post, H_res)``: x beside
    what was read of it, and the x RETURNED is what the write is to read.
    Where ``in_place`` holds and ``ops/hyper_connection.py``'s rule takes the
    shape that is its ``streams``: Mosaic passes over rows of X for whatever
    is X's size, ``_hc_maps`` below for what is n (n + 2) numbers a token,
    and ONE backward rule that writes x's whole cotangent (three functions of
    x would each write one, and JAX would add them).  Every other trace runs
    the ``jnp`` lines below and ``_hc_read``.  TOKENS ON THE LANES.  A ``[T,
    n, n]`` float32 tensor pads each token's n * n numbers to a tile of 8 x
    128, 64 times their bytes, and the 2 * ``hc_sinkhorn_iters``
    normalisations would walk that; as n * n rows of tokens every step is a
    few whole vector registers: a sum over a LEADING axis, which adds
    registers, and a division.  The steps are a ``lax.scan`` of fixed
    length that the COMPILER unrolls (``unroll``): traced once, no loop on
    the device.  (Unrolled in Python, their 1,300 equations a sublayer, their
    transposes and the layer's recomputation cost 100 s of every run's
    start-up; left a loop, 5 ms of a 459 ms step: PERF.md, PR 65.)  The one product is a float32
    product of X as it lies, cast up on the way in (as the router's): the
    RMS's factor is a number a token and multiplies the product's OUTPUT, so
    no normed copy of X is made.

    Leaves: ``phi_pre``, ``phi_post`` ``[n H, n]`` and ``phi_res`` ``[n H, n
    n]`` (LeCun normal: u has unit RMS, so the products have unit variance);
    ``b_pre``, ``b_post`` ``[n]``, ``b_res`` ``[n n]`` (zeros) and the gains
    ``g_pre``, ``g_post``, ``g_res`` (rank 0, ones), all created float32.
    This is the builder's initialisation, at which the dynamic part of every
    map carries signal from the first step; the published recipe is understood
    to start from small gains (0.01) and biases that make H_res the
    identity, a state a caller reaches by setting these leaves."""

    config: LlamaConfig
    in_place: bool = False      # ``LlamaLayer``'s reading of attention_fn

    @nn.compact
    def __call__(self, x, read: bool = False):
        cfg = self.config
        B, S, n, H = x.shape
        widths = (("pre", n), ("post", n), ("res", n * n))
        phi = jnp.concatenate(
            [self.param(f"phi_{name}", nn.initializers.lecun_normal(),
                        (n * H, width)) for name, width in widths], axis=1)
        bias = jnp.concatenate(
            [self.param(f"b_{name}", nn.initializers.zeros, (width,))
             for name, width in widths])
        gain = jnp.concatenate(
            [jnp.broadcast_to(self.param(f"g_{name}", nn.initializers.ones,
                                         ()), (width,))
             for name, width in widths])
        maps = functools.partial(_hc_maps, cfg, n)
        if read and _hc.note(x, self.in_place) is None:
            return _hc.streams(maps, x, phi, gain, bias)
        with _scopes.scope(_scopes.HC_MAP):
            # Each reader of x casts it up for itself: its cotangent is then
            # rounded to x's dtype where it is made, and no float32 tensor
            # of x's size is kept to add them up in.
            flat = x.reshape(B * S, n * H)
            mean_square = jnp.mean(
                jnp.square(flat.astype(jnp.float32)), axis=-1)
            logits = jnp.einsum("tk,km->mt", flat.astype(jnp.float32),
                                phi.astype(jnp.float32))
            h_pre, h_post, h_res = maps(logits, mean_square, gain, bias)
        if not read:
            return h_pre, h_post, h_res
        return x, _hc_read(x, h_pre), h_post, h_res


def _hc_maps(cfg, n, logits, mean_square, gain, bias):
    """``(h_pre [n, T], h_post [n, T], H_res [n, n, T])`` of
    ``HyperConnection`` from what is ``n (n + 2)`` numbers a token: ``logits
    [n (n + 2), T] = (vec(X) Phi)^T``, a token's ``mean_square [T]`` of
    vec(X), and the gains and biases a column.  The one function of both
    bodies: the ``jnp`` body hands it its own product, the Mosaic calls
    (``ops/hyper_connection.py``) theirs, and differentiate it by
    ``jax.vjp``."""
    scale = jax.lax.rsqrt(mean_square + cfg.hc_eps)
    logits = (gain.astype(jnp.float32)[:, None] * (logits * scale)
              + bias.astype(jnp.float32)[:, None])
    h_pre = jax.nn.sigmoid(logits[:n])
    h_post = 2.0 * jax.nn.sigmoid(logits[n:2 * n])

    def step(m, _):     # m [i, j, T]: columns, then rows
        m = m / (jnp.sum(m, axis=0, keepdims=True) + cfg.hc_eps)
        return m / (jnp.sum(m, axis=1, keepdims=True)
                    + cfg.hc_eps), None

    h_res, _ = jax.lax.scan(
        step, jnp.exp(jnp.clip(logits[2 * n:].reshape(n, n, -1),
                               *cfg.hc_res_clamp)),
        None, length=cfg.hc_sinkhorn_iters,
        unroll=cfg.hc_sinkhorn_iters)
    return h_pre, h_post, h_res


def _hc_read(x, h_pre):
    """``x_in = h_pre X``: ``x [B, S, n, H]``, ``h_pre [n, B S]`` -> ``[B, S,
    H]`` in x's dtype, summed in float32."""
    B, S, n, H = x.shape
    with _scopes.scope(_scopes.HC_MIX):
        return jnp.einsum("jt,tjc->tc", h_pre, x.reshape(B * S, n, H).astype(
            jnp.float32)).astype(x.dtype).reshape(B, S, H)


def _hc_write(x, y, h_post, h_res):
    """``X' = H_res X + h_post^T y``: the streams mixed and the sublayer's
    output ``y [B, S, H]`` written over all of them; ``[B, S, n, H]`` in x's
    dtype, summed in float32 and rounded once.  One Mosaic pass each way
    (``ops/hyper_connection.py::write``) where the layer around the call
    says ``in_place`` (``calls_in_place``: the accepted benchmark's tests
    wrap this function by name with these four operands and nothing else)
    and the rule takes the shape."""
    B, S, n, H = x.shape
    if _hc.takes(x, called_in_place()):
        return _hc.write(x, y, h_post, h_res)
    with _scopes.scope(_scopes.HC_MIX):
        mixed = jnp.einsum("ijt,tjc->tic", h_res,
                           x.reshape(B * S, n, H).astype(jnp.float32))
        wrote = jnp.einsum("it,tc->tic", h_post,
                           y.reshape(B * S, H).astype(jnp.float32))
        return (mixed + wrote).astype(x.dtype).reshape(x.shape)


ATTENTION_KINDS = {"full": LlamaAttention, "latent": LatentAttention,
                   "sparse": SparseAttention,
                   "differential": DifferentialAttention}
# A ``LayerSpec.mixer``'s name under its layer and its class; None:
# ``attention_kind``'s, which is handed the layer's tables too.
MIXERS = {SELF_ATTENTION: ("attn", None), CROSS_ATTENTION: ("attn", None),
          DELTA_RULE: ("linear", GatedDeltaNet),
          KDA: ("kda", KimiDeltaAttention),
          SHORT_CONV: ("conv", GatedShortConv), MAMBA2: ("mamba", Mamba2),
          SCAN: ("mamba", Mamba1), MEMORY_GATE: ("gmu", GatedMemory)}


class LlamaLayer(nn.Module):
    """Layer ``index`` of the stack, as ``config.layers[index]`` (a
    ``LayerSpec``) says: its mixer (``MIXERS``) under ``hvd.block.attn`` and
    its feed-forward (a ``SwiGLU`` as ``"mlp"``, ``RoutedExperts`` as
    ``"moe"``) under ``hvd.block.ffn``, those it has, each residual with one
    of the spec's ``norms`` (``_stack_norm``): on the sublayer's input or,
    with ``norm_placement`` ``"post"``, on its output inside the residual.
    ``cos``, ``sin`` are the tables of the spec's ``rope``.  With
    ``router_input`` ``"layer"`` the routed experts' router is handed x as
    the layer received it, beside the feed-forward's normed input.

    With ``hc_mult`` n > 1 x is the token's n streams, ``[B, S, n, H]`` or
    as the stack hands them from layer to layer the same as rows ``[B, S, n
    H]`` (the layer returns the shape it was given), and each residual ``x + F(N(x))`` becomes read, sublayer, write, inside the
    sublayer's own block scope: a ``HyperConnection`` of the sublayer's own
    (``"hc_attn"``, ``"hc_mlp"``; ``"hc"`` in a one-sublayer layer) makes
    ``h_pre``, ``h_post`` and ``H_res`` from x, F and N see ``x_in = h_pre x``
    ``[B, S, H]`` exactly as they see x without streams, and the layer
    returns ``H_res x + h_post^T F(N(x_in))``.  With n = 1 none of this is
    traced.

    Where layers share tensors (``LlamaConfig.layers_share``) the layer
    takes and returns ``shared`` beside x: its mixer is handed
    ``shared[spec.reads]`` beside the normed state, and what the mixer
    returns beside its output is left as ``shared[spec.writes]``."""

    config: LlamaConfig
    attention_fn: Callable = staticmethod(causal_attention)
    index: int = 0

    @nn.compact
    def __call__(self, x, cos, sin, shared=None):
        cfg = self.config
        spec = cfg.layers[self.index]
        # The one reading of the rule: every mixer is handed the answer.
        in_place = _reads_in_place(self.attention_fn)
        # The streams as the stack hands them on, rows [B, S, n H], or as
        # [B, S, n, H]: the layer gives back what it was given.
        as_rows = cfg.hc_mult > 1 and x.ndim == 3
        if as_rows:
            x = x.reshape(*x.shape[:2], cfg.hc_mult, cfg.hidden_size)
        wrote = {}
        mixer = ffn = None
        if spec.mixer is not None:
            name, cls = MIXERS[spec.mixer]
            if cls is None:
                module = ATTENTION_KINDS[cfg.attention_kind](
                    cfg, attention_fn=self.attention_fn, index=self.index,
                    in_place=in_place, name=name)
                beside = (cos, sin)
            else:
                module, beside = cls(cfg, in_place=in_place, name=name), ()
            if spec.reads is not None:
                beside += (shared[spec.reads],)

            def mixer(h):
                out = module(h, *beside)
                if isinstance(out, tuple):      # and what others may read
                    out, made = out
                    if spec.writes is not None:
                        wrote[spec.writes] = made
                return out
        if spec.ffn is not None:
            ffn = (RoutedExperts(cfg, in_place=in_place, name="moe")
                   if spec.ffn == ROUTED else SwiGLU(cfg, name="mlp"))
            if spec.ffn == ROUTED and cfg.router_input == "layer":
                # The router reads the stream as the layer received it.
                ffn = functools.partial(ffn, router_x=x)

        def residual(x, sublayer, norm):
            streams = x
            if cfg.hc_mult > 1:
                # The streams come back beside what was made of them: the
                # write reads THEM, so that one cotangent reaches x.
                streams, x, h_post, h_res = HyperConnection(
                    cfg, in_place=in_place,
                    name="hc" + norm[len("norm"):])(streams, read=True)
            norm = _stack_norm(cfg, norm)
            out = (sublayer(norm(x)) if cfg.norm_placement == "pre"
                   else norm(sublayer(x)))
            if cfg.residual_multiplier != 1.0:
                out = out * cfg.residual_multiplier
            if cfg.hc_mult > 1:
                with calls_in_place(in_place):
                    return _hc_write(streams, out, h_post, h_res)
            return x + out

        # Norm and residual add inside each block's scope: XLA fuses them
        # with the neighbouring products (common/scopes.py).
        if mixer is not None:
            with _scopes.scope(_scopes.BLOCK_ATTN):
                x = residual(x, mixer, spec.norms[0])
        if ffn is not None:
            with _scopes.scope(_scopes.BLOCK_FFN):
                x = residual(x, ffn, spec.norms[-1])
        if as_rows:
            x = x.reshape(*x.shape[:2], -1)
        return x if shared is None else (x, {**shared, **wrote})


class LlamaModel(nn.Module):
    """Decoder-only LM; with ``config.total_ut_steps`` T > 1 a looped one.

    T = 1: ``tokens [B, S] -> logits [B, S, V]``.  With ``config.hc_mult``
    n > 1 the embedding is copied into n residual streams, the layers carry
    them as rows ``[B, S, n H]`` (``LlamaLayer``), and the final norm reads
    the streams' sum.

    T > 1 (Ouro / LoopLM; Zhu et al., arXiv:2510.25741).  With E the
    embedding, Stack the ``num_layers`` layers in order, N the final
    norm, W the head (its own matrix, or the embedding's transpose where
    ``tie_word_embeddings``)::

        h(0) = E[tokens];   h(t) = N(Stack(h(t-1)))        t = 1..T
        g(t) = h(t) . w_g + b_g                            (float32)

    The same layer modules, so the same weights and the same rotary
    positions, in every pass: autodiff sums a weight's gradient over the
    passes.  The normalised h(t) is what pass t + 1 reads, what exit t's
    head reads and what the gate reads.  ``__call__`` returns ``(hidden
    [T, B, S, H], gate_logits [T, B, S])`` and never a logits tensor:
    ``head`` turns one exit's hidden states into logits, and
    ``ops.losses.expected_exit_loss`` applies it exit by exit so that one
    logits tensor is alive at a time.  The gate (``exit_gate``: a Linear
    H -> 1 with bias, zero-initialised, shared by all passes) gives
    sigma(g(t)), the probability of leaving at pass t having reached it.
    """

    config: LlamaConfig
    attention_fn: Callable = staticmethod(causal_attention)

    @nn.compact
    def __call__(self, input_ids, *, positions_offset: int = 0):
        cfg = self.config
        B, S = input_ids.shape
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="tok_emb")(input_ids)
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        # One table a distinct rotation, made once and handed to the layers
        # of its type (a stack with one rotation: one table, as before).
        tables = {None: (None, None)}
        for rope in map(cfg.rope_of, range(cfg.num_layers)):
            if rope not in tables:
                tables[rope] = rope_freqs(
                    cfg.rope_dim, S, rope.rope_theta,
                    offset=positions_offset, scaling=rope.scaling,
                    rotary_dim=int(rope.partial_rotary_factor
                                   * cfg.rope_dim))
        layer_cls = LlamaLayer
        if cfg.remat != "none":
            layer_cls = nn.remat(LlamaLayer,
                                 policy=REMAT_POLICIES[cfg.remat])

        def one_pass(mdl, x):
            """Stack(x), its modules made under ``mdl`` by name.  What a
            decoder-hybrid-decoder stack's layers share goes from layer to
            layer beside x, through ``nn.remat`` as a layer's input and
            output (kept, like x: a reader's gradient reaches its writer
            through them)."""
            shared = {} if cfg.layers_share else None
            for i in range(cfg.num_layers):
                layer = layer_cls(cfg, attention_fn=self.attention_fn,
                                  index=i, name=f"layer_{i}", parent=mdl)
                # A span alone, by the layer's kind: its self time is what
                # JAX did for the layer outside the program's Python.
                spec = cfg.layers[i]
                with _scopes.layer_span(spec.mixer, spec.ffn, spec.window):
                    if shared is None:
                        x = layer(x, *tables[cfg.rope_of(i)])
                    else:
                        x, shared = layer(x, *tables[cfg.rope_of(i)], shared)
            return x

        def norm_f(mdl, x):
            return _stack_norm(cfg, "norm_f", parent=mdl)(x)

        if cfg.total_ut_steps == 1:
            if cfg.hc_mult > 1:
                # The streams cross the layers as ROWS [B, S, n H].  XLA
                # lays a [B, S, n, H] array out in tiles of n rows, which is
                # not how the rows lie: wherever such an array has to exist
                # (a checkpoint's barrier at every layer's edge, both ways)
                # it would be relaid going in and coming out.  So the
                # copies are put side by side here, and the streams added
                # up as lane slices below, not through a fourth axis.
                x = jnp.concatenate([x] * cfg.hc_mult, axis=-1)
            x = one_pass(self, x)
            with _scopes.scope(_scopes.HEAD):
                if cfg.hc_mult > 1:
                    x = functools.reduce(jnp.add, (
                        stream.astype(jnp.float32) for stream in jnp.split(
                            x, cfg.hc_mult, axis=-1))).astype(cfg.dtype)
                x = norm_f(self, x)
            return self.head(x)

        def norm_and_gate(mdl, x):
            with _scopes.scope(_scopes.HEAD):
                x = norm_f(mdl, x)
                gate = nn.Dense(1, dtype=jnp.float32, name="exit_gate",
                                kernel_init=nn.initializers.zeros,
                                parent=mdl)(x.astype(jnp.float32))
                return x, gate[..., 0]

        if cfg.remat != "none":
            # Else a pass keeps three float32 copies of its state for the
            # norm's and the gate's backward (201 MB at 8192 x 2048).
            norm_and_gate = nn.remat(norm_and_gate)

        def pass_and_exit(mdl, x, _):
            x = one_pass(mdl, x)
            with _scopes.scope(_scopes.LOOP_EXIT):
                x, gate = norm_and_gate(mdl, x)
            return x, (x, gate)

        # A scan, not a Python loop: its backward pass is a loop that adds
        # each pass's weight gradients to one accumulator.  Unrolled, XLA
        # fuses the optimizer's update into ONE pass's weight-gradient
        # matmuls and holds their operands until every other pass has
        # contributed: 4.9 GB at 8192 tokens x 2048 (PERF.md, PR 26).
        with _scopes.scope(_scopes.LOOP_PASS):
            _, (hidden, gate_logits) = nn.scan(
                pass_and_exit, variable_broadcast="params",
                split_rngs={"params": False},
                length=cfg.total_ut_steps)(self, x, None)
        if self.is_initializing():
            self.head(hidden[-1])
        return hidden, gate_logits

    @nn.compact
    def head(self, hidden):
        """Normalised hidden states ``[..., H]`` -> logits ``[..., V]``:
        the one output head, which every exit shares."""
        cfg = self.config
        with _scopes.scope(_scopes.HEAD):
            if cfg.tie_word_embeddings:
                # logits = hidden E^T: the embedding's own leaf, read again.
                table = self.get_variable("params", "tok_emb")["embedding"]
                logits = jax.lax.dot_general(
                    hidden.astype(cfg.logits_dtype),
                    table.astype(cfg.logits_dtype),
                    (((hidden.ndim - 1,), (1,)), ((), ())))
            else:
                logits = nn.Dense(cfg.vocab_size, use_bias=False,
                                  dtype=cfg.logits_dtype,
                                  name="lm_head")(hidden)
            if cfg.logits_scaling != 1.0:
                logits = logits / cfg.logits_scaling
            return logits
