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
* Optional MoE (``num_experts > 1``): top-k routed experts via einsum
  dispatch/combine, the expert-parallel workload.
* Optional weight-shared passes over the stack (``total_ut_steps > 1``,
  the looped LM of Ouro / LoopLM) with a per-token exit gate, and
  recomputation of each layer in the backward pass (``remat``); see
  ``LlamaModel``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_policies

from horovod_tpu.common import scopes as _scopes

__all__ = ["LlamaConfig", "LlamaModel", "RMSNorm", "apply_rope",
           "causal_attention"]


REMAT_POLICIES = {
    # Nothing of a layer is kept but its input: the backward pass runs the
    # layer's forward again, the flash kernel's forward call included.
    "layer": None,
    # The flash kernel's output and row statistics are kept as well (34 MB
    # a layer at 8192 tokens x 2048), so its forward call is not repeated.
    "layer_keep_attention": checkpoint_policies.save_only_these_names(
        _scopes.FLASH_OUT_NAME, _scopes.FLASH_LSE_NAME),
}


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    """Sizes and options of ``LlamaModel``.

    ``total_ut_steps`` (the published key of Ouro's ``config.json``) is the
    number of weight-shared passes over the layer stack.  1 is the plain
    decoder: one walk over the layers, logits out.  With T > 1 the same
    ``num_layers`` modules (one parameter set) are applied T times, the
    final norm ends every pass, an exit gate reads every pass's output,
    and the model returns the T normalised hidden states and gate logits
    in place of logits (``LlamaModel``; the loss is
    ``ops.losses.expected_exit_loss``).  ``models/generation.py`` and the
    serve plane refuse T > 1 (a served looped model keeps a cache a pass),
    and so does the pipelined step, which walks the stack once.
    ``ring_attention`` as ``attention_fn`` and the MoE block are untested
    with T > 1.

    ``remat`` names what the backward pass recomputes: ``"none"``;
    ``"layer"`` (each layer application keeps its input alone);
    ``"layer_keep_attention"`` (and the flash kernel's output and row
    statistics).  Four passes hold four times one pass's activations,
    so a looped model at a long sequence needs one of the last two.
    """

    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    intermediate_size: int = 11008
    max_seq_len: int = 8192
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    num_experts: int = 1          # >1 enables MoE
    experts_per_token: int = 2
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
        return self.hidden_size // self.num_heads


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1,
                                           keepdims=True) + self.eps)
        return (x32 * scale).astype(self.dtype)


def rope_freqs(head_dim: int, seq_len: int, theta: float,
               offset=0) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables [S, head_dim/2] in fp32.  ``offset`` may be a traced
    value (sequence-parallel shards pass ``axis_index * S_local``)."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32) + offset
    ang = jnp.outer(t, inv)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate pairs (x[..., ::2], x[..., 1::2]).  x: [B, S, H, D]."""
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    r1 = x1 * c - x2 * s
    r2 = x1 * s + x2 * c
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def causal_attention(q, k, v, *, q_offset: int = 0):
    """Default causal attention, fp32 logits, GQA-aware.

    q: [B, Sq, Hq, D]; k, v: [B, Sk, Hkv, D] with Hq % Hkv == 0.
    ``q_offset``: global position of q[0] (for decode / sequence shards).
    """
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, group, D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(jnp.asarray(D, jnp.float32))
    q_pos = jnp.arange(Sq) + q_offset
    k_pos = jnp.arange(k.shape[1])
    mask = q_pos[:, None] >= k_pos[None, :]
    logits = jnp.where(mask[None, None, None], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, Hq, D)


class LlamaAttention(nn.Module):
    config: LlamaConfig
    attention_fn: Callable = staticmethod(causal_attention)

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        B, S, _ = x.shape
        D = cfg.head_dim
        q = nn.Dense(cfg.num_heads * D, use_bias=False, dtype=cfg.dtype,
                     name="wq")(x).reshape(B, S, cfg.num_heads, D)
        k = nn.Dense(cfg.num_kv_heads * D, use_bias=False, dtype=cfg.dtype,
                     name="wk")(x).reshape(B, S, cfg.num_kv_heads, D)
        v = nn.Dense(cfg.num_kv_heads * D, use_bias=False, dtype=cfg.dtype,
                     name="wv")(x).reshape(B, S, cfg.num_kv_heads, D)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        out = self.attention_fn(q, k, v)
        out = out.reshape(B, S, cfg.num_heads * D)
        return nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                        name="wo")(out)


class SwiGLU(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        # Fused gate+up: one [H, 2F] matmul.
        gu = nn.Dense(2 * cfg.intermediate_size, use_bias=False,
                      dtype=cfg.dtype, name="w_gate_up")(x)
        gate, up = jnp.split(gu, 2, axis=-1)
        return nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                        name="w_down")(nn.silu(gate) * up)


class MoEBlock(nn.Module):
    """Top-k routed mixture of SwiGLU experts (expert-parallel workload).

    Dense dispatch/combine via einsum — dynamic-shape-free so it shards
    cleanly over an ``expert`` mesh axis.
    """

    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, S, H = x.shape
        E, K = cfg.num_experts, cfg.experts_per_token
        router = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                          name="router")(x.astype(jnp.float32))   # [B,S,E]
        weights, sel = jax.lax.top_k(jax.nn.softmax(router, -1), K)
        weights = weights / jnp.sum(weights, -1, keepdims=True)
        one_hot = jax.nn.one_hot(sel, E, dtype=cfg.dtype)          # [B,S,K,E]
        combine = jnp.einsum("bske,bsk->bse", one_hot,
                             weights.astype(cfg.dtype))            # [B,S,E]
        # Expert-batched weights: [E, H, 2F] and [E, F, H].
        w_gu = self.param("w_gate_up", nn.initializers.lecun_normal(),
                          (E, H, 2 * cfg.intermediate_size)).astype(cfg.dtype)
        w_down = self.param("w_down", nn.initializers.lecun_normal(),
                            (E, cfg.intermediate_size, H)).astype(cfg.dtype)
        sel_mask = (combine != 0).astype(cfg.dtype)                # [B,S,E]
        xe = jnp.einsum("bsh,bse->ebsh", x, sel_mask)              # masked copy
        gu = jnp.einsum("ebsh,ehf->ebsf", xe, w_gu)
        gate, up = jnp.split(gu, 2, axis=-1)
        ye = jnp.einsum("ebsf,efh->ebsh", nn.silu(gate) * up, w_down)
        return jnp.einsum("ebsh,bse->bsh", ye, combine)


class LlamaLayer(nn.Module):
    config: LlamaConfig
    attention_fn: Callable = staticmethod(causal_attention)

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        y = RMSNorm(cfg.rms_eps, cfg.dtype, name="norm_attn")(x)
        x = x + LlamaAttention(cfg, attention_fn=self.attention_fn,
                               name="attn")(y, cos, sin)
        y = RMSNorm(cfg.rms_eps, cfg.dtype, name="norm_mlp")(x)
        if cfg.num_experts > 1:
            x = x + MoEBlock(cfg, name="moe")(y)
        else:
            x = x + SwiGLU(cfg, name="mlp")(y)
        return x


class LlamaModel(nn.Module):
    """Decoder-only LM; with ``config.total_ut_steps`` T > 1 a looped one.

    T = 1: ``tokens [B, S] -> logits [B, S, V]``.

    T > 1 (Ouro / LoopLM; Zhu et al., arXiv:2510.25741).  With E the
    embedding, Stack the ``num_layers`` layers in order, N the final
    RMSNorm, W the untied head::

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
        cos, sin = rope_freqs(cfg.head_dim, S, cfg.rope_theta,
                              offset=positions_offset)
        layer_cls = LlamaLayer
        if cfg.remat != "none":
            layer_cls = nn.remat(LlamaLayer,
                                 policy=REMAT_POLICIES[cfg.remat])

        def one_pass(mdl, x):
            """Stack(x), its modules made under ``mdl`` by name."""
            for i in range(cfg.num_layers):
                x = layer_cls(cfg, attention_fn=self.attention_fn,
                              name=f"layer_{i}", parent=mdl)(x, cos, sin)
            return x

        def norm_f(mdl, x):
            return RMSNorm(cfg.rms_eps, cfg.dtype, name="norm_f",
                           parent=mdl)(x)

        if cfg.total_ut_steps == 1:
            return self.head(norm_f(self, one_pass(self, x)))

        def norm_and_gate(mdl, x):
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
            with jax.named_scope(_scopes.LOOP_EXIT):
                x, gate = norm_and_gate(mdl, x)
            return x, (x, gate)

        # A scan, not a Python loop: its backward pass is a loop that adds
        # each pass's weight gradients to one accumulator.  Unrolled, XLA
        # fuses the optimizer's update into ONE pass's weight-gradient
        # matmuls and holds their operands until every other pass has
        # contributed: 4.9 GB at 8192 tokens x 2048 (PERF.md, PR 26).
        with jax.named_scope(_scopes.LOOP_PASS):
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
        return nn.Dense(cfg.vocab_size, use_bias=False,
                        dtype=cfg.logits_dtype, name="lm_head")(hidden)
