"""Autoregressive decoding with a KV cache for the Llama family.

No reference equivalent (Horovod 0.15.1 is a training add-on; it serves
models by exporting plain graphs — docs/inference.md).  This module
completes the train→serve story for the flagship model: greedy /
temperature sampling from a ``LlamaModel`` checkpoint with O(1) work per
generated token instead of re-running the full sequence.

Design (TPU-first):
* Pure functions over the ``LlamaModel`` parameter pytree — the exact
  params a train state holds; no module surgery, no separate decode
  checkpoint format.  Forward math mirrors ``models/llama.py`` (RMSNorm
  fp32, RoPE on the fly, GQA, SwiGLU) and is pinned to it by a
  logits-parity test.
* Static shapes end to end: the KV cache is [L, B, S0+N, Hkv, D] from
  the start, the decode loop is one ``lax.scan`` over N steps — a single
  compiled program, no per-step retrace, no dynamic shapes.
* Prefill computes the prompt's logits and cache in one batched pass
  (MXU-friendly), then scan steps decode one token at a time.

MoE configs are not supported here (dense decode path only).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from horovod_tpu.models.llama import LlamaConfig, apply_rope, rope_freqs

__all__ = ["prefill", "decode_step", "generate",
           "paged_prefill", "paged_decode_step"]


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (x32 * scale).astype(x.dtype)


def _attend(q, k, v, *, q_pos, k_len):
    """q: [B,Sq,Hq,D]; k/v: [B,T,Hkv,D] (cache, only [:k_len] valid).
    ``q_pos``: [Sq] global positions.  fp32 logits, GQA via grouping."""
    B, Sq, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    qg = q.reshape(B, Sq, Hkv, Hq // Hkv, D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(jnp.asarray(D, jnp.float32))
    k_pos = jnp.arange(T)
    mask = (k_pos[None, :] <= q_pos[:, None]) & (k_pos[None, :] < k_len)
    logits = jnp.where(mask[None, None, None], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, Hq, D)


def _layer(cfg: LlamaConfig, lp, x, cache_k, cache_v, *, pos0, k_len):
    """One decoder layer over x: [B,S,H], writing K/V at [pos0, pos0+S)
    into this layer's cache [B,T,Hkv,D].  Returns (x, cache_k, cache_v)."""
    D = cfg.head_dim
    B, S, _ = x.shape
    y = _rms(x, lp["norm_attn"]["scale"], cfg.rms_eps)
    a = lp["attn"]
    q = (y @ a["wq"]["kernel"].astype(cfg.dtype)).reshape(
        B, S, cfg.num_heads, D)
    k = (y @ a["wk"]["kernel"].astype(cfg.dtype)).reshape(
        B, S, cfg.num_kv_heads, D)
    v = (y @ a["wv"]["kernel"].astype(cfg.dtype)).reshape(
        B, S, cfg.num_kv_heads, D)
    cos, sin = rope_freqs(D, S, cfg.rope_theta, offset=pos0)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k, (0, pos0, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v, (0, pos0, 0, 0))
    out = _attend(q, cache_k, cache_v,
                  q_pos=jnp.arange(S) + pos0, k_len=k_len)
    x = x + out.reshape(B, S, cfg.num_heads * D) @ \
        a["wo"]["kernel"].astype(cfg.dtype)
    y = _rms(x, lp["norm_mlp"]["scale"], cfg.rms_eps)
    m = lp["mlp"]
    gate, up = jnp.split(y @ m["w_gate_up"]["kernel"].astype(cfg.dtype), 2,
                         axis=-1)
    return x + (jax.nn.silu(gate) * up) @ \
        m["w_down"]["kernel"].astype(cfg.dtype), cache_k, cache_v


def _forward(cfg, p, ids, caches_k, caches_v, *, pos0, k_len):
    x = jnp.take(p["tok_emb"]["embedding"], ids, axis=0).astype(cfg.dtype)
    new_k, new_v = [], []
    for i in range(cfg.num_layers):
        x, ck, cv = _layer(cfg, p[f"layer_{i}"], x, caches_k[i],
                           caches_v[i], pos0=pos0, k_len=k_len)
        new_k.append(ck)
        new_v.append(cv)
    x = _rms(x, p["norm_f"]["scale"], cfg.rms_eps)
    # Same head dtype as LlamaModel (cfg.logits_dtype) so cached decode
    # is logit-exact against model.apply.
    logits = (x.astype(cfg.logits_dtype)
              @ p["lm_head"]["kernel"].astype(cfg.logits_dtype))
    return logits, jnp.stack(new_k), jnp.stack(new_v)


def _params(variables):
    return variables["params"] if "params" in variables else variables


def _check_supported(cfg: LlamaConfig) -> None:
    cfg.refuse_new_kinds("KV-cache decode")
    if cfg.total_ut_steps > 1:
        raise NotImplementedError(
            f"KV-cache decode walks the layer stack once; a looped model "
            f"(total_ut_steps={cfg.total_ut_steps}) needs a cache for "
            f"every pass and an exit rule, which are not built")


def prefill(cfg: LlamaConfig, variables, prompt_ids, *, cache_len: int):
    """Run the prompt [B, S0] through the model once, returning
    (last-position logits [B, V], kv_cache) with caches sized
    ``cache_len`` (>= S0 + tokens to generate)."""
    _check_supported(cfg)
    p = _params(variables)
    B, S0 = prompt_ids.shape
    shape = (cfg.num_layers, B, cache_len, cfg.num_kv_heads, cfg.head_dim)
    ck = jnp.zeros(shape, cfg.dtype)
    cv = jnp.zeros(shape, cfg.dtype)
    logits, ck, cv = _forward(cfg, p, prompt_ids, ck, cv, pos0=0, k_len=S0)
    return logits[:, -1], (ck, cv)


def decode_step(cfg: LlamaConfig, variables, token, cache, *, pos):
    """One token [B] in, next-position logits [B, V] out; ``pos`` is the
    token's global position (traced ok)."""
    _check_supported(cfg)
    p = _params(variables)
    ck, cv = cache
    logits, ck, cv = _forward(cfg, p, token[:, None], ck, cv,
                              pos0=pos, k_len=pos + 1)
    return logits[:, -1], (ck, cv)


def generate(cfg: LlamaConfig, variables, prompt_ids, *,
             max_new_tokens: int, temperature: float = 0.0,
             rng: Optional[jax.Array] = None,
             cache_len: Optional[int] = None):
    """Generate ``max_new_tokens`` continuations of ``prompt_ids`` [B, S0].

    ``temperature == 0`` is greedy argmax; otherwise softmax sampling at
    the given temperature (``rng`` required).  Returns [B, max_new_tokens].
    Wrap in ``jax.jit`` (static cfg/max_new_tokens) for production use —
    the loop is a single ``lax.scan``, so it compiles once.

    ``cache_len`` pins the physical KV length (default: exactly
    ``S0 + max_new_tokens``).  Logits are a deterministic function of
    the prompt AND this physical length — XLA's reduction grouping over
    the key axis varies with it, so near-tied logits can argmax
    differently at different lengths.  The serving stack runs every
    forward at ``cache_len = max_model_len``; pass the same value here
    to get the bit-identical reference stream (tests/test_serve.py).
    """
    if temperature > 0 and rng is None:
        raise ValueError("temperature sampling needs an rng key")
    B, S0 = prompt_ids.shape
    if cache_len is None:
        cache_len = S0 + max_new_tokens
    if cache_len < S0 + max_new_tokens:
        raise ValueError(f"cache_len {cache_len} < prompt + new tokens "
                         f"{S0 + max_new_tokens}")
    logits, cache = prefill(cfg, variables, prompt_ids,
                            cache_len=cache_len)

    def pick(logits, key):
        if temperature <= 0:
            return jnp.argmax(logits, -1).astype(prompt_ids.dtype)
        return jax.random.categorical(
            key, logits / temperature, -1).astype(prompt_ids.dtype)

    keys = (jax.random.split(rng, max_new_tokens) if rng is not None
            else jnp.zeros((max_new_tokens, 2), jnp.uint32))
    tok0 = pick(logits, keys[0] if rng is not None else None)

    def body(carry, key_pos):
        tok, cache = carry
        key, pos = key_pos
        logits, cache = decode_step(cfg, variables, tok, cache, pos=pos)
        nxt = pick(logits, key if rng is not None else None)
        return (nxt, cache), nxt  # emit the NEW token

    # Step i consumes the token at global position S0+i and produces the
    # token for position S0+i+1; tok0 (from prefill) is position S0.
    (_, _), rest = jax.lax.scan(
        body, (tok0, cache),
        (keys[1:], S0 + jnp.arange(max_new_tokens - 1)))
    return jnp.concatenate([tok0[:, None], rest.T], axis=1)  # [B, N]


# ---------------------------------------------------------------------------
# Paged (block-table) KV cache — the serving data path (horovod_tpu/serve/).
#
# The cache is a pool of fixed-size blocks [L, NB, BS, Hkv, D]; each
# sequence owns a table of physical block ids covering its logical
# positions.  The decode math gathers a sequence's blocks back into a
# contiguous [T, Hkv, D] view and then runs the EXACT per-element
# operations of the contiguous path above — a gather is a permutation
# copy, so paged ≡ contiguous bit-for-bit at equal physical length
# (tests/test_serve.py pins it).  Physical block id 0 is the TRASH block:
# padded batch rows and unfunded table entries point at it, it is written
# by every padded row and never read by a live one.
# ---------------------------------------------------------------------------


def _rope_at(head_dim: int, positions, theta: float):
    """cos/sin [B, head_dim/2] at per-sequence ``positions`` [B] — the
    batched counterpart of ``rope_freqs(head_dim, 1, theta, offset=p)``,
    computed with the identical fp32 ops so the bits match."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    t = positions.astype(jnp.float32)
    ang = t[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _apply_rope_b(x, cos, sin):
    """apply_rope with per-batch-row tables: x [B, 1, H, D]; cos/sin
    [B, D/2]."""
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    c = cos[:, None, None, :]
    s = sin[:, None, None, :]
    r1 = x1 * c - x2 * s
    r2 = x1 * s + x2 * c
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def _attend_b(q, k, v, *, q_pos, k_len):
    """_attend with per-sequence positions: q [B,1,Hq,D]; k/v [B,T,Hkv,D];
    ``q_pos``/``k_len`` [B].  Same einsum strings / fp32 logits / mask
    value as :func:`_attend`, so valid entries carry identical bits."""
    B, Sq, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    qg = q.reshape(B, Sq, Hkv, Hq // Hkv, D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(jnp.asarray(D, jnp.float32))
    k_pos = jnp.arange(T)
    mask = (k_pos[None, :] <= q_pos[:, None]) & \
        (k_pos[None, :] < k_len[:, None])                      # [B, T]
    logits = jnp.where(mask[:, None, None, None, :], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Sq, Hq, D)


def _paged_layer(cfg: LlamaConfig, lp, x, pk, pv, tables, *, pos,
                 fused: bool = False):
    """One decoder layer over one decode token per sequence.

    x: [B, 1, H]; pk/pv: this layer's pool [NB, BS, Hkv, D];
    tables: [B, MAXB] physical block ids; pos: [B] global positions.
    Writes K/V at each sequence's ``pos`` slot, then attends — via the
    gather + :func:`_attend_b` oracle by default, or via the fused
    paged-attention kernel (``ops/paged_attention.py``: block-table
    reads, no contiguous staging) when ``fused``.  Returns (x, pk, pv).
    """
    D = cfg.head_dim
    B, S, _ = x.shape
    bs = pk.shape[1]
    y = _rms(x, lp["norm_attn"]["scale"], cfg.rms_eps)
    a = lp["attn"]
    q = (y @ a["wq"]["kernel"].astype(cfg.dtype)).reshape(
        B, S, cfg.num_heads, D)
    k = (y @ a["wk"]["kernel"].astype(cfg.dtype)).reshape(
        B, S, cfg.num_kv_heads, D)
    v = (y @ a["wv"]["kernel"].astype(cfg.dtype)).reshape(
        B, S, cfg.num_kv_heads, D)
    cos, sin = _rope_at(D, pos, cfg.rope_theta)
    q, k = _apply_rope_b(q, cos, sin), _apply_rope_b(k, cos, sin)
    blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
    off = pos % bs
    pk = pk.at[blk, off].set(k[:, 0])
    pv = pv.at[blk, off].set(v[:, 0])
    if fused:
        from horovod_tpu.ops.paged_attention import paged_attention_decode

        out = paged_attention_decode(q, pk, pv, tables, pos)
    else:
        maxb = tables.shape[1]
        ck = pk[tables].reshape(B, maxb * bs, cfg.num_kv_heads, D)
        cv = pv[tables].reshape(B, maxb * bs, cfg.num_kv_heads, D)
        out = _attend_b(q, ck, cv, q_pos=pos, k_len=pos + 1)
    x = x + out.reshape(B, S, cfg.num_heads * D) @ \
        a["wo"]["kernel"].astype(cfg.dtype)
    y = _rms(x, lp["norm_mlp"]["scale"], cfg.rms_eps)
    m = lp["mlp"]
    gate, up = jnp.split(y @ m["w_gate_up"]["kernel"].astype(cfg.dtype), 2,
                         axis=-1)
    return x + (jax.nn.silu(gate) * up) @ \
        m["w_down"]["kernel"].astype(cfg.dtype), pk, pv


def paged_decode_step(cfg: LlamaConfig, variables, tokens, pool_k, pool_v,
                      tables, pos, *, fused: bool = False):
    """One decode step for a batch of independent sequences over the
    paged pool.

    tokens: [B] current token per sequence; pool_k/pool_v:
    [L, NB, BS, Hkv, D]; tables: [B, MAXB] int32 block tables (unused
    tail entries and padded rows point at trash block 0); pos: [B]
    global position of each token.  Returns (next-position logits
    [B, V], pool_k, pool_v).  Rows are computed independently — a padded
    row (pos 0, all-trash table) produces garbage logits the caller
    discards, and never perturbs a live row.

    ``fused`` (static under jit) selects the fused paged-attention
    kernel instead of the gather oracle; numerically equivalent within
    the documented tolerance, argmax-stable on the greedy corpus, but
    NOT bitwise identical (online softmax re-associates the key
    reduction) — ``HOROVOD_SERVE_FUSED_ATTN=0`` keeps the oracle.
    """
    _check_supported(cfg)
    p = _params(variables)
    x = jnp.take(p["tok_emb"]["embedding"], tokens[:, None],
                 axis=0).astype(cfg.dtype)
    new_k, new_v = [], []
    for i in range(cfg.num_layers):
        x, pk, pv = _paged_layer(cfg, p[f"layer_{i}"], x, pool_k[i],
                                 pool_v[i], tables, pos=pos, fused=fused)
        new_k.append(pk)
        new_v.append(pv)
    x = _rms(x, p["norm_f"]["scale"], cfg.rms_eps)
    logits = (x.astype(cfg.logits_dtype)
              @ p["lm_head"]["kernel"].astype(cfg.logits_dtype))
    return logits[:, -1], jnp.stack(new_k), jnp.stack(new_v)


def paged_prefill(cfg: LlamaConfig, variables, prompt_ids, pool_k, pool_v,
                  table, *, prompt_len, cache_len=None, start_blk: int = 0):
    """Prefill one sequence's (padded) prompt into its pool blocks.

    prompt_ids: [1, S_pad] with S_pad a multiple of the block size
    (positions >= ``prompt_len`` may hold any id — their K/V rows land in
    cache slots that every later read either masks or overwrites);
    table: [cache_len/BS] physical block ids (unfunded tail = trash 0);
    ``prompt_len`` may be traced.  Returns (logits at the last prompt
    position [1, V], pool_k, pool_v).

    ``cache_len`` (default S_pad) is the physical length of the
    temporary contiguous cache the prompt attends over.  Logits depend
    bitwise on this length (reduction-order effect — see
    :func:`generate`), so the serving engine pins it to
    ``max_model_len``: prefill then attends the exact geometry the
    block-table decode steps do, and the whole serve stream is
    bit-reproducible against offline ``generate()`` at that
    ``cache_len``.

    ``start_blk`` (static) > 0 is the prefix-cache hit path: the first
    ``start_blk`` table blocks already hold this prompt's K/V (shared,
    content-hash matched — serve/kv_cache.py), ``prompt_ids`` is the
    PADDED SUFFIX starting at position ``start_blk * BS``, and only the
    suffix is computed.  The temporary contiguous cache is seeded by
    gathering the whole table from the pool — a permutation copy, so the
    shared positions carry the exact bits a full prefill of the same
    content would recompute — and only blocks ``>= start_blk`` are
    scattered back: shared blocks are never written (the copy-on-write
    invariant).  Positions beyond ``prompt_len`` hold junk from unfunded
    table entries; the ``k_len`` mask zeroes them exactly (finfo.min →
    exp → 0), so the hit path is bit-identical to the full prefill
    (tests/test_serve.py pins it).
    """
    _check_supported(cfg)
    p = _params(variables)
    B, S_pad = prompt_ids.shape
    bs = pool_k.shape[2]
    if cache_len is None:
        cache_len = S_pad
    nb = cache_len // bs
    if start_blk == 0:
        shape = (cfg.num_layers, B, cache_len, cfg.num_kv_heads,
                 cfg.head_dim)
        ck = jnp.zeros(shape, cfg.dtype)
        cv = jnp.zeros(shape, cfg.dtype)
        logits, ck, cv = _forward(cfg, p, prompt_ids, ck, cv, pos0=0,
                                  k_len=prompt_len)
        last = jax.lax.dynamic_index_in_dim(logits, prompt_len - 1, axis=1,
                                            keepdims=False)
        pool_k = pool_k.at[:, table].set(
            ck[:, 0].reshape(cfg.num_layers, nb, bs, cfg.num_kv_heads,
                             cfg.head_dim))
        pool_v = pool_v.at[:, table].set(
            cv[:, 0].reshape(cfg.num_layers, nb, bs, cfg.num_kv_heads,
                             cfg.head_dim))
        return last, pool_k, pool_v
    start = start_blk * bs
    ck = pool_k[:, table].reshape(cfg.num_layers, cache_len,
                                  cfg.num_kv_heads, cfg.head_dim)[:, None]
    cv = pool_v[:, table].reshape(cfg.num_layers, cache_len,
                                  cfg.num_kv_heads, cfg.head_dim)[:, None]
    logits, ck, cv = _forward(cfg, p, prompt_ids, ck, cv, pos0=start,
                              k_len=prompt_len)
    last = jax.lax.dynamic_index_in_dim(logits, prompt_len - 1 - start,
                                        axis=1, keepdims=False)
    tail = table[start_blk:]
    pool_k = pool_k.at[:, tail].set(
        ck[:, 0, start:].reshape(cfg.num_layers, nb - start_blk, bs,
                                 cfg.num_kv_heads, cfg.head_dim))
    pool_v = pool_v.at[:, tail].set(
        cv[:, 0, start:].reshape(cfg.num_layers, nb - start_blk, bs,
                                 cfg.num_kv_heads, cfg.head_dim))
    return last, pool_k, pool_v


def paged_prefill_suffix(cfg: LlamaConfig, variables, prompt_ids, pool_k,
                         pool_v, table, *, prompt_len, start, cache_len):
    """The prefix-cache hit path with a TRACED ``start``.

    Identical math to :func:`paged_prefill` with ``start_blk > 0`` —
    gather-seed the contiguous cache from the whole table, run only the
    padded suffix through the model at ``pos0=start`` — but ``start``
    (block-aligned positions, ``0 < start < prompt_len``) is an operand,
    so ONE compiled program serves every hit offset at a given suffix
    bucket instead of one per ``(bucket, start_blk)`` pair.  The price
    of the dynamic offset is the scatter-back: with no static block
    split available, the WHOLE table is written.  That stays correct
    under copy-on-write because positions below ``start`` pass through
    ``_forward`` untouched from the gather seed, so every shared block
    is rewritten with exactly its own bytes — shared content never
    changes.  The caller must guarantee ``start + S_pad <= cache_len``
    (a clamped ``dynamic_update_slice`` would silently shift the
    writes); the engine falls back to the static path otherwise.
    """
    _check_supported(cfg)
    p = _params(variables)
    bs = pool_k.shape[2]
    nb = cache_len // bs
    ck = pool_k[:, table].reshape(cfg.num_layers, cache_len,
                                  cfg.num_kv_heads, cfg.head_dim)[:, None]
    cv = pool_v[:, table].reshape(cfg.num_layers, cache_len,
                                  cfg.num_kv_heads, cfg.head_dim)[:, None]
    logits, ck, cv = _forward(cfg, p, prompt_ids, ck, cv, pos0=start,
                              k_len=prompt_len)
    last = jax.lax.dynamic_index_in_dim(logits, prompt_len - 1 - start,
                                        axis=1, keepdims=False)
    pool_k = pool_k.at[:, table].set(
        ck[:, 0].reshape(cfg.num_layers, nb, bs, cfg.num_kv_heads,
                         cfg.head_dim))
    pool_v = pool_v.at[:, table].set(
        cv[:, 0].reshape(cfg.num_layers, nb, bs, cfg.num_kv_heads,
                         cfg.head_dim))
    return last, pool_k, pool_v
