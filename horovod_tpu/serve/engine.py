"""The replica's model execution engine: jitted paged prefill/decode.

Owns the parameter pytree and the physical KV block pool, and exposes
two host-level calls the scheduler drives:

* ``prefill(prompt, table)`` — one sequence's prompt through the model
  in a single batched pass, K/V scattered into its funded blocks;
  returns the last-position logits.
* ``decode(tokens, tables, pos)`` — one token for every running
  sequence in a single batched step over the paged pool.

Static shapes via power-of-two padding buckets (prompt length for
prefill, batch width for decode), so each bucket compiles once; padded
batch rows point at the trash block and their outputs are discarded on
the host.  Every forward attends a physical cache of exactly
``max_blocks_per_seq * block_size`` slots — logits depend bitwise on
that length AND on eager-vs-jit program structure, so pinning it makes
serve streams bit-identical to offline ``jax.jit(generate)`` at
``cache_len=max_model_len`` regardless of batch composition
(``tests/test_serve.py`` pins paged ≡ contiguous and serve ≡ offline).

Parameters are built deterministically from ``HOROVOD_SERVE_PARAM_SEED``
so every replica serves identical weights without shipping a checkpoint;
a checkpointed deployment sets ``HOROVOD_SERVE_CHECKPOINT`` (what
``run.py --serve --serve-model <dir>`` does) and every replica loads
the newest complete manifest's ``params`` tree instead — trained
weights at boot, with live trainer pushes layering on top
(docs/checkpointing.md).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Sequence

import numpy as np

from horovod_tpu.serve.config import ServeConfig, _pow2_at_least
from horovod_tpu.serve.kv_cache import TRASH_BLOCK

__all__ = ["ModelRunner", "build_model_config",
           "serve_collective_priority", "SERVE_DECODE_BAND"]

#: The band serve-plane collectives stamp: 0 = most urgent, so decode
#: traffic preempts bulk gradient fusion when a replica shares an engine
#: world with training (the PR 15 priority seam).
SERVE_DECODE_BAND = 0


def serve_collective_priority(environ=None) -> Optional[int]:
    """Priority the serve engine stamps on its collectives, or None when
    stamping does not apply (no engine world, or priority bands off —
    the engine then uses its legacy unstamped path, exactly as before).

    Jax-free and cheap: replicas call it per enqueue.  Only meaningful
    under ``HOROVOD_SERVE_ENGINE=1`` (the replica IS an engine world)
    with ``HOROVOD_PRIORITY_BANDS>0``; serve decode always takes band
    ``SERVE_DECODE_BAND`` (0, most urgent) so mixed serve+train traffic
    dispatches serve first — ``priority_inversions`` stays 0
    (tests/test_priority.py).
    """
    env = os.environ if environ is None else environ
    if env.get("HOROVOD_SERVE_ENGINE") != "1":
        return None
    try:
        bands = int(env.get("HOROVOD_PRIORITY_BANDS", "0") or "0")
    except ValueError:
        bands = 0
    return SERVE_DECODE_BAND if bands > 0 else None


def build_model_config(serve_cfg: ServeConfig):
    """Resolve HOROVOD_SERVE_MODEL/_DTYPE into a LlamaConfig."""
    import jax.numpy as jnp

    from horovod_tpu.models.llama import LlamaConfig

    builder = getattr(LlamaConfig, serve_cfg.model, None)
    if builder is None:
        raise ValueError(f"unknown serve model {serve_cfg.model!r} "
                         "(no LlamaConfig builder of that name)")
    cfg = builder()
    if serve_cfg.dtype:
        dt = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}.get(
            serve_cfg.dtype)
        if dt is None:
            raise ValueError(f"unsupported HOROVOD_SERVE_DTYPE "
                             f"{serve_cfg.dtype!r}")
        cfg = dataclasses.replace(cfg, dtype=dt, logits_dtype=dt)
    cfg.refuse_new_kinds(f"serve model {serve_cfg.model!r}: the paged KV "
                         f"cache")
    if cfg.total_ut_steps > 1:
        raise ValueError(
            f"serve model {serve_cfg.model!r} is a looped model "
            f"(total_ut_steps={cfg.total_ut_steps}): the paged KV cache "
            f"holds one pass over the stack, so it cannot be served")
    return cfg


class ModelRunner:
    """Jitted paged-KV model execution for one replica."""

    def __init__(self, serve_cfg: ServeConfig):
        import jax
        import jax.numpy as jnp

        from horovod_tpu.models.llama import LlamaModel

        self._jax, self._jnp = jax, jnp
        self.serve_cfg = serve_cfg
        self.model_cfg = build_model_config(serve_cfg)
        mcfg = self.model_cfg
        model = LlamaModel(mcfg)
        dummy = jnp.zeros((1, 8), jnp.int32)
        self.variables = model.init(jax.random.key(serve_cfg.param_seed),
                                    dummy)
        #: manifest step the params came from (None = seeded params)
        self.checkpoint_step = None
        if serve_cfg.checkpoint:
            self._restore_checkpoint(serve_cfg.checkpoint)
        self.block_size = serve_cfg.block_size
        self.max_blocks_per_seq = serve_cfg.max_blocks_per_seq
        #: pool blocks INCLUDING the reserved trash block 0
        self.num_blocks = serve_cfg.kv_blocks + 1
        shape = (mcfg.num_layers, self.num_blocks, self.block_size,
                 mcfg.num_kv_heads, mcfg.head_dim)
        self.pool_k = jnp.zeros(shape, mcfg.dtype)
        self.pool_v = jnp.zeros(shape, mcfg.dtype)
        #: fused paged-attention decode (HOROVOD_SERVE_FUSED_ATTN) —
        #: static per runner, baked into every decode jit.
        self.fused_attn = bool(serve_cfg.fused_attn)
        self._prefill_fns: Dict[object, object] = {}
        self._decode_fns: Dict[int, object] = {}
        self.compilations = 0

    def _restore_checkpoint(self, directory: str) -> None:
        """Replace the seeded params with the newest complete
        checkpoint's ``params`` tree (walk-path fill: shape-checked per
        leaf, cast into the model's own dtype).  Raises loudly on a
        torn/absent checkpoint or a geometry mismatch — serving random
        weights silently is worse than not starting."""
        from horovod_tpu.checkpoint import CheckpointError, CheckpointLoader

        loader = CheckpointLoader(directory)
        try:
            if "params" not in loader.slot_names():
                raise CheckpointError(
                    f"checkpoint step {loader.step} in {directory} has "
                    f"no 'params' slot (slots: {loader.slot_names()}) — "
                    "was it written by a trainer capture?")
            variables = dict(self.variables)
            variables["params"] = loader.restore_tree(
                variables["params"], "params")
            self.variables = variables
            self.checkpoint_step = loader.step
        finally:
            loader.close()

    # -- jit caches --

    def _prefill_fn(self, s_pad: int, start_blk: int = 0):
        key = s_pad if start_blk == 0 else (s_pad, start_blk)
        fn = self._prefill_fns.get(key)
        if fn is None:
            from horovod_tpu.models.generation import paged_prefill

            # Physical cache length is pinned to the decode geometry
            # (max_blocks_per_seq * block_size) so prefill and every
            # decode step attend the same reduction shape — the
            # bit-reproducibility contract (see paged_prefill).
            cache_len = self.max_blocks_per_seq * self.block_size

            def impl(variables, pool_k, pool_v, prompt, table, prompt_len):
                return paged_prefill(self.model_cfg, variables, prompt,
                                     pool_k, pool_v, table,
                                     prompt_len=prompt_len,
                                     cache_len=cache_len,
                                     start_blk=start_blk)

            fn = self._jax.jit(impl, donate_argnums=(1, 2))
            self._prefill_fns[key] = fn
            self.compilations += 1
        return fn

    def _prefill_suffix_fn(self, s_pad: int):
        """Prefix-cache hit path: ONE program per suffix bucket, the hit
        offset rides as a traced operand (``paged_prefill_suffix``) —
        compile count stays O(buckets), not O(buckets x hit offsets)."""
        key = ("sfx", s_pad)
        fn = self._prefill_fns.get(key)
        if fn is None:
            from horovod_tpu.models.generation import paged_prefill_suffix

            cache_len = self.max_blocks_per_seq * self.block_size

            def impl(variables, pool_k, pool_v, prompt, table, prompt_len,
                     start):
                return paged_prefill_suffix(self.model_cfg, variables,
                                            prompt, pool_k, pool_v, table,
                                            prompt_len=prompt_len,
                                            start=start,
                                            cache_len=cache_len)

            fn = self._jax.jit(impl, donate_argnums=(1, 2))
            self._prefill_fns[key] = fn
            self.compilations += 1
        return fn

    def _decode_fn(self, b_pad: int):
        fn = self._decode_fns.get(b_pad)
        if fn is None:
            from horovod_tpu.models.generation import paged_decode_step

            fused = self.fused_attn

            def impl(variables, pool_k, pool_v, tokens, tables, pos):
                return paged_decode_step(self.model_cfg, variables, tokens,
                                         pool_k, pool_v, tables, pos,
                                         fused=fused)

            fn = self._jax.jit(impl, donate_argnums=(1, 2))
            self._decode_fns[b_pad] = fn
            self.compilations += 1
        return fn

    # -- host API --

    def warmup(self, max_tokens: int = 0) -> int:
        """Pre-compile the programs steady-state serving will need —
        every pow2 decode batch bucket up to ``max_batch`` and every
        pow2 prefill bucket up to ``max_tokens`` (0 = the
        ``HOROVOD_SERVE_WARMUP`` knob; includes the prefix-cache hit
        path's suffix programs when prefix caching is on).  Run before
        taking traffic so jit compilation lands in replica startup
        rather than inside the first unlucky requests' latency window.
        Dummy operands route every K/V write to the trash block, so no
        allocatable pool block is touched.  Returns the number of
        programs compiled."""
        jnp = self._jnp
        cap = int(max_tokens) or self.serve_cfg.warmup_tokens
        if cap <= 0:
            return 0
        before = self.compilations
        cache_len = self.max_blocks_per_seq * self.block_size
        tbl = jnp.asarray(np.full((self.max_blocks_per_seq,), TRASH_BLOCK,
                                  np.int32))
        b = 1
        while True:
            tbls = jnp.asarray(np.full((b, self.max_blocks_per_seq),
                                       TRASH_BLOCK, np.int32))
            zeros = jnp.zeros((b,), jnp.int32)
            fn = self._decode_fn(b)
            _, self.pool_k, self.pool_v = fn(
                self.variables, self.pool_k, self.pool_v, zeros, tbls,
                zeros)
            if b >= self.serve_cfg.max_batch:
                break
            b *= 2
        s = self.block_size
        top = min(_pow2_at_least(cap, self.block_size), cache_len)
        while s <= top:
            prompt = jnp.zeros((1, s), jnp.int32)
            fn = self._prefill_fn(s)
            _, self.pool_k, self.pool_v = fn(
                self.variables, self.pool_k, self.pool_v, prompt, tbl, s)
            if self.serve_cfg.prefix_cache and self.block_size + s <= \
                    cache_len:
                # Hit-path suffix program for the same bucket; the start
                # offset is traced, so one dummy offset compiles it for
                # every future offset.
                fn = self._prefill_suffix_fn(s)
                _, self.pool_k, self.pool_v = fn(
                    self.variables, self.pool_k, self.pool_v, prompt, tbl,
                    self.block_size + s, self.block_size)
            s *= 2
        return self.compilations - before

    def prefill(self, prompt: Sequence[int], table: Sequence[int],
                *, start: int = 0) -> np.ndarray:
        """Prompt (len S0 >= 1) through the model; ``table`` must fund
        ceil(S0/block_size) blocks.  Returns fp32 last-position logits
        [V].

        ``start`` (block-aligned, < S0) is the prefix-cache hit path:
        the first ``start`` positions' K/V already sit in the table's
        shared leading blocks, so only the suffix is computed — and only
        blocks from ``start // block_size`` on are written (copy-on-
        write).  ``start=0`` is byte-for-byte the pre-prefix-cache
        program; the hit path is bit-identical to it
        (tests/test_serve.py pins both)."""
        jnp = self._jnp
        s0 = len(prompt)
        cache_len = self.max_blocks_per_seq * self.block_size
        if start % self.block_size or not 0 <= start < s0:
            raise ValueError(f"start {start} not block-aligned in [0, {s0})")
        start_blk = start // self.block_size
        # Pow2 bucket of the computed span, for few compiles.
        s_pad = _pow2_at_least(s0 - start, self.block_size)
        dynamic = bool(start) and start + s_pad <= cache_len
        if not dynamic:
            # Clip to the pinned physical cache length (always a block
            # multiple >= any legal prompt/suffix).
            s_pad = min(s_pad, cache_len - start)
        prompt_pad = np.zeros((1, s_pad), np.int32)
        prompt_pad[0, :s0 - start] = np.asarray(prompt[start:], np.int32)
        tbl = np.full((self.max_blocks_per_seq,), TRASH_BLOCK, np.int32)
        tbl[:len(table)] = np.asarray(table, np.int32)
        if dynamic:
            # Hit path: the offset is an operand, one compile per
            # bucket.  The guard keeps the UNCLIPPED padded suffix
            # inside the cache (a clamped dynamic_update_slice would
            # shift the writes); near-end overshoots take the static
            # fallback, whose clipped bucket is start-dependent anyway.
            fn = self._prefill_suffix_fn(s_pad)
            logits, self.pool_k, self.pool_v = fn(
                self.variables, self.pool_k, self.pool_v,
                jnp.asarray(prompt_pad), jnp.asarray(tbl), s0, start)
        else:
            fn = self._prefill_fn(s_pad, start_blk)
            logits, self.pool_k, self.pool_v = fn(
                self.variables, self.pool_k, self.pool_v,
                jnp.asarray(prompt_pad), jnp.asarray(tbl), s0)
        return np.asarray(logits[0]).astype(np.float32)

    def decode(self, tokens: Sequence[int], tables: Sequence[np.ndarray],
               pos: Sequence[int]) -> np.ndarray:
        """One token per running sequence; ``tables[i]`` is a
        [max_blocks_per_seq] int32 array.  Returns fp32 logits [B, V]."""
        jnp = self._jnp
        b = len(tokens)
        b_pad = _pow2_at_least(b, 1)
        toks = np.zeros((b_pad,), np.int32)
        toks[:b] = np.asarray(tokens, np.int32)
        tbls = np.full((b_pad, self.max_blocks_per_seq), TRASH_BLOCK,
                       np.int32)
        for i, t in enumerate(tables):
            tbls[i] = t
        ps = np.zeros((b_pad,), np.int32)
        ps[:b] = np.asarray(pos, np.int32)
        fn = self._decode_fn(b_pad)
        logits, self.pool_k, self.pool_v = fn(
            self.variables, self.pool_k, self.pool_v, jnp.asarray(toks),
            jnp.asarray(tbls), jnp.asarray(ps))
        return np.asarray(logits[:b]).astype(np.float32)
