"""Chip smoke: the trainer's main path, once, on the TPU, at full width.

    python3 chip_smoke.py        # from the root of a checkout, on a TPU host

One process.  ``hvd.init()`` -> ``hvd.data_parallel_mesh()`` ->
``hvd.DistributedOptimizer`` -> ``hvd.make_train_step`` -> a few steps of
a 400M decoder (hidden 1024, 16 layers, 8 heads x 128,
FFN 4096, vocab 32000; 8 sequences of 2048 tokens a chip; flash attention,
bf16 params + fp32 masters + AdamW) over every chip JAX reports, after a
short agreement check of the two Pallas kernels against their XLA
references.  Weights and the one batch come from fixed seeds.

It is a proof that the system starts and computes the right thing on the
device — not a benchmark: the step time it prints is informational.
Without a TPU it exits non-zero before it builds anything; there is no CPU
mode.  Any failed check or exception is a non-zero exit and no result
line.  The last line of stdout is the verdict and nothing else,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as JAX reports it; the line before it, ``[chip_smoke]
report {...}``, carries what was measured on the way.
"""

from __future__ import annotations

import json
import math
import re
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu.jax as hvd
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.models.llama import causal_attention
from horovod_tpu.ops import paged_attention
from horovod_tpu.ops.flash_attention import flash_attention, flash_attention_fn
from horovod_tpu.ops.losses import softmax_cross_entropy
from horovod_tpu.ops.mixed_precision import cast_compute, master_weights

#: A 400M decoder, whole: no width or depth cut.
CONFIG = dict(vocab_size=32000, hidden_size=1024, num_layers=16, num_heads=8,
              num_kv_heads=8, intermediate_size=4096, max_seq_len=2048)
BATCH_PER_CHIP, SEQ, STEPS = 8, 2048, 8

# bf16 flash attention against the dense fp32 reference at "highest" matmul
# precision.  Outputs are convex combinations of N(0,1) values, |o| <~ 1:
# the kernel rounds P and O to bf16 (2^-9 relative) and the repo's own
# figure for that is ~2e-2 on values (SKILL.md, "Flash-attention variants
# on the real chip").  A wrong causal edge or a dropped KV block moves
# early rows by O(0.1-1), ten times the bound.
FLASH_VALUE_TOL = 2e-2
# Gradients of sum(out * w), judged relative to the largest reference
# entry of each gradient: bf16 rounding of P, dS and the operands gives a
# few 1e-3 of the largest entry; a mask or block defect moves entries by
# the size of the entries themselves.
FLASH_GRAD_REL_TOL = 2e-2
# Paged decode at fp32 inputs: kernel and XLA twin share the math and
# differ only by re-association of the online softmax; the repo's bound is
# 1e-4 (tests/test_serve.py FUSED_TOL).  One skipped or misaddressed block
# of 16 keys among 2048 moves a row by O(1e-2).  BOTH sides run at
# "highest" matmul precision: on the MXU an fp32 dot at default precision
# is one bf16 pass, which put the kernel 6.3e-3 from the reference on the
# v5e (PR 21) — rounding the XLA twin shares at default precision, and
# enough to hide exactly the defects this check exists for.
PAGED_TOL = 1e-4

_MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def check_flash(batch: int, seq: int, heads: int, head_dim: int) -> dict:
    """Non-interpreted flash attention, forward and ``jax.grad``, against
    dense ``causal_attention`` at the train step's own attention shape."""
    kq, kk, kv, kw = jax.random.split(jax.random.key(1), 4)
    shape = (batch, seq, heads, head_dim)
    q, k, v = (jax.random.normal(key, shape, jnp.bfloat16)
               for key in (kq, kk, kv))
    w = jax.random.normal(kw, shape, jnp.float32)

    def grads_of(attn):
        # w rides as an argument: closed over, its 67 MB would be baked
        # into the executable as a constant.
        return jax.jit(jax.grad(
            lambda q, k, v, w: jnp.sum(attn(q, k, v).astype(jnp.float32) * w),
            argnums=(0, 1, 2)))

    flash = jax.jit(flash_attention).lower(q, k, v).compile()
    require(_MOSAIC_CALL in flash.as_text(),
            "flash_attention did not lower to a Mosaic kernel")
    out = flash(q, k, v)
    grads = grads_of(flash_attention)(q, k, v, w)

    q32, k32, v32 = (t.astype(jnp.float32) for t in (q, k, v))
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(causal_attention)(q32, k32, v32)
        ref_grads = grads_of(causal_attention)(q32, k32, v32, w)

    value_err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    grad_err = max(
        float(jnp.max(jnp.abs(g.astype(jnp.float32) - r))
              / jnp.max(jnp.abs(r)))
        for g, r in zip(grads, ref_grads))
    say(f"flash vs dense at {shape}: value max|err| {value_err:.2e} "
        f"(tol {FLASH_VALUE_TOL}), grad max rel-to-peak err {grad_err:.2e} "
        f"(tol {FLASH_GRAD_REL_TOL})")
    require(math.isfinite(value_err) and value_err < FLASH_VALUE_TOL,
            f"flash forward off the dense reference by {value_err}")
    require(math.isfinite(grad_err) and grad_err < FLASH_GRAD_REL_TOL,
            f"flash gradient off the dense reference by {grad_err}")
    return {"value_max_abs_err": value_err, "grad_max_rel_err": grad_err}


def check_paged(q_heads: int, kv_heads: int, *, table_blocks: int = 128,
                block_size: int = 16) -> float:
    """Pallas paged decode against its blockwise XLA twin: four rows over
    scattered physical blocks, ending inside a block, at a block's start
    and on the table's last slot, unfunded table entries on trash block 0."""
    rows, head_dim = 4, 128
    rng = np.random.default_rng(2)
    n_blocks = rows * table_blocks + 1
    kq, kk, kv = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(kq, (rows, 1, q_heads, head_dim), jnp.float32)
    pool = (n_blocks, block_size, kv_heads, head_dim)
    pool_k = jax.random.normal(kk, pool, jnp.float32)
    pool_v = jax.random.normal(kv, pool, jnp.float32)
    last = table_blocks * block_size - 1
    pos = np.asarray([5, last // 3, 2 * (last // 3 // block_size) * block_size,
                      last], np.int32)
    tables = rng.permutation(np.arange(1, n_blocks)).reshape(
        rows, table_blocks).astype(np.int32)
    tables[np.arange(table_blocks)[None, :] > (pos // block_size)[:, None]] = 0
    tables, pos = jnp.asarray(tables), jnp.asarray(pos)

    args = (q, pool_k, pool_v, tables, pos)
    with jax.default_matmul_precision("highest"):
        fused = jax.jit(
            paged_attention.paged_attention_decode).lower(*args).compile()
        require(_MOSAIC_CALL in fused.as_text(),
                "paged_attention_decode did not lower to a Mosaic kernel")
        out = fused(*args)
        ref = jax.jit(paged_attention._decode_blockwise)(*args)
    err = float(jnp.max(jnp.abs(out - ref)))
    say(f"paged decode (Hq,Hkv)=({q_heads},{kv_heads}) BS={block_size} "
        f"table {rows}x{table_blocks}: max|err| {err:.2e} (tol {PAGED_TOL})")
    require(math.isfinite(err) and err < PAGED_TOL,
            f"paged decode ({q_heads},{kv_heads}) off its XLA twin by {err}")
    return err


def train(cfg: LlamaConfig, batch_per_chip: int, seq: int, steps: int) -> dict:
    """The main path: mesh over every chip, state replicated and the batch
    sharded BEFORE step 1, ``steps`` blocked steps on one fixed batch."""
    n_chips = jax.device_count()
    mesh = hvd.data_parallel_mesh()
    replicated = NamedSharding(mesh, P())
    model = LlamaModel(cfg, attention_fn=flash_attention_fn)
    tokens = jax.device_put(
        np.random.default_rng(0).integers(
            0, cfg.vocab_size, (batch_per_chip * n_chips, seq + 1),
            dtype=np.int32),
        NamedSharding(mesh, P("data")))
    params = jax.jit(
        lambda: cast_compute(model.init(jax.random.key(0),
                                        jnp.zeros((1, seq), jnp.int32))),
        out_shardings=replicated)()
    opt = hvd.DistributedOptimizer(master_weights(optax.adamw(3e-4)))
    opt_state = jax.jit(opt.inner.init, out_shardings=replicated)(params)

    def loss_fn(params, batch_tokens):
        logits = model.apply(params, batch_tokens[:, :-1])
        return softmax_cross_entropy(logits, batch_tokens[:, 1:])

    step = hvd.make_train_step(loss_fn, opt, mesh)

    # Compile ahead of time, alone on the clock, and ask JAX's own
    # monitoring whether the persistent cache served it.
    cache_hits = []
    jax.monitoring.register_event_listener(
        lambda event, **_: cache_hits.append(event)
        if event == "/jax/compilation_cache/cache_hits" else None)
    lowered = step.lower(params, opt_state, tokens)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    cache_hit = bool(cache_hits)
    hlo = compiled.as_text()
    mosaic_calls = hlo.count(_MOSAIC_CALL)
    all_reduces = len(re.findall(r"\ball-reduce(?:-start)?\(", hlo))
    memory = compiled.memory_analysis()
    say(f"step compiled in {compile_s:.1f} s (persistent cache "
        f"{'hit' if cache_hit else 'miss'}): {mosaic_calls} Mosaic calls, "
        f"{all_reduces} all-reduces, arguments "
        f"{memory.argument_size_in_bytes / 1e9:.2f} GB + temporaries "
        f"{memory.temp_size_in_bytes / 1e9:.2f} GB a device")

    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, tokens)
        jax.block_until_ready((params, opt_state, loss))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    say("losses " + " ".join(f"{x:.4f}" for x in losses))
    say("step s " + " ".join(f"{x:.3f}" for x in step_s)
        + "  (the first includes jit's own trace and cache lookup)")

    stats = [d.memory_stats() for d in jax.devices()]
    in_use = [m["bytes_in_use"] for m in stats]
    # The runtime books a program's temporaries as "reserved", apart from
    # the live buffers "in use"; the peak a chip saw is the two together.
    peak = max(m["peak_bytes_in_use"] + m["peak_bytes_reserved"]
               for m in stats)
    compiles_after_first = step._cache_size() - 1
    say(f"compilations after the first step {compiles_after_first}, "
        f"bytes in use per device {in_use}, peak {peak / 1e9:.2f} GB of "
        f"{stats[0]['bytes_limit'] / 1e9:.2f} GB")

    # At initialisation the logits have unit variance (lecun-normal lm_head
    # over RMS-normalised features), so E[loss] = ln V + 1/2.
    expect = math.log(cfg.vocab_size) + 0.5
    require(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    require(abs(losses[0] - expect) < 0.25,
            f"step-0 loss {losses[0]} is not near ln(vocab) + 1/2 = "
            f"{expect:.2f}")
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    require(compiles_after_first == 0,
            f"the step compiled {compiles_after_first + 1} times")
    # One flash forward and one backward kernel a layer.
    require(mosaic_calls == 2 * cfg.num_layers,
            f"{mosaic_calls} Mosaic calls in the step, expected "
            f"{2 * cfg.num_layers}: a kernel fell off the path")
    require(n_chips == 1 or all_reduces >= 1,
            f"no all-reduce in a step over {n_chips} chips")
    require(max(in_use) <= 1.1 * min(in_use),
            f"devices hold unequal bytes: {in_use}")
    return {
        "mesh": dict(mesh.shape),
        "losses": [round(x, 4) for x in losses],
        "cold_compile_s": round(compile_s, 2),
        "compile_cache_hit": cache_hit,
        "first_step_s": round(step_s[0], 2),
        "step_ms_median_informational": round(
            statistics.median(step_s[1:]) * 1e3, 2),
        "compiles_after_first_step": compiles_after_first,
        "mosaic_calls": mosaic_calls,
        "all_reduces": all_reduces,
        "bytes_in_use_per_device": in_use,
        "peak_hbm_bytes": peak,
        "step_hbm_bytes_compiled": {
            "arguments": memory.argument_size_in_bytes,
            "temporaries": memory.temp_size_in_bytes},
    }


def main() -> None:
    t_start = time.perf_counter()
    # First thing: what did JAX find?  Nothing below runs without a TPU.
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; JAX found platform "
                 f"{device['platform']!r} ({device['kind']!r}, "
                 f"{device['count']} device(s))")
    say(f"platform {device['platform']}, device_kind {device['kind']}, "
        f"{device['count']} device(s), jax {jax.__version__}")
    hvd.init()
    cache_dir = jax.config.jax_compilation_cache_dir
    say(f"compile cache at {cache_dir}")

    cfg = LlamaConfig(**CONFIG)
    kernels = {
        "flash": check_flash(BATCH_PER_CHIP, SEQ, cfg.num_heads,
                             cfg.head_dim),
        "paged_16_16_max_abs_err": check_paged(16, 16),
        "paged_32_8_max_abs_err": check_paged(32, 8),
    }
    result = train(cfg, BATCH_PER_CHIP, SEQ, STEPS)
    # What was measured, for a reader: one JSON object on the line before
    # the last.  The last line is the verdict alone, with exactly these
    # keys — the driver's check reads it and takes nothing else.
    say("report " + json.dumps({
        "jax": jax.__version__,
        "model": {**CONFIG, "batch_per_chip": BATCH_PER_CHIP, "seq": SEQ},
        "kernel_checks": kernels,
        **result,
        "compile_cache_dir": cache_dir,
        "wall_s": round(time.perf_counter() - t_start, 1),
    }))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
