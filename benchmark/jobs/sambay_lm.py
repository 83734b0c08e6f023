"""Causal-LM training of a decoder-hybrid-decoder stack
(Phi-4-mini-flash-reasoning: SambaY, arXiv:2507.06607, with differential
attention, arXiv:2410.05258, over Mamba, arXiv:2312.00752) through the
program's main path: ``DecoderLM``'s job with ``LlamaModel``'s layers as the
configuration's ``mb_per_layer`` places them -- ``Mamba1`` selective scans and
differential attention under a window in the first half; the last scan, whose
output is the memory, and one full-attention layer, whose keys and values are
shared; behind them ``GatedMemory`` units on that memory and cross-attention
to those keys and values -- each with its SwiGLU, under LayerNorm, with a head
tied to the embedding and no positional encoding, under master-weight AdamW.

    python3 -m benchmark.jobs.sambay_lm <workload> <seed>

prints the Mamba layers' own counters for one batch of the cell on the device
it finds (the decays, the steps, the largest state and the largest memory
entry), and the bodies the mixers' calls traced to
(``selective_scan.body_counts``, ``short_conv.body_counts``,
``flash_attention.layout_counts``); it fails where one of them is not finite.
The harness hands a metric reader no live state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

import horovod_tpu.jax as hvd
from benchmark import arithmetic_sambay
from benchmark.jobs.decoder_lm import DecoderLM
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.mixed_precision import cast_compute, master_weights

if "mb_per_layer" not in LlamaConfig.__dataclass_fields__:
    # The driver tries a new cell on the parent of the PR that adds it, with
    # this file laid over that checkout: end there, before the chip is taken.
    raise ImportError("this program's LlamaConfig has no mb_per_layer: it "
                      "cannot run a decoder-hybrid-decoder stack (Mamba-1 "
                      "scans, differential attention, a shared memory and "
                      "shared keys and values)")

from horovod_tpu.models.llama import (  # noqa: E402
    CROSS_ATTENTION, MEMORY_GATE, SCAN, SELF_ATTENTION)

# What LlamaModel's layers compute, under the configuration's own keys.
REQUIRED = {"model_type": "phi4flash", "hidden_act": "silu",
            "tie_word_embeddings": True, "mlp_bias": False,
            "lm_head_bias": False, "mb_per_layer": 2, "embd_pdrop": 0,
            "resid_pdrop": 0}
COUNTERS = ("decay_min", "decay_mean", "dt_mean", "state_max", "out_max")
SCAN_PARAMS = ("conv_w", "conv_b", "a_log", "d")
LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "subln")


def build(config: dict, traffic: dict, chips: int):
    return SambaYLM(config, traffic, chips)


class SambaYLM(DecoderLM):

    def __init__(self, config: dict, traffic: dict, chips: int):
        differ = {key: config[key] for key, wanted in REQUIRED.items()
                  if config[key] != wanted}
        if differ or config["num_hidden_layers"] % 4:
            raise ValueError(f"this job trains a decoder-hybrid-decoder "
                             f"stack ({REQUIRED}, a multiple of four "
                             f"layers); the configuration states "
                             f"{differ or config['num_hidden_layers']}")
        training = config["training"]
        if (training["optimizer"], training["compute_dtype"],
                training["master_dtype"]) != ("adamw", "bfloat16", "float32"):
            raise ValueError(f"this job trains bf16 weights under fp32 "
                             f"master AdamW; asked for {training}")
        sizes = config["assumed"]
        if sizes["dt_rank"] != -(-config["hidden_size"] // 16):
            raise ValueError("dt_rank is hidden_size / 16 rounded up")
        self.config = config
        self.chips = chips
        self.seq = traffic["sequence"]
        self.batch = traffic["batch_per_chip"] * chips
        self.sample_rows = traffic["sample_per_chip"] * chips
        self.units_per_step = self.batch * self.seq
        self.llama = LlamaConfig(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            attention_head_dim=config["head_dim"],
            intermediate_size=config["intermediate_size"],
            max_seq_len=config["max_position_embeddings"],
            rope_theta=None,        # assumed.no_positional_encoding
            rms_eps=config["layer_norm_eps"],
            layer_norm_eps=config["layer_norm_eps"],
            mb_per_layer=config["mb_per_layer"],
            sliding_window=config["sliding_window"],
            tie_word_embeddings=config["tie_word_embeddings"],
            attention_kind="differential", ssm_state_size=sizes["d_state"],
            conv_kernel=sizes["d_conv"], mamba_expand=sizes["expand"],
            remat=training.get("remat", "none"))
        self.model = LlamaModel(self.llama, attention_fn=flash_attention_fn)
        rate = optax.linear_schedule(0.0, training["learning_rate"],
                                     training["warmup_steps"])
        self.optimizer = hvd.DistributedOptimizer(
            master_weights(optax.adamw(rate)))

    # -- what the harness jits ------------------------------------------

    def init_state(self, key):
        """As ``DecoderLM``: the modules' own initialisation (the
        configuration's ``assumed.initialisation``; flax's embedding has
        variance 1 / hidden, which is what leaves the tied logits of unit
        variance)."""
        params = cast_compute(LlamaModel(self.llama).init(
            key, jnp.zeros((1, 8), jnp.int32)))
        return params, self.optimizer.init(params)

    def counters(self, params, batch):
        """What each Mamba layer counts of itself on ``batch``: a dict of
        ``COUNTERS``, each ``[Mamba layers]``."""
        _, sown = self.model.apply(params, batch[:, :-1],
                                   mutable=["sscan_stats"])
        layers = [layer["mamba"] for _, layer in sorted(
            sown["sscan_stats"].items(),
            key=lambda item: int(item[0].split("_")[1]))]
        return {name: jnp.stack([layer[name][0] for layer in layers])
                for name in COUNTERS}

    # -- facts for the metric readers (benchmark/arithmetic_sambay.py) ----

    def _layers(self, *kinds: str) -> int:
        c = self.llama
        return sum(c.mixer_of(i) in kinds for i in range(c.num_layers))

    def _windowed(self) -> int:
        c = self.llama
        return sum(c.mixer_of(i) == SELF_ATTENTION
                   and c.window_of(i) is not None
                   for i in range(c.num_layers))

    def _shape(self) -> dict:
        c = self.llama
        return dict(heads=c.num_heads, kv_heads=c.num_kv_heads,
                    head_dim=c.head_dim, seq=self.seq,
                    window=c.sliding_window)

    def flops_per_unit(self) -> float:
        c = self.llama
        return arithmetic_sambay.train_flops_per_token(
            hidden=c.hidden_size, ffn=c.intermediate_size,
            layers=c.num_layers, scan_layers=self._layers(SCAN),
            memory_layers=self._layers(MEMORY_GATE),
            self_layers=self._layers(SELF_ATTENTION),
            cross_layers=self._layers(CROSS_ATTENTION),
            windowed_layers=self._windowed(), inner=c.scan_inner,
            state=c.ssm_state_size, rank=c.dt_rank, vocab=c.vocab_size,
            **self._shape())

    def kernel_work_per_step(self) -> dict:
        """A chip's step at what the algorithms need: the flash kernel's two
        passes over the attention layers' two maps a head pair (``flash``, by
        pass; ``window_attn``: the windowed layers' part of it) and the selective
        scan over the Mamba layers (``sscan``: three multiply-adds a token,
        channel and state entry, whatever runs them)."""
        c = self.llama
        batch = self.batch // self.chips
        shape = self._shape()
        windowed = self._windowed()
        full = self._layers(SELF_ATTENTION, CROSS_ATTENTION) - windowed
        window = arithmetic_sambay.attention_work(batch=batch, **shape)
        whole = arithmetic_sambay.attention_work(
            batch=batch, **{**shape, "window": None})
        scan = dict(batch=batch, seq=self.seq, channels=c.scan_inner,
                    state=c.ssm_state_size)
        scans = self._layers(SCAN)
        return {
            "flash": jax.tree.map(lambda w, f: windowed * w + full * f,
                                  window, whole),
            "window_attn": jax.tree.map(lambda w: windowed * w, window),
            "sscan": {
                "flops": scans * arithmetic_sambay.scan_flops(**scan),
                "bytes": scans * arithmetic_sambay.scan_bytes(**scan)}}

    # -- checks ---------------------------------------------------------

    def to_reference(self, tree):
        p = tree["params"]
        c = self.llama
        ffn = c.intermediate_size
        layers = []
        for i in range(c.num_layers):
            layer, role = p[f"layer_{i}"], c.mixer_of(i)
            gate_up = layer["mlp"]["w_gate_up"]["kernel"]
            out = {"norm1": layer["norm_attn"], "norm2": layer["norm_mlp"],
                   "w_gate": gate_up[:, :ffn], "w_up": gate_up[:, ffn:],
                   "w_down": layer["mlp"]["w_down"]["kernel"]}
            if role == SCAN:
                mixer = layer["mamba"]
                out.update({name: mixer[name] for name in SCAN_PARAMS})
                out.update({name: mixer[name]["kernel"] for name in (
                    "in_proj", "x_proj", "dt_proj", "out_proj")})
                out["dt_bias"] = mixer["dt_proj"]["bias"]
            elif role == MEMORY_GATE:
                out.update({"gmu_in": layer["gmu"]["in_proj"]["kernel"],
                            "gmu_out": layer["gmu"]["out_proj"]["kernel"]})
            else:
                mixer = layer["attn"]
                q = "wqkv" if role == SELF_ATTENTION else "wq"
                out.update({name: mixer[name] for name in LAMBDAS})
                out.update({q: mixer[q]["kernel"],
                            "b" + q[1:]: mixer[q]["bias"],
                            "wo": mixer["wo"]["kernel"],
                            "bo": mixer["wo"]["bias"]})
            layers.append(out)
        return {"embed": p["tok_emb"]["embedding"], "layers": layers,
                "norm_f": p["norm_f"]}


def main(argv=None) -> None:
    import math
    import sys

    import numpy as np

    from benchmark import manifest
    from horovod_tpu.ops import flash_attention, selective_scan, short_conv

    argv = argv or sys.argv[1:]
    if len(argv) != 2:
        sys.exit("usage: python3 -m benchmark.jobs.sambay_lm <workload> "
                 "<seed>")
    workload, seed = argv
    cell = manifest.cell(workload)
    job = build(cell["config"], cell["traffic"], cell["chips"])
    k_state, k_sample = jax.random.split(
        jax.random.key(np.uint32(int(seed) % 2 ** 32)))

    def counters(k_state, k_sample):
        params, _ = job.init_state(k_state)
        return job.counters(params, job.make_batch(k_sample))

    counted = jax.tree.map(np.asarray, jax.jit(counters)(k_state, k_sample))
    device = jax.devices()[0]
    print(f"[sambay_lm] {workload} seed {seed} on {device.platform} "
          f"({device.device_kind}): {job.batch} x {job.seq} tokens; scans "
          f"traced {selective_scan.body_counts()}, convolutions "
          f"{short_conv.body_counts()}, flash calls "
          f"{flash_attention.layout_counts()}; Mamba layers (the last one's "
          f"out_max is the memory's): "
          + "; ".join(f"{name} {values.tolist()}"
                      for name, values in sorted(counted.items())),
          flush=True)
    if not all(math.isfinite(x) for values in counted.values()
               for x in values.tolist()):
        sys.exit("[sambay_lm] a counter is not finite")


if __name__ == "__main__":
    main()
