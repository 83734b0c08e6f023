"""Causal-LM training of a dense hybrid decoder, Mamba-2 state-space layers
nine to one with grouped-query attention (Granite-4.0-H-Micro), through the
program's main path: ``DecoderLM``'s job with ``LlamaModel``'s layers as the
configuration's ``layer_types`` names them -- a ``"mamba"`` layer's mixer is
``Mamba2`` (a biased short convolution, the chunked scan of ``ops/ssd.py`` at
``mamba_chunk_size`` rows, the skip, the gate and ONE norm group over the
whole inner width, ``ops/gated_norm.py``), an ``"attention"`` layer's the
flash kernel over heads that neither rotate nor carry a position, scaled by
``attention_multiplier`` -- every layer with a dense SwiGLU of
``shared_intermediate_size`` behind a norm of its own, each sublayer added
times ``residual_multiplier``, the embedding times ``embedding_multiplier``,
and a tied head whose logits are divided by ``logits_scaling``; under
master-weight AdamW.

    python3 -m benchmark.jobs.ssm_lm <workload> <seed>

prints the Mamba layers' own counters for one batch of the cell on the device
it finds (decays, steps, the largest state a chunk started from and the
largest output) and the bodies the mixers' calls traced to
(``gated_norm.body_counts``, ``short_conv.body_counts``,
``flash_attention.layout_counts``); it fails where a counter is not finite,
or where on a TPU a Mamba-2 layer's gates took the ``jnp`` body in
``flash_attention_fn``'s model.  The harness hands a metric reader no live
state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import optax

import horovod_tpu.jax as hvd
from benchmark import arithmetic_ssd, arithmetic_ssm_dense, arithmetic_window
from benchmark.jobs.decoder_lm import DecoderLM
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.mixed_precision import cast_compute, master_weights

if "residual_multiplier" not in LlamaConfig.__dataclass_fields__:
    # The driver tries a new cell on the parent of the PR that adds it, with
    # this file laid over that checkout: end there, before the chip is taken.
    raise ImportError("this program's LlamaConfig has no residual_multiplier: "
                      "it cannot run a stack of \"mamba\" and \"attention\" "
                      "layers under Granite's four multipliers")

# What LlamaModel's layers compute, under the configuration's own keys.
REQUIRED = {"model_type": "granitemoehybrid", "hidden_act": "silu",
            "normalization_function": "rmsnorm",
            "position_embedding_type": "nope", "rope_scaling": None,
            "attention_bias": False, "mamba_conv_bias": True,
            "mamba_proj_bias": False, "num_local_experts": 0,
            "num_experts_per_tok": 0, "tie_word_embeddings": True}
MAMBA, ATTENTION = "mamba", "attention"
MAMBA_NAMES = ("conv_w", "conv_b", "a_log", "dt_bias", "d")


def build(config: dict, traffic: dict, chips: int):
    return SsmLM(config, traffic, chips)


class SsmLM(DecoderLM):

    def __init__(self, config: dict, traffic: dict, chips: int):
        differ = {key: config[key] for key, wanted in REQUIRED.items()
                  if config[key] != wanted}
        kinds = tuple(config["layer_types"])
        hidden, heads = config["hidden_size"], config["num_attention_heads"]
        if (differ or len(kinds) != config["num_hidden_layers"]
                or set(kinds) - {MAMBA, ATTENTION}
                or config["head_dim"] * heads != hidden
                or config["mamba_n_heads"] * config["mamba_d_head"]
                != config["mamba_expand"] * hidden):
            raise ValueError(f"this job trains Granite-4.0-H's dense layers "
                             f"({REQUIRED}, one layer type a layer, an inner "
                             f"width of mamba_expand x hidden_size); the "
                             f"configuration states {differ or config}")
        training = config["training"]
        if (training["optimizer"], training["compute_dtype"],
                training["master_dtype"]) != ("adamw", "bfloat16", "float32"):
            raise ValueError(f"this job trains bf16 weights under fp32 "
                             f"master AdamW; asked for {training}")
        self.config = config
        self.chips = chips
        self.seq = traffic["sequence"]
        self.batch = traffic["batch_per_chip"] * chips
        self.sample_rows = traffic["sample_per_chip"] * chips
        self.units_per_step = self.batch * self.seq
        self.llama = LlamaConfig(
            vocab_size=config["vocab_size"], hidden_size=hidden,
            num_layers=config["num_hidden_layers"], layer_types=kinds,
            num_heads=heads, num_kv_heads=config["num_key_value_heads"],
            attention_head_dim=config["head_dim"],
            # (``intermediate_size`` is read by nothing: no routed block.)
            intermediate_size=config["shared_intermediate_size"],
            max_seq_len=config["max_position_embeddings"],
            rope_theta=None,        # position_embedding_type "nope"
            rms_eps=config["rms_norm_eps"],
            mamba_num_heads=config["mamba_n_heads"],
            mamba_head_dim=config["mamba_d_head"],
            ssm_state_size=config["mamba_d_state"],
            n_groups=config["mamba_n_groups"],
            conv_kernel=config["mamba_d_conv"],
            chunk_size=config["mamba_chunk_size"],
            embedding_multiplier=config["embedding_multiplier"],
            attention_multiplier=config["attention_multiplier"],
            residual_multiplier=config["residual_multiplier"],
            logits_scaling=config["logits_scaling"],
            tie_word_embeddings=config["tie_word_embeddings"],
            remat=training.get("remat", "none"))
        self.model = LlamaModel(self.llama, attention_fn=flash_attention_fn)
        rate = optax.linear_schedule(0.0, training["learning_rate"],
                                     training["warmup_steps"])
        self.optimizer = hvd.DistributedOptimizer(
            master_weights(optax.adamw(rate)))

    # -- what the harness jits ------------------------------------------

    def init_state(self, key):
        """As ``DecoderLM``, the embedding as flax makes it (variance 1 /
        hidden: the configuration's ``assumed.initialisation`` says what the
        multipliers make of it)."""
        params = cast_compute(LlamaModel(self.llama).init(
            key, jnp.zeros((1, 8), jnp.int32)))
        return params, self.optimizer.init(params)

    def counters(self, params, batch):
        """What each Mamba layer counts of itself on ``batch``
        (``Mamba2``'s ``ssd_stats``): ``{name: [Mamba layers]}``."""
        _, sown = self.model.apply(params, batch[:, :-1],
                                   mutable=["ssd_stats"])
        layers = [sown["ssd_stats"][f"layer_{i}"]["mamba"]
                  for i in self._layers(MAMBA)]
        return {name: jnp.stack([layer[name][0] for layer in layers])
                for name in layers[0]}

    # -- facts for the metric readers (benchmark/arithmetic_ssm_dense.py) --

    def _layers(self, kind: str) -> list:
        return [i for i, found in enumerate(self.llama.layer_types)
                if found == kind]

    def flops_per_unit(self) -> float:
        c = self.llama
        return arithmetic_ssm_dense.train_flops_per_token(
            hidden=c.hidden_size, mamba_layers=len(self._layers(MAMBA)),
            attention_layers=len(self._layers(ATTENTION)),
            heads=c.num_heads, kv_heads=c.num_kv_heads, head_dim=c.head_dim,
            mamba_heads=c.mamba_num_heads, mamba_head_dim=c.mamba_head_dim,
            groups=c.n_groups, state=c.ssm_state_size,
            ffn=c.intermediate_size, vocab=c.vocab_size, seq=self.seq,
            chunk=c.chunk_size)

    def kernel_work_per_step(self) -> dict:
        """A chip's step at what the algorithms need: the flash kernel's two
        passes over the attention layers (``flash``, by pass), the chunked
        scan over the Mamba layers at the published chunk with B and C read
        once for the one group (``ssd_scan``: the algorithm's count, whatever
        runs it), and those layers' skip, gate and norm at the bytes of their
        tensors (``ssd_gates``)."""
        c = self.llama
        rows = dict(batch=self.batch // self.chips, seq=self.seq)
        mamba = len(self._layers(MAMBA))
        attention = arithmetic_window.attention_work(
            **rows, heads=c.num_heads, kv_heads=c.num_kv_heads,
            head_dim=c.head_dim, window=None)
        scan = dict(**rows, heads=c.mamba_num_heads, groups=c.n_groups,
                    head_dim=c.mamba_head_dim, state=c.ssm_state_size,
                    chunk=c.chunk_size)
        gates = dict(**rows, channels=c.mamba_inner)
        return {
            "flash": jax.tree.map(
                lambda x: x * len(self._layers(ATTENTION)), attention),
            "ssd_scan": {
                "flops": mamba * arithmetic_ssd.scan_flops(**scan),
                "bytes": mamba * arithmetic_ssd.scan_bytes(**scan)},
            "ssd_gates": {
                "flops": mamba * arithmetic_ssm_dense.gates_flops(**gates),
                "bytes": mamba * sum(arithmetic_ssm_dense.gates_bytes(
                    **gates).values())}}

    # -- checks ---------------------------------------------------------

    def to_reference(self, tree):
        p = tree["params"]
        ffn = self.llama.intermediate_size
        layers = []
        for i, kind in enumerate(self.llama.layer_types):
            layer = p[f"layer_{i}"]
            gate_up = layer["mlp"]["w_gate_up"]["kernel"]
            if kind == MAMBA:
                mixer = layer["mamba"]
                mixed = {**{name: mixer[name] for name in MAMBA_NAMES},
                         "in_proj": mixer["in_proj"]["kernel"],
                         "norm_w": mixer["norm"],
                         "out_proj": mixer["out_proj"]["kernel"]}
            else:
                mixed = {name: layer["attn"][name]["kernel"]
                         for name in ("wq", "wk", "wv", "wo")}
            layers.append({
                **mixed,
                "norm_attn": layer["norm_attn"]["scale"],
                "norm_mlp": layer["norm_mlp"]["scale"],
                "w_gate": gate_up[:, :ffn], "w_up": gate_up[:, ffn:],
                "w_down": layer["mlp"]["w_down"]["kernel"]})
        return {"embed": p["tok_emb"]["embedding"], "layers": layers,
                "norm_f": p["norm_f"]["scale"]}


def main(argv=None) -> None:
    import sys

    import numpy as np

    from benchmark import manifest
    from horovod_tpu.ops import flash_attention, gated_norm, short_conv

    argv = argv or sys.argv[1:]
    if len(argv) != 2:
        sys.exit("usage: python3 -m benchmark.jobs.ssm_lm <workload> <seed>")
    workload, seed = argv
    cell = manifest.cell(workload)
    job = build(cell["config"], cell["traffic"], cell["chips"])
    k_state, k_sample = jax.random.split(
        jax.random.key(np.uint32(int(seed) % 2 ** 32)))

    def counters(k_state, k_sample):
        params, _ = job.init_state(k_state)
        return job.counters(params, job.make_batch(k_sample))

    ssd = jax.tree.map(np.asarray, jax.jit(counters)(k_state, k_sample))
    gates = gated_norm.body_counts()
    device = jax.devices()[0]
    print(f"[ssm_lm] {workload} seed {seed} on {device.platform} "
          f"({device.device_kind}): {job.batch} x {job.seq} tokens; gates "
          f"traced {gates}, convolutions {short_conv.body_counts()}, flash "
          f"calls {flash_attention.layout_counts()}; Mamba layers: "
          + "; ".join(f"{name} {values.tolist()}"
                      for name, values in sorted(ssd.items())), flush=True)
    if not all(math.isfinite(float(x)) for values in ssd.values()
               for x in values):
        sys.exit("[ssm_lm] a counter is not finite")
    # (The state's initialisation traces the layers with the model's own
    # attention: those calls are ``NOT_IN_PLACE``'s, and no step's.)
    plain = {why: n for why, n in gates["plain"].items()
             if why != gated_norm.NOT_IN_PLACE}
    if device.platform == "tpu" and (
            plain or gates["mosaic"] < len(job._layers(MAMBA))):
        sys.exit(f"[ssm_lm] a Mamba-2 layer's gates took the jnp body: "
                 f"{gates}")


if __name__ == "__main__":
    main()
