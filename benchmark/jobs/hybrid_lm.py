"""Causal-LM training of a hybrid decoder, gated delta-rule
linear-attention layers among softmax ones (Olmo-Hybrid-7B), through the
program's main path: ``DecoderLM``'s job with ``LlamaModel``'s layers as
the configuration's ``layer_types`` names them -- a ``"linear_attention"``
layer's mixer is ``GatedDeltaNet`` (short causal convolutions, L2-normed q
and k, decay and beta gates, the chunkwise rule of ``ops/gated_delta.py``, a
gated per-head norm), a ``"full_attention"`` layer's the flash kernel over
heads that do not rotate -- in OLMo 2's block (the norm on each sublayer's
output), under master-weight AdamW.

    python3 -m benchmark.jobs.hybrid_lm <workload> <seed>

prints each linear layer's own counters for one sequence of the cell on the
device it finds, the program's (bf16, chunked) beside the plain reference's
(float32, token by token): mean and least alpha, the share of beta over 1,
the largest |S| and |o|; it fails where one of them is not finite.  The
harness hands a metric reader no live state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import optax

import horovod_tpu.jax as hvd
from benchmark import arithmetic_gdn
from benchmark.jobs.decoder_lm import DecoderLM
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.mixed_precision import cast_compute, master_weights

if "layer_types" not in LlamaConfig.__dataclass_fields__:
    # The driver tries a new cell on the parent of the PR that adds it, with
    # this file laid over that checkout: end there, before the chip is taken.
    raise ImportError("this program's LlamaConfig names no mixer a layer "
                      "(no layer_types): it cannot run a hybrid stack")

# What LlamaModel's layers compute, under the configuration's own keys.
REQUIRED = {"model_type": "olmo_hybrid", "hidden_act": "silu",
            "tie_word_embeddings": False, "attention_bias": False,
            "rope_parameters": {"rope_theta": None}}
COUNTERS = ("alpha_mean", "alpha_min", "beta_over_one", "state_max",
            "out_max")
LINEAR_NAMES = ("wq", "wk", "wv", "wg", "wa", "wb", "wo")
LINEAR_PARAMS = ("conv_q", "conv_k", "conv_v", "a_log", "dt_bias", "o_norm")


def build(config: dict, traffic: dict, chips: int):
    return HybridLM(config, traffic, chips)


class HybridLM(DecoderLM):

    def __init__(self, config: dict, traffic: dict, chips: int):
        differ = {key: config[key] for key, wanted in REQUIRED.items()
                  if config[key] != wanted}
        if differ or config["num_key_value_heads"] != config[
                "num_attention_heads"]:
            raise ValueError(f"this job trains Olmo-Hybrid's decoder layers "
                             f"({REQUIRED}, no grouped keys); the "
                             f"configuration states {differ}")
        training = config["training"]
        if (training["optimizer"], training["compute_dtype"],
                training["master_dtype"]) != ("adamw", "bfloat16", "float32"):
            raise ValueError(f"this job trains bf16 weights under fp32 "
                             f"master AdamW; asked for {training}")
        assumed = config["assumed"]
        if (assumed["norm_placement"], assumed["qk_norm"]) != ("post", "all"):
            raise ValueError("this job builds OLMo 2's block: the norm on "
                             "each sublayer's output, q and k normed whole")
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
            intermediate_size=config["intermediate_size"],
            max_seq_len=config["max_position_embeddings"],
            rope_theta=None, rms_eps=config["rms_norm_eps"],
            attention_head_dim=config["head_dim"],
            qk_norm=True, qk_norm_over="all", norm_placement="post",
            layer_types=tuple(config["layer_types"]),
            linear_num_key_heads=config["linear_num_key_heads"],
            linear_num_value_heads=config["linear_num_value_heads"],
            linear_key_head_dim=config["linear_key_head_dim"],
            linear_value_head_dim=config["linear_value_head_dim"],
            linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
            linear_allow_neg_eigval=config["linear_allow_neg_eigval"],
            remat=training.get("remat", "none"))
        self.model = LlamaModel(self.llama, attention_fn=flash_attention_fn)
        rate = optax.linear_schedule(0.0, training["learning_rate"],
                                     training["warmup_steps"])
        self.optimizer = hvd.DistributedOptimizer(
            master_weights(optax.adamw(rate)))

    # -- what the harness jits ------------------------------------------

    def init_state(self, key):
        """As ``DecoderLM``, with an embedding of unit variance (the
        configuration's ``assumed.initialisation`` says why: the mixers of
        this block read the residual stream itself, not a normed copy)."""
        params = LlamaModel(self.llama).init(key, jnp.zeros((1, 8),
                                                            jnp.int32))
        table = params["params"]["tok_emb"]
        table["embedding"] = table["embedding"] * self.llama.hidden_size ** 0.5
        params = cast_compute(params)
        return params, self.optimizer.init(params)

    def counters(self, params, batch):
        """What each linear layer counts of itself on ``batch``: a dict of
        ``COUNTERS``, each ``[linear layers]``."""
        _, sown = self.model.apply(params, batch[:, :-1],
                                   mutable=["gdn_stats"])
        linear = [i for i in range(self.llama.num_layers)
                  if self.llama.is_linear(i)]
        return {name: jnp.stack([
            sown["gdn_stats"][f"layer_{i}"]["linear"][name][0]
            for i in linear]) for name in COUNTERS}

    # -- facts for the metric readers (benchmark/arithmetic_gdn.py) ------

    def _linear_layers(self) -> int:
        return sum(map(self.llama.is_linear, range(self.llama.num_layers)))

    def flops_per_unit(self) -> float:
        c = self.llama
        return arithmetic_gdn.hybrid_train_flops_per_token(
            hidden=c.hidden_size, layer_types=c.layer_types,
            heads=c.num_heads, head_dim=c.head_dim,
            key_heads=c.linear_num_key_heads,
            value_heads=c.linear_num_value_heads,
            key_dim=c.linear_key_head_dim, value_dim=c.linear_value_head_dim,
            ffn=c.intermediate_size, vocab=c.vocab_size, seq=self.seq)

    def kernel_work_per_step(self) -> dict:
        """A chip's step at what the algorithms need: the flash kernel's
        two passes over the softmax layers (their share of ``DecoderLM``'s
        count, which is of every layer), and the chunked rule over the
        linear ones (whatever runs it: ``gdn_scan`` is no Mosaic call's
        count but the algorithm's)."""
        c = self.llama
        linear = self._linear_layers()
        softmax_share = (c.num_layers - linear) / c.num_layers
        rule = dict(batch=self.batch // self.chips, seq=self.seq,
                    value_heads=c.linear_num_value_heads,
                    key_dim=c.linear_key_head_dim,
                    value_dim=c.linear_value_head_dim)
        return {
            "flash": jax.tree.map(lambda x: x * softmax_share,
                                  super().kernel_work_per_step()["flash"]),
            "gdn_scan": {"flops": linear * arithmetic_gdn.scan_flops(**rule),
                         "bytes": linear * arithmetic_gdn.scan_bytes(**rule)}}

    # -- checks ---------------------------------------------------------

    def to_reference(self, tree):
        p = tree["params"]
        ffn = self.llama.intermediate_size
        layers = []
        for i in range(self.llama.num_layers):
            layer = p[f"layer_{i}"]
            gate_up = layer["mlp"]["w_gate_up"]["kernel"]
            if self.llama.is_linear(i):
                mixer = layer["linear"]
                mixed = {**{name: mixer[name]["kernel"]
                            for name in LINEAR_NAMES},
                         **{name: mixer[name] for name in LINEAR_PARAMS}}
            else:
                mixer = layer["attn"]
                mixed = {**{name: mixer[name]["kernel"]
                            for name in ("wq", "wk", "wv", "wo")},
                         "q_norm": mixer["q_norm"]["scale"],
                         "k_norm": mixer["k_norm"]["scale"]}
            layers.append({
                **mixed,
                "norm_attn": layer["norm_attn"]["scale"],
                "norm_mlp": layer["norm_mlp"]["scale"],
                "w_gate": gate_up[:, :ffn], "w_up": gate_up[:, ffn:],
                "w_down": layer["mlp"]["w_down"]["kernel"]})
        return {"embed": p["tok_emb"]["embedding"], "layers": layers,
                "norm_f": p["norm_f"]["scale"],
                "lm_head": p["lm_head"]["kernel"]}


def main(argv=None) -> None:
    import sys

    import numpy as np

    from benchmark import manifest

    argv = argv or sys.argv[1:]
    if len(argv) != 2:
        sys.exit("usage: python3 -m benchmark.jobs.hybrid_lm <workload> "
                 "<seed>")
    workload, seed = argv
    cell = manifest.cell(workload)
    job = build(cell["config"], cell["traffic"], cell["chips"])
    reference = manifest.load_reference(cell["config"]["reference"])
    k_state, k_sample = jax.random.split(
        jax.random.key(np.uint32(int(seed) % 2 ** 32)))

    def counters(k_state, k_sample):
        params, _ = job.init_state(k_state)
        sample = job.make_batch(k_sample, 1)
        plain = reference.layer_counters(job.to_reference(params), sample,
                                         cell["config"])
        return job.counters(params, sample), {
            name: jnp.stack([layer[name] for layer in plain])
            for name in COUNTERS}

    program, plain = jax.tree.map(np.asarray,
                                  jax.jit(counters)(k_state, k_sample))
    device = jax.devices()[0]
    print(f"[hybrid_lm] {workload} seed {seed} on {device.platform} "
          f"({device.device_kind}): 1 x {job.seq} tokens; by linear layer, "
          f"the program's (the plain reference's): " + "; ".join(
              f"{name} {[round(float(x), 5) for x in program[name]]} "
              f"({[round(float(x), 5) for x in plain[name]]})"
              for name in COUNTERS), flush=True)
    if not all(math.isfinite(float(x)) for found in (program, plain)
               for values in found.values() for x in values):
        sys.exit("[hybrid_lm] a counter is not finite")


if __name__ == "__main__":
    main()
