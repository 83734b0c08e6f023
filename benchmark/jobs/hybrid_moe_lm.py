"""Causal-LM training of a hybrid decoder over routed experts
(Qwen3-Next-80B-A3B) through the program's main path: ``DecoderLM``'s job (by
way of ``MoELM``, whose loss and routing counters it keeps) with
``LlamaModel``'s layers as the configuration names them -- three
``GatedDeltaNet`` layers (16 key heads serving 32 value heads) to one softmax
layer whose W_q holds a query and an element-wise output gate a head, heads
of 256 of which a quarter turns, a zero-centred QK-norm; every norm of the
block zero-centred; every layer's feed-forward routed experts, of which this
chip holds ``num_experts`` of ``deployment.num_experts_published``, beside a
gated shared expert -- and the batch-wise balance loss added to the
cross-entropy.

    python3 -m benchmark.jobs.hybrid_moe_lm <workload> <seed>

prints the layers' own counters for one batch of the cell on the device it
finds: the routed layers' rows gathered per held expert, rows dropped and row
buffers run, and the bodies the mixers' calls traced to
(``flash_attention.layout_counts``, ``short_conv.body_counts``); it fails
where a row is dropped.  The harness hands a metric reader no live state.
"""

from __future__ import annotations

import jax
import optax

import horovod_tpu.jax as hvd
from benchmark import (arithmetic_gdn, arithmetic_hybrid_moe, arithmetic_moe,
                       arithmetic_window)
from benchmark.jobs.moe_lm import MoELM
from benchmark.jobs.window_moe_lm import WindowMoELM
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.mixed_precision import master_weights

if "shared_expert_gate" not in LlamaConfig.__dataclass_fields__:
    # The driver tries a new cell on the parent of the PR that adds it, with
    # this file laid over that checkout: end there, before the chip is taken.
    raise ImportError("this program's LlamaConfig has no shared_expert_gate: "
                      "it cannot run a gated shared expert, an element-wise "
                      "output gate or zero-centred norms")

from horovod_tpu.models.llama import RopeParameters  # noqa: E402

# What LlamaModel's layers compute, under the configuration's own keys.
REQUIRED = {"model_type": "qwen3_next", "hidden_act": "silu",
            "tie_word_embeddings": False, "rope_scaling": None,
            "use_sliding_window": False, "decoder_sparse_step": 1,
            "mlp_only_layers": [], "norm_topk_prob": True}
LINEAR_NAMES = ("wq", "wk", "wv", "wg", "wa", "wb", "wo")
LINEAR_PARAMS = ("conv_q", "conv_k", "conv_v", "a_log", "dt_bias", "o_norm")


def layer_types(layers: int, interval: int) -> tuple:
    """``full_attention_interval`` as the published modelling code reads it:
    layer i is a full one where ``(i + 1) % interval == 0``."""
    return tuple("full_attention" if (i + 1) % interval == 0
                 else "linear_attention" for i in range(layers))


def build(config: dict, traffic: dict, chips: int):
    return HybridMoELM(config, traffic, chips)


class HybridMoELM(MoELM):
    """``MoELM``'s loss (cross-entropy + alpha x the balance loss), routing
    counters and first loss; the layers, the arithmetic and the reference's
    layout are this configuration's own."""

    def __init__(self, config: dict, traffic: dict, chips: int):
        differ = {key: config[key] for key, wanted in REQUIRED.items()
                  if config[key] != wanted}
        if differ or (config["shared_expert_intermediate_size"]
                      % config["moe_intermediate_size"]):
            raise ValueError(f"this job trains Qwen3-Next's decoder layers "
                             f"({REQUIRED}); the configuration states "
                             f"{differ or config}")
        training = config["training"]
        if (training["optimizer"], training["compute_dtype"],
                training["master_dtype"]) != ("adamw", "bfloat16", "float32"):
            raise ValueError(f"this job trains bf16 weights under fp32 "
                             f"master AdamW; asked for {training}")
        deployment = config["deployment"]
        layers = config["num_hidden_layers"]
        self.config = config
        self.chips = chips
        self.seq = traffic["sequence"]
        self.batch = traffic["batch_per_chip"] * chips
        self.sample_rows = traffic["sample_per_chip"] * chips
        self.units_per_step = self.batch * self.seq
        self.alpha = config["assumed"]["aux_loss_alpha"]
        self.llama = LlamaConfig(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_layers=layers,
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            attention_head_dim=config["head_dim"],
            intermediate_size=config["intermediate_size"],
            max_seq_len=config["max_position_embeddings"],
            rms_eps=config["rms_norm_eps"],
            layer_types=layer_types(layers,
                                    config["full_attention_interval"]),
            rope_parameters=(("full_attention", RopeParameters(
                float(config["rope_theta"]), None,
                float(config["partial_rotary_factor"]))),),
            qk_norm=True, qk_norm_over="head", zero_centered_norm=True,
            gating="elementwise",
            linear_num_key_heads=config["linear_num_key_heads"],
            linear_num_value_heads=config["linear_num_value_heads"],
            linear_key_head_dim=config["linear_key_head_dim"],
            linear_value_head_dim=config["linear_value_head_dim"],
            linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
            num_experts=deployment["num_experts_published"],
            experts_per_token=config["num_experts_per_tok"],
            held_experts=config["num_experts"],
            first_held_expert=deployment["first_held_expert"],
            moe_intermediate_size=config["moe_intermediate_size"],
            shared_experts=(config["shared_expert_intermediate_size"]
                            // config["moe_intermediate_size"]),
            shared_expert_gate=True,
            norm_topk_prob=config["norm_topk_prob"],
            balance_over="batch", remat=training.get("remat", "none"))
        self.model = LlamaModel(self.llama, attention_fn=flash_attention_fn)
        rate = optax.linear_schedule(0.0, training["learning_rate"],
                                     training["warmup_steps"])
        self.optimizer = hvd.DistributedOptimizer(
            master_weights(optax.adamw(rate)))

    # -- what the harness jits ------------------------------------------

    # As ``DecoderLM``'s, with an embedding of unit variance (the
    # configuration's ``assumed.initialisation`` says why): the routed
    # job's before this one.
    init_state = WindowMoELM.init_state

    # -- facts for the metric readers (benchmark/arithmetic_hybrid_moe.py) --

    def _linear_layers(self) -> int:
        return sum(map(self.llama.is_linear, range(self.llama.num_layers)))

    def flops_per_unit(self) -> float:
        c = self.llama
        linear = self._linear_layers()
        return arithmetic_hybrid_moe.train_flops_per_token(
            hidden=c.hidden_size, linear_layers=linear,
            full_layers=c.num_layers - linear, heads=c.num_heads,
            kv_heads=c.num_kv_heads, head_dim=c.head_dim,
            key_heads=c.linear_num_key_heads,
            value_heads=c.linear_num_value_heads,
            key_dim=c.linear_key_head_dim, value_dim=c.linear_value_head_dim,
            expert_ffn=c.moe_intermediate_size,
            shared_ffn=c.shared_experts * c.moe_intermediate_size,
            experts=c.num_experts, held=c.experts_held,
            per_token=c.experts_per_token, vocab=c.vocab_size, seq=self.seq)

    def kernel_work_per_step(self) -> dict:
        """A chip's step at what the algorithms need: the flash kernel's two
        passes over the full layers at their own head counts (``flash``, by
        pass), the chunked rule over the linear ones with q and k read once
        a key head (``gdn_scan``: the algorithm's count, whatever runs it),
        and every layer's grouped products at the rows its held experts
        expect (``moe_experts``)."""
        c = self.llama
        linear = self._linear_layers()
        batch = self.batch // self.chips
        attention = arithmetic_window.attention_work(
            batch=batch, seq=self.seq, heads=c.num_heads,
            kv_heads=c.num_kv_heads, head_dim=c.head_dim, window=None)
        rule = dict(batch=batch, seq=self.seq,
                    value_heads=c.linear_num_value_heads,
                    key_dim=c.linear_key_head_dim,
                    value_dim=c.linear_value_head_dim)
        rows = arithmetic_moe.expert_rows(
            tokens=self.units_per_step // self.chips,
            per_token=c.experts_per_token, held=c.experts_held,
            experts=c.num_experts)
        return {
            "flash": jax.tree.map(lambda x: x * (c.num_layers - linear),
                                  attention),
            "gdn_scan": {
                "flops": linear * arithmetic_gdn.scan_flops(**rule),
                "bytes": linear * arithmetic_hybrid_moe.scan_bytes(
                    key_heads=c.linear_num_key_heads, **rule)},
            "moe_experts": {
                "flops": c.num_layers * arithmetic_moe.expert_products_flops(
                    rows=rows, hidden=c.hidden_size,
                    expert_ffn=c.moe_intermediate_size),
                "bytes": c.num_layers * arithmetic_moe.expert_products_bytes(
                    rows=rows, held=c.experts_held, hidden=c.hidden_size,
                    expert_ffn=c.moe_intermediate_size)}}

    # -- checks ---------------------------------------------------------

    def to_reference(self, tree):
        p = tree["params"]
        c = self.llama

        def swiglu(block, width):
            gate_up = block["w_gate_up"]["kernel"]
            return {"w_gate": gate_up[:, :width], "w_up": gate_up[:, width:],
                    "w_down": block["w_down"]["kernel"]}

        layers = []
        for i in range(c.num_layers):
            layer = p[f"layer_{i}"]
            if c.is_linear(i):
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
            moe, width = layer["moe"], c.moe_intermediate_size
            layers.append({
                **mixed,
                "norm_attn": layer["norm_attn"]["scale"],
                "norm_mlp": layer["norm_mlp"]["scale"],
                "router": moe["router"]["kernel"],
                "experts": {"w_gate": moe["w_gate_up"][..., :width],
                            "w_up": moe["w_gate_up"][..., width:],
                            "w_down": moe["w_down"]},
                "shared": swiglu(moe["shared"], c.shared_experts * width),
                "shared_gate": moe["shared_gate"]["kernel"]})
        return {"embed": p["tok_emb"]["embedding"], "layers": layers,
                "norm_f": p["norm_f"]["scale"],
                "lm_head": p["lm_head"]["kernel"]}


def main(argv=None) -> None:
    import sys

    import numpy as np

    from benchmark import manifest
    from horovod_tpu.ops import flash_attention, short_conv

    argv = argv or sys.argv[1:]
    if len(argv) != 2:
        sys.exit("usage: python3 -m benchmark.jobs.hybrid_moe_lm <workload> "
                 "<seed>")
    workload, seed = argv
    cell = manifest.cell(workload)
    job = build(cell["config"], cell["traffic"], cell["chips"])
    k_state, k_sample = jax.random.split(
        jax.random.key(np.uint32(int(seed) % 2 ** 32)))

    def counters(k_state, k_sample):
        params, _ = job.init_state(k_state)
        return job.routing_counters(params, job.make_batch(k_sample))

    rows, dropped, buffers = map(np.asarray,
                                 jax.jit(counters)(k_state, k_sample))
    device = jax.devices()[0]
    print(f"[hybrid_moe_lm] {workload} seed {seed} on {device.platform} "
          f"({device.device_kind}): {job.batch} x {job.seq} tokens; flash "
          f"calls traced {flash_attention.layout_counts()}, convolutions "
          f"{short_conv.body_counts()}; rows gathered per held expert a "
          f"layer: mean {rows.mean():.1f}, max {rows.max()}, min "
          f"{rows.min()}; by layer max {rows.max(axis=1).tolist()}; rows "
          f"dropped {dropped.tolist()}; row buffers run {buffers.tolist()}",
          flush=True)
    if dropped.any():
        sys.exit("[hybrid_moe_lm] a row was dropped")


if __name__ == "__main__":
    main()
