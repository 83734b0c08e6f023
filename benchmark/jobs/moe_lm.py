"""Causal-LM training of a decoder with latent attention and routed experts
(DeepSeek-V2-Lite) through the program's main path: ``DecoderLM``'s job
with ``LlamaModel``'s layers of the kinds the configuration's file names --
MLA with YaRN, a leading dense layer, then routed layers of which this chip
holds ``n_routed_experts`` of ``deployment.n_routed_experts_published``
beside the shared experts -- and the sequence-wise balance loss added to
the cross-entropy.

    python3 -m benchmark.jobs.moe_lm <workload> <seed>

prints the routed layers' own counters for one batch of the cell on the
device it finds (rows gathered per held expert, rows dropped, row buffers
run): the harness hands a metric reader no live state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

import horovod_tpu.jax as hvd
from benchmark import arithmetic_moe
from benchmark.jobs.decoder_lm import DecoderLM
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.models.llama import YarnScaling
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.losses import balance_loss, softmax_cross_entropy
from horovod_tpu.ops.mixed_precision import master_weights

# What LlamaModel's layers compute, under the configuration's own keys.
REQUIRED = {"model_type": "deepseek_v2", "hidden_act": "silu",
            "tie_word_embeddings": False, "attention_bias": False,
            "q_lora_rank": None, "scoring_func": "softmax",
            "topk_method": "greedy", "n_group": 1, "topk_group": 1,
            "moe_layer_freq": 1, "seq_aux": True,
            "routed_scaling_factor": 1}


def build(config: dict, traffic: dict, chips: int):
    return MoELM(config, traffic, chips)


class MoELM(DecoderLM):

    def __init__(self, config: dict, traffic: dict, chips: int):
        differ = {key: config[key] for key, wanted in REQUIRED.items()
                  if config[key] != wanted}
        if differ or config["rope_scaling"]["type"] != "yarn":
            raise ValueError(f"this job trains DeepSeek-V2's layers "
                             f"({REQUIRED}, YaRN); the configuration "
                             f"states {differ or config['rope_scaling']}")
        training = config["training"]
        if (training["optimizer"], training["compute_dtype"],
                training["master_dtype"]) != ("adamw", "bfloat16", "float32"):
            raise ValueError(f"this job trains bf16 weights under fp32 "
                             f"master AdamW; asked for {training}")
        deployment = config["deployment"]
        scaling = {key: value for key, value in
                   config["rope_scaling"].items() if key != "type"}
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
            num_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            intermediate_size=config["intermediate_size"],
            max_seq_len=config["max_position_embeddings"],
            rope_theta=float(config["rope_theta"]),
            rms_eps=config["rms_norm_eps"],
            num_experts=deployment["n_routed_experts_published"],
            experts_per_token=config["num_experts_per_tok"],
            held_experts=config["n_routed_experts"],
            first_held_expert=deployment["first_held_expert"],
            moe_intermediate_size=config["moe_intermediate_size"],
            shared_experts=config["n_shared_experts"],
            first_dense_layers=config["first_k_dense_replace"],
            norm_topk_prob=config["norm_topk_prob"],
            attention_kind="latent", kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            rope_scaling=YarnScaling(**scaling),
            remat=training.get("remat", "none"))
        self.model = LlamaModel(self.llama, attention_fn=flash_attention_fn)
        # Linear from 0 over the recipe's warm-up, then constant: the window
        # is the first minute of a run that warms up for 2000 steps.
        rate = optax.linear_schedule(0.0, training["learning_rate"],
                                     training["warmup_steps"])
        self.optimizer = hvd.DistributedOptimizer(
            master_weights(optax.adamw(rate)))

    # -- what the harness jits ------------------------------------------

    def loss_fn(self, params, batch):
        logits, sown = self.model.apply(params, batch[:, :-1],
                                        mutable=["losses"])
        return (softmax_cross_entropy(logits, batch[:, 1:])
                + self.alpha * balance_loss(sown))

    def routing_counters(self, params, batch):
        """What the routed layers count of themselves on ``batch``: rows
        gathered per held expert ``[routed layers, held]``, rows dropped
        and row buffers run ``[routed layers]``."""
        _, sown = self.model.apply(params, batch[:, :-1],
                                   mutable=["moe_stats"])
        layers = [sown["moe_stats"][f"layer_{i}"]["moe"]
                  for i in range(self.llama.num_layers)
                  if self.llama.is_routed(i)]
        return tuple(jnp.stack([layer[name][0] for layer in layers])
                     for name in ("rows_per_expert", "rows_dropped",
                                  "row_buffers_run"))

    # -- facts for the metric readers (benchmark/arithmetic_moe.py) -------

    def _sizes(self) -> dict:
        c = self.llama
        return dict(hidden=c.hidden_size, heads=c.num_heads,
                    qk_nope=c.qk_nope_head_dim, qk_rope=c.qk_rope_head_dim,
                    v_dim=c.v_head_dim, kv_rank=c.kv_lora_rank)

    def flops_per_unit(self) -> float:
        c = self.llama
        return arithmetic_moe.moe_decoder_train_flops_per_token(
            **self._sizes(), layers=c.num_layers,
            dense_layers=c.first_dense_layers,
            dense_ffn=c.intermediate_size,
            expert_ffn=c.moe_intermediate_size, shared=c.shared_experts,
            experts=c.num_experts, held=c.experts_held,
            per_token=c.experts_per_token, vocab=c.vocab_size, seq=self.seq)

    def kernel_work_per_step(self) -> dict:
        """A chip's Mosaic calls in one step: the flash kernel's passes at
        192 / 128 over every layer, and the routed layers' grouped products
        at the rows their held experts expect."""
        c = self.llama
        shape = dict(batch=self.batch // self.chips, seq=self.seq,
                     heads=c.num_heads,
                     qk_dim=c.qk_nope_head_dim + c.qk_rope_head_dim,
                     v_dim=c.v_head_dim)

        def flash(flops, nbytes):
            return {"flops": c.num_layers * flops(**shape),
                    "bytes": c.num_layers * nbytes(**shape)}

        forward = flash(arithmetic_moe.flash_forward_flops,
                        arithmetic_moe.flash_forward_bytes)
        backward = flash(arithmetic_moe.flash_backward_flops,
                         arithmetic_moe.flash_backward_bytes)
        routed_layers = c.num_layers - c.first_dense_layers
        rows = arithmetic_moe.expert_rows(
            tokens=self.units_per_step // self.chips,
            per_token=c.experts_per_token, held=c.experts_held,
            experts=c.num_experts)
        return {
            "flash": {"flops": forward["flops"] + backward["flops"],
                      "bytes": forward["bytes"] + backward["bytes"],
                      "forward": forward, "backward": backward},
            "moe_experts": {
                "flops": routed_layers * arithmetic_moe.expert_products_flops(
                    rows=rows, hidden=c.hidden_size,
                    expert_ffn=c.moe_intermediate_size),
                "bytes": routed_layers * arithmetic_moe.expert_products_bytes(
                    rows=rows, held=c.experts_held, hidden=c.hidden_size,
                    expert_ffn=c.moe_intermediate_size)}}

    # -- checks ---------------------------------------------------------

    def expected_first_loss(self) -> float:
        # ln V + 1/2 as DecoderLM, and alpha times a balance loss that is 1
        # under uniform routing and little more at initialisation.
        return super().expected_first_loss() + self.alpha

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
            attn = layer["attn"]
            out = {"norm_attn": layer["norm_attn"]["scale"],
                   "wq": attn["wq"]["kernel"],
                   "wkv_a": attn["wkv_a"]["kernel"],
                   "kv_norm": attn["kv_norm"]["scale"],
                   "wkv_b": attn["wkv_b"]["kernel"],
                   "wo": attn["wo"]["kernel"],
                   "norm_mlp": layer["norm_mlp"]["scale"]}
            if c.is_routed(i):
                moe, width = layer["moe"], c.moe_intermediate_size
                out.update({
                    "router": moe["router"]["kernel"],
                    "experts": {"w_gate": moe["w_gate_up"][..., :width],
                                "w_up": moe["w_gate_up"][..., width:],
                                "w_down": moe["w_down"]},
                    "shared": swiglu(moe["shared"],
                                     c.shared_experts * width)})
            else:
                out.update(swiglu(layer["mlp"], c.intermediate_size))
            layers.append(out)
        return {"embed": p["tok_emb"]["embedding"], "layers": layers,
                "norm_f": p["norm_f"]["scale"],
                "lm_head": p["lm_head"]["kernel"]}


def main(argv=None) -> None:
    import sys

    import numpy as np

    from benchmark import manifest

    argv = argv or sys.argv[1:]
    if len(argv) != 2:
        sys.exit("usage: python3 -m benchmark.jobs.moe_lm <workload> <seed>")
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
    print(f"[moe_lm] {workload} seed {seed} on {device.platform} "
          f"({device.device_kind}): {job.batch} x {job.seq} tokens, rows "
          f"gathered per held expert a layer: mean {rows.mean():.1f}, max "
          f"{rows.max()}, min {rows.min()}; by layer "
          f"{rows.tolist()}; rows dropped {dropped.tolist()}; row buffers "
          f"run {buffers.tolist()}", flush=True)


if __name__ == "__main__":
    main()
