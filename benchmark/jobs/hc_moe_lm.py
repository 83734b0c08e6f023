"""Causal-LM training of a decoder whose residual path is four
hyper-connected streams around DeepSeek-V3's block (Xing4.0-29B-A4B) through
the program's main path: ``MoELM``'s job (by way of ``LconvMoELM``, whose
threading of the routers' choice bias, loss and counters it keeps) with
``LlamaModel``'s layers as the configuration's keys name them -- ``hc_mult``
residual streams that every sublayer reads and rewrites under maps of its own
(``HyperConnection``), latent attention with a query latent under YaRN, a
leading dense layer, then ``RoutedExperts`` behind a sigmoid router whose
choice a bias corrects, of which this chip holds ``n_routed_experts`` of
``deployment.n_routed_experts_published`` beside the shared one -- and the
sequence-wise balance loss added to the cross-entropy.  The router's bias is
state and no parameter: it travels through ``hvd.make_train_step``'s
``has_aux`` path and the optimizer never sees it.

    python3 -m benchmark.jobs.hc_moe_lm <workload> <seed>

prints the layers' own counters for one batch of the cell on the device it
finds: the flash calls' layouts, the routed layers' rows gathered per held
expert, rows dropped, row buffers run and load over all the experts; it fails
where a row is dropped.  The harness hands a metric reader no live state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

import horovod_tpu.jax as hvd
from benchmark import arithmetic_hc, arithmetic_moe
from benchmark.jobs.lconv_moe_lm import LconvMoELM
from benchmark.jobs.moe_lm import MoELM
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.mixed_precision import cast_compute, master_weights

if "hc_mult" not in LlamaConfig.__dataclass_fields__:
    # The driver tries a new cell on the parent of the PR that adds it, with
    # this file laid over that checkout: end there, before the chip is taken.
    raise ImportError("this program's LlamaConfig has no hc_mult: its layers "
                      "add to one residual stream and cannot carry four "
                      "hyper-connected ones")

from horovod_tpu.models.llama import ROUTER_STATE, YarnScaling  # noqa: E402

# What LlamaModel's layers compute, under the configuration's own keys.
REQUIRED = {"model_type": "xing4_0", "hidden_act": "silu",
            "tie_word_embeddings": False, "attention_bias": False,
            "scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
            "num_nextn_predict_layers": 0}
HC_LEAVES = ("phi_pre", "phi_post", "phi_res", "b_pre", "b_post", "b_res",
             "g_pre", "g_post", "g_res")


def build(config: dict, traffic: dict, chips: int):
    return HcMoELM(config, traffic, chips)


class HcMoELM(LconvMoELM):
    """``LconvMoELM``'s state beside the parameters, loss and counters
    (``MoELM``'s first loss); the layers, the arithmetic and the reference's
    layout are this configuration's own."""

    def __init__(self, config: dict, traffic: dict, chips: int):
        differ = {key: config[key] for key, wanted in REQUIRED.items()
                  if config[key] != wanted}
        if differ or config["rope_scaling"]["type"] != "yarn":
            raise ValueError(f"this job trains Xing4.0's layers ({REQUIRED}, "
                             f"YaRN); the configuration states "
                             f"{differ or config['rope_scaling']}")
        training = config["training"]
        if (training["optimizer"], training["compute_dtype"],
                training["master_dtype"]) != ("adamw", "bfloat16", "float32"):
            raise ValueError(f"this job trains bf16 weights under fp32 "
                             f"master AdamW; asked for {training}")
        deployment, assumed = config["deployment"], config["assumed"]
        scaling = {key: value for key, value in
                   config["rope_scaling"].items() if key != "type"}
        self.config = config
        self.chips = chips
        self.seq = traffic["sequence"]
        self.batch = traffic["batch_per_chip"] * chips
        self.sample_rows = traffic["sample_per_chip"] * chips
        self.units_per_step = self.batch * self.seq
        self.alpha = assumed["aux_loss_alpha"]
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
            hc_mult=config["hc_mult"],
            hc_sinkhorn_iters=config["hc_sinkhorn_iters"],
            hc_eps=config["hc_eps"],
            hc_res_clamp=(config["mhc_h_res_clamp_min"],
                          config["mhc_h_res_clamp_max"]),
            num_experts=deployment["n_routed_experts_published"],
            experts_per_token=config["num_experts_per_tok"],
            held_experts=config["n_routed_experts"],
            first_held_expert=deployment["first_held_expert"],
            moe_intermediate_size=config["moe_intermediate_size"],
            shared_experts=config["n_shared_experts"],
            first_dense_layers=config["first_k_dense_replace"],
            scoring_func=config["scoring_func"],
            topk_method=config["topk_method"],
            router_bias_update_rate=assumed["router_bias_update_rate"],
            norm_topk_prob=config["norm_topk_prob"],
            routed_scaling_factor=config["routed_scaling_factor"],
            attention_kind="latent", q_lora_rank=config["q_lora_rank"],
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            rope_scaling=YarnScaling(**scaling),
            remat=training.get("remat", "none"))
        self.model = LlamaModel(self.llama, attention_fn=flash_attention_fn)
        rate = optax.linear_schedule(0.0, training["learning_rate"],
                                     training["warmup_steps"])
        self.optimizer = hvd.DistributedOptimizer(
            master_weights(optax.adamw(rate)))

    # -- what the harness jits ------------------------------------------

    def init_state(self, key):
        """(params, opt_state, the routers' choice bias): the modules' own
        initialisation with an embedding of unit variance (the
        configuration's ``assumed.initialisation`` says why)."""
        variables = LlamaModel(self.llama).init(
            key, jnp.zeros((1, 8), jnp.int32))
        table = variables["params"]["tok_emb"]
        table["embedding"] = table["embedding"] * self.llama.hidden_size ** 0.5
        params = cast_compute({"params": variables["params"]})
        return (params, self.optimizer.init(params),
                variables[ROUTER_STATE])

    # -- facts for the metric readers (benchmark/arithmetic_hc.py) --------

    def flops_per_unit(self) -> float:
        c = self.llama
        return arithmetic_hc.train_flops_per_token(
            hidden=c.hidden_size, streams=c.hc_mult, layers=c.num_layers,
            dense_layers=c.first_dense_layers, heads=c.num_heads,
            qk_nope=c.qk_nope_head_dim, qk_rope=c.qk_rope_head_dim,
            v_dim=c.v_head_dim, kv_rank=c.kv_lora_rank,
            q_rank=c.q_lora_rank, dense_ffn=c.intermediate_size,
            expert_ffn=c.moe_intermediate_size, shared=c.shared_experts,
            experts=c.num_experts, held=c.experts_held,
            per_token=c.experts_per_token, vocab=c.vocab_size, seq=self.seq)

    def kernel_work_per_step(self) -> dict:
        """``MoELM``'s two (the flash kernel's passes at 192 / 128 over every
        layer, the routed layers' grouped products at the rows their held
        experts expect) and ``hc_mix``: the streams' reads and writes around
        both sublayers of every layer, at what the algorithm has to move."""
        c = self.llama
        return {**MoELM.kernel_work_per_step(self),
                "hc_mix": arithmetic_hc.mix_work(
                    tokens=self.units_per_step // self.chips,
                    streams=c.hc_mult, hidden=c.hidden_size,
                    sublayers=2 * c.num_layers)}

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
            attn = layer["attn"]
            # The stack's first sublayer reads four copies of the embedding:
            # the leaves behind its h_pre and H_res have no gradient, and the
            # reference does not take them (its docstring says why).
            out = {"hc_attn": {name: layer["hc_attn"][name]
                               for name in HC_LEAVES
                               if i or name.endswith("_post")},
                   "hc_mlp": {name: layer["hc_mlp"][name]
                              for name in HC_LEAVES},
                   "norm_attn": layer["norm_attn"]["scale"],
                   "norm_mlp": layer["norm_mlp"]["scale"],
                   **{name: attn[name]["kernel"] for name in (
                       "wq_a", "wq_b", "wkv_a", "wkv_b", "wo")},
                   **{name: attn[name]["scale"]
                      for name in ("q_norm", "kv_norm")}}
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
    from horovod_tpu.ops import flash_attention

    argv = argv or sys.argv[1:]
    if len(argv) != 2:
        sys.exit("usage: python3 -m benchmark.jobs.hc_moe_lm <workload> "
                 "<seed>")
    workload, seed = argv
    cell = manifest.cell(workload)
    job = build(cell["config"], cell["traffic"], cell["chips"])
    k_state, k_sample = jax.random.split(
        jax.random.key(np.uint32(int(seed) % 2 ** 32)))

    def counters(k_state, k_sample):
        params, _, bias = job.init_state(k_state)
        return job.layer_counters(params, bias, job.make_batch(k_sample))

    moe = jax.tree.map(np.asarray, jax.jit(counters)(k_state, k_sample))
    rows = moe["rows_per_expert"]
    device = jax.devices()[0]
    print(f"[hc_moe_lm] {workload} seed {seed} on {device.platform} "
          f"({device.device_kind}): {job.batch} x {job.seq} tokens in "
          f"{job.llama.hc_mult} streams; flash calls traced "
          f"{flash_attention.layout_counts()}; rows gathered per held expert "
          f"a routed layer: mean {rows.mean():.1f}, max {rows.max()}, min "
          f"{rows.min()}; by layer max {rows.max(axis=1).tolist()}; rows "
          f"dropped {moe['rows_dropped'].tolist()}; row buffers run "
          f"{moe['row_buffers_run'].tolist()}; load over all "
          f"{job.llama.num_experts} experts, max over mean "
          f"{moe['load_max_over_mean'].tolist()}; choice bias, largest "
          f"{moe['bias_abs_max'].tolist()}", flush=True)
    if moe["rows_dropped"].any():
        sys.exit("[hc_moe_lm] a row was dropped")


if __name__ == "__main__":
    main()
