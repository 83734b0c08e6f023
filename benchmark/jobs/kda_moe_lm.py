"""Causal-LM training of a hybrid decoder whose linear layers are Kimi Delta
Attention (Kimi-Linear-48B-A3B) through the program's main path: ``MoELM``'s
job (by way of ``LconvMoELM``, whose threading of the routers' choice bias,
loss and counters it keeps) with ``LlamaModel``'s layers as the
configuration's keys name them -- ``linear_attn_config.kda_layers`` the delta
rule with a decay a key channel behind three short filters, low-rank gates
and a sigmoid output gate; ``full_attn_layers`` latent attention whose shared
lanes do not rotate (``mla_use_nope``); a leading dense layer, then
``RoutedExperts`` behind a sigmoid router whose choice a bias corrects, of
which this chip holds ``num_experts`` of ``deployment.num_experts_published``
beside the shared one -- and the sequence-wise balance loss added to the
cross-entropy.  The router's bias is state and no parameter: it travels
through ``hvd.make_train_step``'s ``has_aux`` path and the optimizer never
sees it.

    python3 -m benchmark.jobs.kda_moe_lm <workload> <seed>

prints the layers' own counters for one batch of the cell on the device it
finds: which bodies the mixers' calls traced to (``kda.walk_counts``,
``kda.solve_counts``, ``short_conv.body_counts``, ``gated_norm.body_counts``,
``flash_attention.layout_counts``), the KDA layers' ``kda_stats``, the routed
layers' rows gathered per held expert, rows dropped, row buffers run and load
over all the experts; it fails where a row is dropped.  The harness hands a
metric reader no live state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

import horovod_tpu.jax as hvd
from benchmark import arithmetic_kda, arithmetic_moe
from benchmark.jobs.lconv_moe_lm import LconvMoELM
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.mixed_precision import cast_compute, master_weights

if "linear_attn_config" not in LlamaConfig.__dataclass_fields__:
    # The driver tries a new cell on the parent of the PR that adds it, with
    # this file laid over that checkout: end there, before the chip is taken.
    raise ImportError("this program's LlamaConfig has no linear_attn_config: "
                      "it has no Kimi Delta Attention mixer (a delta rule "
                      "with a decay a channel) and no latent attention that "
                      "does not rotate")

from horovod_tpu.models.llama import ROUTER_STATE  # noqa: E402

# What LlamaModel's layers compute, under the configuration's own keys.
REQUIRED = {"model_type": "kimi_linear", "hidden_act": "silu",
            "tie_word_embeddings": False, "mla_use_nope": True,
            "q_lora_rank": None, "rope_scaling": None,
            "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
            "use_grouped_topk": True, "num_expert_group": 1, "topk_group": 1,
            "moe_layer_freq": 1, "num_nextn_predict_layers": 0}
KDA_KERNELS = ("wq", "wk", "wv", "wo", "f_a", "f_b", "g_a", "g_b", "wb")
KDA_PARAMS = ("conv_q", "conv_k", "conv_v", "a_log", "dt_bias", "o_norm")


def build(config: dict, traffic: dict, chips: int):
    return KdaMoELM(config, traffic, chips)


class KdaMoELM(LconvMoELM):
    """``LconvMoELM``'s state beside the parameters, loss and counters
    (``MoELM``'s first loss); the layers, the arithmetic and the reference's
    layout are this configuration's own."""

    def __init__(self, config: dict, traffic: dict, chips: int):
        differ = {key: config[key] for key, wanted in REQUIRED.items()
                  if config[key] != wanted}
        if differ:
            raise ValueError(f"this job trains Kimi Linear's layers "
                             f"({REQUIRED}); the configuration states "
                             f"{differ}")
        training = config["training"]
        if (training["optimizer"], training["compute_dtype"],
                training["master_dtype"]) != ("adamw", "bfloat16", "float32"):
            raise ValueError(f"this job trains bf16 weights under fp32 "
                             f"master AdamW; asked for {training}")
        deployment, assumed = config["deployment"], config["assumed"]
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
            max_seq_len=config["model_max_length"],
            rope_theta=float(config["rope_theta"]),
            rms_eps=config["rms_norm_eps"],
            linear_attn_config=tuple(
                (key, tuple(value) if isinstance(value, list) else value)
                for key, value in sorted(
                    config["linear_attn_config"].items())),
            attention_kind="latent", q_lora_rank=config["q_lora_rank"],
            mla_use_nope=config["mla_use_nope"],
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            num_experts=deployment["num_experts_published"],
            experts_per_token=config["num_experts_per_token"],
            held_experts=config["num_experts"],
            first_held_expert=deployment["first_held_expert"],
            moe_intermediate_size=config["moe_intermediate_size"],
            shared_experts=config["num_shared_experts"],
            first_dense_layers=config["first_k_dense_replace"],
            scoring_func=config["moe_router_activation_func"],
            topk_method="noaux_tc",
            router_bias_update_rate=assumed["router_bias_update_rate"],
            norm_topk_prob=config["moe_renormalize"],
            routed_scaling_factor=config["routed_scaling_factor"],
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

    def kda_counters(self, params, bias, batch):
        """``{name: [KDA layers]}`` of the KDA layers' ``kda_stats`` on
        ``batch``."""
        _, sown = self._apply(params, bias, batch[:, :-1], ["kda_stats"])
        layers = [sown["kda_stats"][f"layer_{i}"]["kda"]
                  for i in range(self.llama.num_layers)
                  if self.llama.is_kda(i)]
        return {name: jnp.stack([layer[name][0] for layer in layers])
                for name in layers[0]}

    # -- facts for the metric readers (benchmark/arithmetic_kda.py) -------

    def _kda_layers(self) -> int:
        return sum(map(self.llama.is_kda, range(self.llama.num_layers)))

    def flops_per_unit(self) -> float:
        c = self.llama
        sizes = dict(c.linear_attn_config)
        kda = self._kda_layers()
        return arithmetic_kda.train_flops_per_token(
            hidden=c.hidden_size, kda_layers=kda,
            latent_layers=c.num_layers - kda,
            dense_layers=c.first_dense_layers, heads=c.num_heads,
            kda_heads=sizes["num_heads"], kda_head_dim=sizes["head_dim"],
            qk_nope=c.qk_nope_head_dim, qk_rope=c.qk_rope_head_dim,
            v_dim=c.v_head_dim, kv_rank=c.kv_lora_rank,
            dense_ffn=c.intermediate_size,
            expert_ffn=c.moe_intermediate_size, shared=c.shared_experts,
            experts=c.num_experts, held=c.experts_held,
            per_token=c.experts_per_token, vocab=c.vocab_size, seq=self.seq)

    def kernel_work_per_step(self) -> dict:
        """A chip's step at what the algorithms need: the flash kernel's two
        passes at 192 / 128 over the latent layers (``flash``, by pass), the
        chunked rule with a decay a channel over the KDA layers
        (``kda_scan``: the algorithm's count, whatever runs it), and the
        routed layers' grouped products at the rows their held experts expect
        (``moe_experts``)."""
        c = self.llama
        sizes = dict(c.linear_attn_config)
        kda = self._kda_layers()
        latent = c.num_layers - kda
        batch = self.batch // self.chips
        shape = dict(batch=batch, seq=self.seq, heads=c.num_heads,
                     qk_dim=c.qk_nope_head_dim + c.qk_rope_head_dim,
                     v_dim=c.v_head_dim)

        def flash(flops, nbytes):
            return {"flops": latent * flops(**shape),
                    "bytes": latent * nbytes(**shape)}

        forward = flash(arithmetic_moe.flash_forward_flops,
                        arithmetic_moe.flash_forward_bytes)
        backward = flash(arithmetic_moe.flash_backward_flops,
                         arithmetic_moe.flash_backward_bytes)
        routed = c.num_layers - c.first_dense_layers
        rows = arithmetic_moe.expert_rows(
            tokens=self.units_per_step // self.chips,
            per_token=c.experts_per_token, held=c.experts_held,
            experts=c.num_experts)
        return {
            "flash": {"flops": forward["flops"] + backward["flops"],
                      "bytes": forward["bytes"] + backward["bytes"],
                      "forward": forward, "backward": backward},
            "kda_scan": arithmetic_kda.scan_work(
                layers=kda, batch=batch, seq=self.seq,
                heads=sizes["num_heads"], key_dim=sizes["head_dim"],
                value_dim=sizes["head_dim"]),
            "moe_experts": {
                "flops": routed * arithmetic_moe.expert_products_flops(
                    rows=rows, hidden=c.hidden_size,
                    expert_ffn=c.moe_intermediate_size),
                "bytes": routed * arithmetic_moe.expert_products_bytes(
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
            out = {"norm_attn": layer["norm_attn"]["scale"],
                   "norm_mlp": layer["norm_mlp"]["scale"]}
            if c.is_kda(i):
                mixer = layer["kda"]
                out.update({name: mixer[name]["kernel"]
                            for name in KDA_KERNELS})
                out.update({name: mixer[name] for name in KDA_PARAMS})
            else:
                attn = layer["attn"]
                out.update({name: attn[name]["kernel"]
                            for name in ("wq", "wkv_a", "wkv_b", "wo")})
                out["kv_norm"] = attn["kv_norm"]["scale"]
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
    from horovod_tpu.ops import flash_attention, gated_norm, kda, short_conv

    argv = argv or sys.argv[1:]
    if len(argv) != 2:
        sys.exit("usage: python3 -m benchmark.jobs.kda_moe_lm <workload> "
                 "<seed>")
    workload, seed = argv
    cell = manifest.cell(workload)
    job = build(cell["config"], cell["traffic"], cell["chips"])
    k_state, k_sample = jax.random.split(
        jax.random.key(np.uint32(int(seed) % 2 ** 32)))

    def counters(k_state, k_sample):
        params, _, bias = job.init_state(k_state)
        batch = job.make_batch(k_sample)
        return (job.layer_counters(params, bias, batch),
                job.kda_counters(params, bias, batch))

    moe, stats = jax.tree.map(np.asarray,
                              jax.jit(counters)(k_state, k_sample))
    rows = moe["rows_per_expert"]
    device = jax.devices()[0]
    print(f"[kda_moe_lm] {workload} seed {seed} on {device.platform} "
          f"({device.device_kind}): {job.batch} x {job.seq} tokens; the rule "
          f"walked {kda.walk_counts()}, solved {kda.solve_counts()}; "
          f"convolutions {short_conv.body_counts()}, output norms "
          f"{gated_norm.body_counts()}, flash calls traced "
          f"{flash_attention.layout_counts()}; kda_stats by layer "
          f"{ {name: value.tolist() for name, value in stats.items()} }; "
          f"rows gathered per held expert a routed layer: mean "
          f"{rows.mean():.1f}, max {rows.max()}, min {rows.min()}; by layer "
          f"max {rows.max(axis=1).tolist()}; rows dropped "
          f"{moe['rows_dropped'].tolist()}; row buffers run "
          f"{moe['row_buffers_run'].tolist()}; load over all "
          f"{job.llama.num_experts} experts, max over mean "
          f"{moe['load_max_over_mean'].tolist()}; choice bias, largest "
          f"{moe['bias_abs_max'].tolist()}", flush=True)
    if moe["rows_dropped"].any():
        sys.exit("[kda_moe_lm] a row was dropped")


if __name__ == "__main__":
    main()
