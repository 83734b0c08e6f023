"""Causal-LM training of a decoder whose layer is ONE sublayer
(NVIDIA-Nemotron-3-Nano-30B-A3B) through the program's main path:
``DecoderLM``'s job (by way of ``MoELM``, whose loss it extends) with
``LlamaModel``'s layers as the configuration's ``hybrid_override_pattern``
spells them -- ``Mamba2`` state-space mixers, ``RoutedExperts`` with relu^2
experts behind a sigmoid router whose choice a bias corrects, of which this
chip holds ``n_routed_experts`` of ``deployment.num_experts_published``
beside a shared expert of its own width, and grouped-query attention without
a rotation -- and the batch-wise balance loss added to the cross-entropy.
The router's bias is state and no parameter: it travels through
``hvd.make_train_step``'s ``has_aux`` path (``loss_fn(params, bias, rows) ->
(loss, new bias)``), as ResNet-50's batch-norm state does, and the optimizer
never sees it.

    python3 -m benchmark.jobs.ssm_moe_lm <workload> <seed>

prints the layers' own counters for one batch of the cell on the device it
finds: the routed layers' rows gathered per held expert, rows dropped, row
buffers run and load over all the experts, the Mamba layers' decays, steps
and largest state, and the bodies the mixers' calls traced to
(``flash_attention.layout_counts``, ``short_conv.body_counts``); it fails
where a row is dropped.  The harness hands a metric reader no live state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

import horovod_tpu.jax as hvd
from benchmark import arithmetic_moe, arithmetic_ssd, arithmetic_window
from benchmark.jobs.moe_lm import MoELM
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.losses import balance_loss, softmax_cross_entropy
from horovod_tpu.ops.mixed_precision import cast_compute, master_weights

if "hybrid_override_pattern" not in LlamaConfig.__dataclass_fields__:
    # The driver tries a new cell on the parent of the PR that adds it, with
    # this file laid over that checkout: end there, before the chip is taken.
    raise ImportError("this program's LlamaConfig has no "
                      "hybrid_override_pattern: it cannot run a stack whose "
                      "layer is one sublayer, a Mamba-2 mixer, relu2 experts "
                      "or a bias-corrected sigmoid router")

from horovod_tpu.models.llama import (ATTENTION, EXPERTS, MAMBA,  # noqa: E402
                                      ROUTER_STATE)

# What LlamaModel's layers compute, under the configuration's own keys.
REQUIRED = {"model_type": "nemotron_h", "mlp_hidden_act": "relu2",
            "mamba_hidden_act": "silu", "use_conv_bias": True,
            "mamba_proj_bias": False, "mlp_bias": False,
            "attention_bias": False, "use_bias": False,
            "tie_word_embeddings": False, "n_group": 1, "topk_group": 1,
            "n_shared_experts": 1, "sliding_window": None,
            "residual_in_fp32": False, "rescale_prenorm_residual": True}
# The matrices that write the residual stream, which
# ``rescale_prenorm_residual`` scales by 1 / sqrt(the published depth).
WRITERS = {MAMBA: ("mamba", "out_proj"), ATTENTION: ("attn", "wo")}


def build(config: dict, traffic: dict, chips: int):
    return SsmMoELM(config, traffic, chips)


class SsmMoELM(MoELM):
    """``MoELM``'s first loss; the layers, the state beside the parameters,
    the arithmetic and the reference's layout are this configuration's own."""

    has_aux = True

    def __init__(self, config: dict, traffic: dict, chips: int):
        differ = {key: config[key] for key, wanted in REQUIRED.items()
                  if config[key] != wanted}
        pattern = config["hybrid_override_pattern"]
        if (differ or len(pattern) != config["num_hidden_layers"]
                or config["mamba_num_heads"] * config["mamba_head_dim"]
                != config["assumed"]["d_inner"]):
            raise ValueError(f"this job trains Nemotron-H's layers "
                             f"({REQUIRED}, one pattern character a layer); "
                             f"the configuration states {differ or config}")
        training = config["training"]
        if (training["optimizer"], training["compute_dtype"],
                training["master_dtype"]) != ("adamw", "bfloat16", "float32"):
            raise ValueError(f"this job trains bf16 weights under fp32 "
                             f"master AdamW; asked for {training}")
        deployment = config["deployment"]
        self.config = config
        self.chips = chips
        self.seq = traffic["sequence"]
        self.batch = traffic["batch_per_chip"] * chips
        self.sample_rows = traffic["sample_per_chip"] * chips
        self.units_per_step = self.batch * self.seq
        self.alpha = config["assumed"]["aux_loss_alpha"]
        self.depth_published = deployment["num_hidden_layers_published"]
        self.llama = LlamaConfig(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            hybrid_override_pattern=pattern,
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            attention_head_dim=config["head_dim"],
            intermediate_size=config["intermediate_size"],
            max_seq_len=config["max_position_embeddings"],
            rope_theta=None,        # assumed.no_positional_embedding
            rms_eps=config["layer_norm_epsilon"],
            mamba_num_heads=config["mamba_num_heads"],
            mamba_head_dim=config["mamba_head_dim"],
            ssm_state_size=config["ssm_state_size"],
            n_groups=config["n_groups"],
            conv_kernel=config["conv_kernel"],
            chunk_size=config["chunk_size"],
            num_experts=deployment["num_experts_published"],
            experts_per_token=config["num_experts_per_tok"],
            held_experts=config["n_routed_experts"],
            first_held_expert=deployment["first_held_expert"],
            moe_intermediate_size=config["moe_intermediate_size"],
            shared_experts=config["n_shared_experts"],
            moe_shared_expert_intermediate_size=config[
                "moe_shared_expert_intermediate_size"],
            mlp_hidden_act=config["mlp_hidden_act"],
            scoring_func="sigmoid", topk_method="noaux_tc",
            router_bias_update_rate=config["assumed"]["bias_update_rate"],
            norm_topk_prob=config["norm_topk_prob"],
            routed_scaling_factor=config["routed_scaling_factor"],
            balance_over="batch", remat=training.get("remat", "none"))
        self.model = LlamaModel(self.llama, attention_fn=flash_attention_fn)
        rate = optax.linear_schedule(0.0, training["learning_rate"],
                                     training["warmup_steps"])
        self.optimizer = hvd.DistributedOptimizer(
            master_weights(optax.adamw(rate)))

    # -- what the harness jits ------------------------------------------

    def init_state(self, key):
        """(params, opt_state, the routers' choice bias).  As ``DecoderLM``,
        with an embedding of unit variance and the matrices that write the
        residual stream scaled by 1 / sqrt(the published depth) (the
        configuration's ``assumed.initialisation`` says why)."""
        c = self.llama
        variables = LlamaModel(c).init(key, jnp.zeros((1, 8), jnp.int32))
        params = variables["params"]
        table = params["tok_emb"]
        table["embedding"] = table["embedding"] * c.hidden_size ** 0.5
        shrink = self.depth_published ** -0.5
        for i in range(c.num_layers):
            layer, kind = params[f"layer_{i}"], c.kind_of(i)
            if kind == EXPERTS:
                moe = layer["moe"]
                moe["w_down"] = moe["w_down"] * shrink
                writer = moe["shared"]["w_down"]
            else:
                module, name = WRITERS[kind]
                writer = layer[module][name]
            writer["kernel"] = writer["kernel"] * shrink
        params = cast_compute({"params": params})
        return (params, self.optimizer.init(params),
                variables[ROUTER_STATE])

    def _apply(self, params, bias, tokens, mutable):
        return self.model.apply({**params, ROUTER_STATE: bias}, tokens,
                                mutable=mutable)

    def loss_fn(self, params, bias, batch):
        logits, sown = self._apply(params, bias, batch[:, :-1],
                                   ["losses", ROUTER_STATE])
        loss = (softmax_cross_entropy(logits, batch[:, 1:])
                + self.alpha * balance_loss(sown))
        return loss, sown[ROUTER_STATE]

    def layer_counters(self, params, bias, batch):
        """What the layers count of themselves on ``batch``: the routed
        layers' ``moe_stats`` and the Mamba layers' ``ssd_stats``, each
        ``{name: [layers of the kind, ..]}``."""
        _, sown = self._apply(params, bias, batch[:, :-1],
                              ["moe_stats", "ssd_stats"])

        def stacked(collection, module):
            layers = [layer[module] for _, layer in sorted(
                sown[collection].items(),
                key=lambda item: int(item[0].split("_")[1]))]
            return {name: jnp.stack([layer[name][0] for layer in layers])
                    for name in layers[0]}

        return stacked("moe_stats", "moe"), stacked("ssd_stats", "mamba")

    def routing_counters(self, params, bias, batch):
        """``MoELM``'s three, with the state it has no argument for."""
        moe, _ = self.layer_counters(params, bias, batch)
        return tuple(moe[name] for name in (
            "rows_per_expert", "rows_dropped", "row_buffers_run"))

    # -- facts for the metric readers (benchmark/arithmetic_ssd.py) -------

    def _layers(self, kind: str) -> int:
        return self.llama.hybrid_override_pattern.count(kind)

    def flops_per_unit(self) -> float:
        c = self.llama
        return arithmetic_ssd.train_flops_per_token(
            hidden=c.hidden_size, mamba_layers=self._layers(MAMBA),
            attention_layers=self._layers(ATTENTION),
            routed_layers=self._layers(EXPERTS), heads=c.num_heads,
            kv_heads=c.num_kv_heads, head_dim=c.head_dim,
            mamba_heads=c.mamba_num_heads, mamba_head_dim=c.mamba_head_dim,
            groups=c.n_groups, state=c.ssm_state_size,
            expert_ffn=c.moe_intermediate_size,
            shared_ffn=c.moe_shared_expert_intermediate_size,
            experts=c.num_experts, held=c.experts_held,
            per_token=c.experts_per_token, vocab=c.vocab_size, seq=self.seq,
            chunk=c.chunk_size)

    def kernel_work_per_step(self) -> dict:
        """A chip's step at what the algorithms need: the flash kernel's two
        passes over the attention layers (``flash``, by pass), the chunked
        scan over the Mamba layers with B and C read once a group
        (``ssd_scan``: the algorithm's count, whatever runs it), and the
        routed layers' two grouped products at the rows their held experts
        expect (``moe_experts``)."""
        c = self.llama
        batch = self.batch // self.chips
        attention = arithmetic_window.attention_work(
            batch=batch, seq=self.seq, heads=c.num_heads,
            kv_heads=c.num_kv_heads, head_dim=c.head_dim, window=None)
        scan = dict(batch=batch, seq=self.seq, heads=c.mamba_num_heads,
                    groups=c.n_groups, head_dim=c.mamba_head_dim,
                    state=c.ssm_state_size, chunk=c.chunk_size)
        rows = arithmetic_moe.expert_rows(
            tokens=self.units_per_step // self.chips,
            per_token=c.experts_per_token, held=c.experts_held,
            experts=c.num_experts)
        mamba, routed = self._layers(MAMBA), self._layers(EXPERTS)
        return {
            "flash": jax.tree.map(lambda x: x * self._layers(ATTENTION),
                                  attention),
            "ssd_scan": {
                "flops": mamba * arithmetic_ssd.scan_flops(**scan),
                "bytes": mamba * arithmetic_ssd.scan_bytes(**scan)},
            "moe_experts": {
                "flops": routed * arithmetic_ssd.expert_products_flops(
                    rows=rows, hidden=c.hidden_size,
                    expert_ffn=c.moe_intermediate_size),
                "bytes": routed * arithmetic_ssd.expert_products_bytes(
                    rows=rows, held=c.experts_held, hidden=c.hidden_size,
                    expert_ffn=c.moe_intermediate_size)}}

    # -- checks ---------------------------------------------------------

    def to_reference(self, tree):
        p = tree["params"]
        c = self.llama
        layers = []
        for i in range(c.num_layers):
            layer, kind = p[f"layer_{i}"], c.kind_of(i)
            out = {"norm": layer["norm"]["scale"]}
            if kind == MAMBA:
                mixer = layer["mamba"]
                out.update({name: mixer[name] for name in (
                    "conv_w", "conv_b", "a_log", "dt_bias", "d")})
                out.update({"in_proj": mixer["in_proj"]["kernel"],
                            "norm_w": mixer["norm"],
                            "out_proj": mixer["out_proj"]["kernel"]})
            elif kind == ATTENTION:
                out.update({name: layer["attn"][name]["kernel"]
                            for name in ("wq", "wk", "wv", "wo")})
            else:
                moe = layer["moe"]
                out.update({
                    "router": moe["router"]["kernel"],
                    "experts": {"w_up": moe["w_up"],
                                "w_down": moe["w_down"]},
                    "shared": {name: moe["shared"][name]["kernel"]
                               for name in ("w_up", "w_down")}})
            layers.append(out)
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
        sys.exit("usage: python3 -m benchmark.jobs.ssm_moe_lm <workload> "
                 "<seed>")
    workload, seed = argv
    cell = manifest.cell(workload)
    job = build(cell["config"], cell["traffic"], cell["chips"])
    k_state, k_sample = jax.random.split(
        jax.random.key(np.uint32(int(seed) % 2 ** 32)))

    def counters(k_state, k_sample):
        params, _, bias = job.init_state(k_state)
        return job.layer_counters(params, bias, job.make_batch(k_sample))

    moe, ssd = jax.tree.map(np.asarray, jax.jit(counters)(k_state, k_sample))
    rows = moe["rows_per_expert"]
    device = jax.devices()[0]
    print(f"[ssm_moe_lm] {workload} seed {seed} on {device.platform} "
          f"({device.device_kind}): {job.batch} x {job.seq} tokens; flash "
          f"calls traced {flash_attention.layout_counts()}, convolutions "
          f"{short_conv.body_counts()}; rows gathered per held expert a "
          f"routed layer: mean {rows.mean():.1f}, max {rows.max()}, min "
          f"{rows.min()}; by layer max {rows.max(axis=1).tolist()}; rows "
          f"dropped {moe['rows_dropped'].tolist()}; row buffers run "
          f"{moe['row_buffers_run'].tolist()}; load over all "
          f"{job.llama.num_experts} experts, max over mean "
          f"{moe['load_max_over_mean'].tolist()}; choice bias, largest "
          f"{moe['bias_abs_max'].tolist()}; Mamba layers: "
          + "; ".join(f"{name} {values.tolist()}"
                      for name, values in sorted(ssd.items())), flush=True)
    if moe["rows_dropped"].any():
        sys.exit("[ssm_moe_lm] a row was dropped")


if __name__ == "__main__":
    main()
