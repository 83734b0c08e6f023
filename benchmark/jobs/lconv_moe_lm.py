"""Causal-LM training of a decoder whose mixers are double-gated short
convolutions three to one with grouped-query attention (LFM2-24B-A2B) through
the program's main path: ``DecoderLM``'s job (by way of ``MoELM``, whose loss
it extends) with ``LlamaModel``'s layers as the configuration's
``layer_types`` spells them -- ``GatedShortConv`` mixers and softmax layers
of 64-wide heads with a per-head QK-norm -- over a dense SwiGLU in the first
``num_dense_layers`` layers and ``RoutedExperts`` behind a sigmoid router
whose choice a bias corrects in the others, of which this chip holds
``num_experts`` of ``deployment.num_experts_published`` and none is shared,
a head tied to the embedding, and the batch-wise balance loss added to the
cross-entropy.  The router's bias is state and no parameter: it travels
through ``hvd.make_train_step``'s ``has_aux`` path (``loss_fn(params, bias,
rows) -> (loss, new bias)``), as ``ssm_moe_lm``'s does, and the optimizer
never sees it.

    python3 -m benchmark.jobs.lconv_moe_lm <workload> <seed>

prints the layers' own counters for one batch of the cell on the device it
finds: the routed layers' rows gathered per held expert, rows dropped, row
buffers run and load over all the experts, and the bodies the mixers' calls
traced to (``flash_attention.layout_counts``, ``short_conv.body_counts``); it
fails where a row is dropped.  The harness hands a metric reader no live
state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

import horovod_tpu.jax as hvd
from benchmark import arithmetic_lconv, arithmetic_moe, arithmetic_window
from benchmark.jobs.moe_lm import MoELM
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.losses import balance_loss, softmax_cross_entropy
from horovod_tpu.ops.mixed_precision import cast_compute, master_weights

if "conv_L_cache" not in LlamaConfig.__dataclass_fields__:
    # The driver tries a new cell on the parent of the PR that adds it, with
    # this file laid over that checkout: end there, before the chip is taken.
    raise ImportError("this program's LlamaConfig has no conv_L_cache: it "
                      "cannot run a stack with double-gated "
                      "short-convolution ('conv') layers")

from horovod_tpu.models.llama import ROUTER_STATE  # noqa: E402

# What LlamaModel's layers compute, under the configuration's own keys.
REQUIRED = {"model_type": "lfm2_moe", "conv_bias": False,
            "use_expert_bias": True, "norm_topk_prob": True}
CONV, ATTENTION = "conv", "full_attention"


def build(config: dict, traffic: dict, chips: int):
    return LconvMoELM(config, traffic, chips)


class LconvMoELM(MoELM):
    """``MoELM``'s first loss; the layers, the state beside the parameters,
    the arithmetic and the reference's layout are this configuration's own."""

    has_aux = True

    def __init__(self, config: dict, traffic: dict, chips: int):
        differ = {key: config[key] for key, wanted in REQUIRED.items()
                  if config[key] != wanted}
        kinds, assumed = tuple(config["layer_types"]), config["assumed"]
        if (differ or len(kinds) != config["num_hidden_layers"]
                or set(kinds) - {CONV, ATTENTION}
                or config["rope_parameters"]["rope_type"] != "default"
                or config["head_dim"] * config["num_attention_heads"]
                != config["hidden_size"]):
            raise ValueError(f"this job trains LFM2's layers ({REQUIRED}, one "
                             f"layer type a layer, an unscaled rotation); "
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
        self.alpha = assumed["aux_loss_alpha"]
        self.llama = LlamaConfig(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            layer_types=kinds,
            conv_L_cache=config["conv_L_cache"],
            conv_bias=config["conv_bias"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            attention_head_dim=config["head_dim"],
            qk_norm=True,           # assumed.qk_layernorm
            intermediate_size=config["intermediate_size"],
            max_seq_len=config["max_position_embeddings"],
            rope_theta=float(config["rope_parameters"]["rope_theta"]),
            rms_eps=config["norm_eps"],
            num_experts=deployment["num_experts_published"],
            experts_per_token=config["num_experts_per_tok"],
            held_experts=config["num_experts"],
            first_held_expert=deployment["first_held_expert"],
            moe_intermediate_size=config["moe_intermediate_size"],
            first_dense_layers=config["num_dense_layers"],
            scoring_func="sigmoid", topk_method="noaux_tc",
            router_bias_update_rate=assumed["bias_update_rate"],
            norm_topk_prob=config["norm_topk_prob"],
            routed_scaling_factor=config["routed_scaling_factor"],
            tie_word_embeddings=assumed["tie_word_embeddings"],
            balance_over="batch", remat=training.get("remat", "none"))
        self.model = LlamaModel(self.llama, attention_fn=flash_attention_fn)
        rate = optax.linear_schedule(0.0, training["learning_rate"],
                                     training["warmup_steps"])
        self.optimizer = hvd.DistributedOptimizer(
            master_weights(optax.adamw(rate)))

    # -- what the harness jits ------------------------------------------

    def init_state(self, key):
        """(params, opt_state, the routers' choice bias): the modules' own
        initialisation (the configuration's ``assumed.initialisation``;
        flax's embedding has variance 1 / hidden, which is what leaves the
        tied logits of unit variance)."""
        variables = LlamaModel(self.llama).init(
            key, jnp.zeros((1, 8), jnp.int32))
        params = cast_compute({"params": variables["params"]})
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
        """What the routed layers count of themselves on ``batch``:
        ``{name: [routed layers, ..]}`` of their ``moe_stats``."""
        _, sown = self._apply(params, bias, batch[:, :-1], ["moe_stats"])
        layers = [layer["moe"] for _, layer in sorted(
            sown["moe_stats"].items(),
            key=lambda item: int(item[0].split("_")[1]))]
        return {name: jnp.stack([layer[name][0] for layer in layers])
                for name in layers[0]}

    def routing_counters(self, params, bias, batch):
        """``MoELM``'s three, with the state it has no argument for."""
        moe = self.layer_counters(params, bias, batch)
        return tuple(moe[name] for name in (
            "rows_per_expert", "rows_dropped", "row_buffers_run"))

    # -- facts for the metric readers (benchmark/arithmetic_lconv.py) -----

    def _layers(self, kind: str) -> int:
        return self.llama.layer_types.count(kind)

    def flops_per_unit(self) -> float:
        c = self.llama
        return arithmetic_lconv.train_flops_per_token(
            hidden=c.hidden_size, conv_layers=self._layers(CONV),
            attention_layers=self._layers(ATTENTION),
            dense_layers=c.first_dense_layers,
            routed_layers=c.num_layers - c.first_dense_layers,
            heads=c.num_heads, kv_heads=c.num_kv_heads, head_dim=c.head_dim,
            dense_ffn=c.intermediate_size,
            expert_ffn=c.moe_intermediate_size, experts=c.num_experts,
            held=c.experts_held, per_token=c.experts_per_token,
            vocab=c.vocab_size, seq=self.seq, taps=c.conv_L_cache)

    def kernel_work_per_step(self) -> dict:
        """A chip's step at what the algorithms need: the flash kernel's two
        passes over the attention layers (``flash``, by pass), the gated
        filters of the conv layers, every tensor once each way
        (``lconv_conv``: the algorithm's count, whatever runs it), and the
        routed layers' grouped products at the rows their held experts
        expect (``moe_experts``)."""
        c = self.llama
        batch = self.batch // self.chips
        attention = arithmetic_window.attention_work(
            batch=batch, seq=self.seq, heads=c.num_heads,
            kv_heads=c.num_kv_heads, head_dim=c.head_dim, window=None)
        filters = dict(batch=batch, seq=self.seq, channels=c.hidden_size)
        rows = arithmetic_moe.expert_rows(
            tokens=self.units_per_step // self.chips,
            per_token=c.experts_per_token, held=c.experts_held,
            experts=c.num_experts)
        conv, routed = (self._layers(CONV),
                        c.num_layers - c.first_dense_layers)
        return {
            "flash": jax.tree.map(lambda x: x * self._layers(ATTENTION),
                                  attention),
            "lconv_conv": {
                "flops": conv * arithmetic_lconv.gated_conv_flops(
                    **filters, taps=c.conv_L_cache),
                "bytes": conv * sum(arithmetic_lconv.gated_conv_bytes(
                    **filters).values())},
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

        def swiglu(gate_up, down, width):
            return {"w_gate": gate_up[..., :width], "w_up": gate_up[..., width:],
                    "w_down": down}

        layers = []
        for i in range(c.num_layers):
            layer = p[f"layer_{i}"]
            out = {"norm_op": layer["norm_attn"]["scale"],
                   "norm_ffn": layer["norm_mlp"]["scale"]}
            if c.is_conv(i):
                mixer = layer["conv"]
                out.update({"in_proj": mixer["in_proj"]["kernel"],
                            "conv_w": mixer["conv_w"],
                            "out_proj": mixer["out_proj"]["kernel"]})
            else:
                attn = layer["attn"]
                out.update({name: attn[name]["kernel"]
                            for name in ("wq", "wk", "wv", "wo")})
                out.update({name: attn[name]["scale"]
                            for name in ("q_norm", "k_norm")})
            if c.is_routed(i):
                moe = layer["moe"]
                out.update({
                    "router": moe["router"]["kernel"],
                    "experts": swiglu(moe["w_gate_up"], moe["w_down"],
                                      c.moe_intermediate_size)})
            else:
                out.update(swiglu(layer["mlp"]["w_gate_up"]["kernel"],
                                  layer["mlp"]["w_down"]["kernel"],
                                  c.intermediate_size))
            layers.append(out)
        return {"embed": p["tok_emb"]["embedding"], "layers": layers,
                "norm_f": p["norm_f"]["scale"]}


def main(argv=None) -> None:
    import sys

    import numpy as np

    from benchmark import manifest
    from horovod_tpu.ops import flash_attention, short_conv

    argv = argv or sys.argv[1:]
    if len(argv) != 2:
        sys.exit("usage: python3 -m benchmark.jobs.lconv_moe_lm <workload> "
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
    print(f"[lconv_moe_lm] {workload} seed {seed} on {device.platform} "
          f"({device.device_kind}): {job.batch} x {job.seq} tokens; flash "
          f"calls traced {flash_attention.layout_counts()}, convolutions "
          f"{short_conv.body_counts()}; rows gathered per held expert a "
          f"routed layer: mean {rows.mean():.1f}, max {rows.max()}, min "
          f"{rows.min()}; by layer max {rows.max(axis=1).tolist()}; rows "
          f"dropped {moe['rows_dropped'].tolist()}; row buffers run "
          f"{moe['row_buffers_run'].tolist()}; load over all "
          f"{job.llama.num_experts} experts, max over mean "
          f"{moe['load_max_over_mean'].tolist()}; choice bias, largest "
          f"{moe['bias_abs_max'].tolist()}", flush=True)
    if moe["rows_dropped"].any():
        sys.exit("[lconv_moe_lm] a row was dropped")


if __name__ == "__main__":
    main()
