"""Causal-LM training of a decoder whose router reads the layer's INPUT,
before the norm and before attention, whose experts are ReGLU with none
shared and no dense layer, and whose softmax layers are of two kinds: those
that attend to all their causal keys and do not rotate, and those that
attend through a sliding window and do (SmallThinker-21BA3B-Instruct),
through the program's main path: ``WindowMoELM``'s job (and through it
``MoELM``'s loss and routing counters), its arithmetic of the band and of the
held experts' rows, with ``LlamaModel``'s layers as the configuration's
``rope_layout`` and ``sliding_window_layout`` name them, of which this chip
holds ``moe_num_primary_experts`` of ``deployment.num_experts_published``
experts, and the batch-wise balance loss added to the cross-entropy.

    python3 -m benchmark.jobs.prerouted_moe_lm <workload> <seed>

prints the layers' own counters for one batch of the cell on the device it
finds: the block pairs a head that each kind of layer's flash calls walk
and those an edge of its mask crosses (``flash_attention.pair_counts``),
the layouts the calls took (``layout_counts``), the rows gathered per held
expert by layer, rows dropped and row buffers run, and the load over ALL
the experts as max over mean; it fails where a row is dropped.  The harness
hands a metric reader no live state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

import horovod_tpu.jax as hvd
from benchmark.jobs.window_moe_lm import WindowMoELM
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.mixed_precision import master_weights

if "router_input" not in LlamaConfig.__dataclass_fields__:
    # The driver tries a new cell on the parent of the PR that adds it, with
    # this file laid over that checkout: end there, before the chip is taken.
    raise ImportError("this program's LlamaConfig has no router_input: its "
                      "router reads what the experts read and cannot read "
                      "the layer's input ahead of attention")

from horovod_tpu.models.llama import RopeParameters  # noqa: E402

# What LlamaModel's layers compute, under the configuration's own keys.
REQUIRED = {"model_name": "smallthinker_21b_instruct",
            "tie_word_embeddings": False, "rope_scaling": None,
            "moe_primary_router_apply_softmax": True, "norm_topk_prob": True}
ASSUMED = {"router_input": "layer_input", "qk_norm": False,
           "attention_bias": False, "output_gate": None}
KINDS = ("full_attention", "sliding_attention")     # by sliding_window_layout


def build(config: dict, traffic: dict, chips: int):
    return PreRoutedMoELM(config, traffic, chips)


class PreRoutedMoELM(WindowMoELM):
    """``WindowMoELM``'s state (an embedding of unit variance), arithmetic
    and counters; the layers and the reference's layout are this
    configuration's own."""

    def __init__(self, config: dict, traffic: dict, chips: int):
        layers = config["num_hidden_layers"]
        differ = {key: config[key] for key, wanted in REQUIRED.items()
                  if config[key] != wanted}
        differ.update({key: config["assumed"][key]
                       for key, wanted in ASSUMED.items()
                       if config["assumed"][key] != wanted})
        windowed = config["sliding_window_layout"]
        if (differ or len(windowed) != layers
                or config["rope_layout"] != windowed
                or set(windowed) - {0, 1}):
            raise ValueError(
                f"this job trains SmallThinker's decoder layers "
                f"({REQUIRED}, {ASSUMED}; rope_layout and "
                f"sliding_window_layout one 0 or 1 a layer and equal: a "
                f"window layer rotates and a global one does not); the "
                f"configuration states {differ or config}")
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
        self.llama = LlamaConfig(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_layers=layers,
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            attention_head_dim=config["head_dim"],
            # No layer has a dense feed-forward: the field is not read.
            intermediate_size=config["moe_ffn_hidden_size"],
            max_seq_len=config["max_position_embeddings"],
            rms_eps=config["rms_norm_eps"],
            layer_types=tuple(KINDS[flag] for flag in windowed),
            sliding_window=(config["sliding_window_size"]
                            if any(windowed) else None),
            rope_parameters=(
                (KINDS[0], None),
                (KINDS[1], RopeParameters(float(config["rope_theta"])))),
            num_experts=deployment["num_experts_published"],
            experts_per_token=config["moe_num_active_primary_experts"],
            held_experts=config["moe_num_primary_experts"],
            first_held_expert=deployment["first_held_expert"],
            moe_intermediate_size=config["moe_ffn_hidden_size"],
            norm_topk_prob=config["norm_topk_prob"],
            mlp_hidden_act="relu", router_input="layer",
            balance_over="batch", remat=training.get("remat", "none"))
        self.model = LlamaModel(self.llama, attention_fn=flash_attention_fn)
        rate = optax.linear_schedule(0.0, training["learning_rate"],
                                     training["warmup_steps"])
        self.optimizer = hvd.DistributedOptimizer(
            master_weights(optax.adamw(rate)))

    def layer_counters(self, params, batch):
        """``routing_counters`` and, a layer, the load over all the experts
        as max over mean."""
        _, sown = self.model.apply(params, batch[:, :-1],
                                   mutable=["moe_stats"])
        layers = [sown["moe_stats"][f"layer_{i}"]["moe"]
                  for i in range(self.llama.num_layers)]
        return tuple(jnp.stack([layer[name][0] for layer in layers])
                     for name in ("rows_per_expert", "rows_dropped",
                                  "row_buffers_run", "load_max_over_mean"))

    # -- checks ---------------------------------------------------------

    def to_reference(self, tree):
        p = tree["params"]
        width = self.llama.moe_intermediate_size
        layers = []
        for i in range(self.llama.num_layers):
            layer = p[f"layer_{i}"]
            moe = layer["moe"]
            layers.append({
                "norm_attn": layer["norm_attn"]["scale"],
                **{name: layer["attn"][name]["kernel"]
                   for name in ("wq", "wk", "wv", "wo")},
                "norm_mlp": layer["norm_mlp"]["scale"],
                "router": moe["router"]["kernel"],
                "experts": {"w_gate": moe["w_gate_up"][..., :width],
                            "w_up": moe["w_gate_up"][..., width:],
                            "w_down": moe["w_down"]}})
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
        sys.exit("usage: python3 -m benchmark.jobs.prerouted_moe_lm "
                 "<workload> <seed>")
    workload, seed = argv
    cell = manifest.cell(workload)
    job = build(cell["config"], cell["traffic"], cell["chips"])
    k_state, k_sample = jax.random.split(
        jax.random.key(np.uint32(int(seed) % 2 ** 32)))

    def counters(k_state, k_sample):
        params, _ = job.init_state(k_state)
        return job.layer_counters(params, job.make_batch(k_sample))

    before = flash_attention.layout_counts()
    rows, dropped, buffers, load = map(
        np.asarray, jax.jit(counters)(k_state, k_sample))
    after = flash_attention.layout_counts()
    block = flash_attention._pick_block(job.seq, flash_attention.BLOCK_Q)
    pairs = {
        str(window): flash_attention.pair_counts(job.seq, block, block, True,
                                                 window)
        for window in sorted({w for _, w in job._layers()}, key=str)}
    device = jax.devices()[0]
    print(f"[prerouted_moe_lm] {workload} seed {seed} on {device.platform} "
          f"({device.device_kind}): {job.batch} x {job.seq} tokens; block "
          f"pairs a head at {block}-row blocks (live, crossed by an edge) by "
          f"window {pairs}; flash calls traced in place "
          f"{after['in_place'] - before['in_place']}, flat "
          f"{after['flat']}; rows gathered per held expert a layer: mean "
          f"{rows.mean():.1f}, max {rows.max()}, min {rows.min()}; by layer "
          f"{rows.tolist()}; rows dropped {dropped.tolist()}; row buffers "
          f"run {buffers.tolist()}; load over all {job.llama.num_experts} "
          f"experts, max over mean, by layer "
          f"{[round(float(m), 3) for m in load]}", flush=True)
    if dropped.any():
        sys.exit("[prerouted_moe_lm] a row was dropped")


if __name__ == "__main__":
    main()
