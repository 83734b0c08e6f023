"""Causal-LM training of a decoder whose softmax layers attend to all their
causal keys or through a sliding window, each kind at its own count of query
heads and with its own rotary table, a per-head output gate, and routed
experts beside a shared one (Laguna-S-2.1) through the program's main path:
``DecoderLM``'s job (by way of ``MoELM``, whose loss and routing counters
it keeps) with ``LlamaModel``'s layers as the configuration's ``layer_types``, ``num_attention_heads_per_layer``, ``rope_parameters``,
``gating`` and ``mlp_layer_types`` name them -- a ``"sliding_attention"``
layer's flash calls walk its band alone -- of which this chip holds
``num_experts`` of ``deployment.num_experts_published`` experts, and the
batch-wise balance loss added to the cross-entropy.

    python3 -m benchmark.jobs.window_moe_lm <workload> <seed>

prints the layers' own counters for one batch of the cell on the device it
finds: the block pairs a head that each kind of layer's flash calls walk
and those an edge of its mask crosses (``flash_attention.pair_counts``),
the layouts the calls took (``layout_counts``), and the routed layers' rows
gathered per held expert, rows dropped and row buffers run; it fails where
a row is dropped.  The harness hands a metric reader no live state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

import horovod_tpu.jax as hvd
from benchmark import arithmetic_moe, arithmetic_window
from benchmark.jobs.moe_lm import MoELM
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.mixed_precision import cast_compute, master_weights

if "sliding_window" not in LlamaConfig.__dataclass_fields__:
    # The driver tries a new cell on the parent of the PR that adds it, with
    # this file laid over that checkout: end there, before the chip is taken.
    raise ImportError("this program's LlamaConfig has no sliding_window: it "
                      "cannot run window and full attention layers in one "
                      "stack")

from horovod_tpu.models.llama import RopeParameters, YarnScaling  # noqa: E402

# What LlamaModel's layers compute, under the configuration's own keys.
REQUIRED = {"model_type": "laguna", "tie_word_embeddings": False,
            "attention_bias": False, "gating": "per-head",
            "decoder_sparse_step": 1, "mlp_only_layers": [0],
            "moe_apply_router_weight_on_input": False,
            "moe_router_logit_softcapping": 0}
YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "attention_factor")


def rope_parameters(published: dict) -> tuple:
    """``rope_parameters`` of the configuration's file as ``LlamaConfig``
    takes them; a ``rope_type`` the model's tables do not know is refused."""
    entries = []
    for kind, rope in published.items():
        known = {"rope_type", "rope_theta", "partial_rotary_factor"}
        scaling = None
        if rope["rope_type"] == "yarn":
            known |= set(YARN_KEYS)
            scaling = YarnScaling(**{key: rope[key] for key in YARN_KEYS})
        elif rope["rope_type"] != "default":
            raise ValueError(f"rope_type {rope['rope_type']!r} of {kind} "
                             f"layers: 'default' or 'yarn'")
        if set(rope) - known:
            raise ValueError(f"rope_parameters of {kind} layers state "
                             f"{sorted(set(rope) - known)}, which this job "
                             f"does not compute")
        entries.append((kind, RopeParameters(
            float(rope["rope_theta"]), scaling,
            float(rope["partial_rotary_factor"]))))
    return tuple(entries)


def build(config: dict, traffic: dict, chips: int):
    return WindowMoELM(config, traffic, chips)


class WindowMoELM(MoELM):
    """``MoELM``'s loss (cross-entropy + alpha x the balance loss), routing
    counters and first loss; the layers, the arithmetic and the reference's
    layout are this configuration's own."""

    def __init__(self, config: dict, traffic: dict, chips: int):
        layers = config["num_hidden_layers"]
        differ = {key: config[key] for key, wanted in REQUIRED.items()
                  if config[key] != wanted}
        if (differ or config["mlp_layer_types"]
                != ["dense"] + ["sparse"] * (layers - 1)
                or set(config["gating_types"]) != {"per_head"}
                or config["shared_expert_intermediate_size"]
                % config["moe_intermediate_size"]):
            raise ValueError(f"this job trains Laguna's decoder layers "
                             f"({REQUIRED}, one leading dense layer, a "
                             f"per-head gate in every layer); the "
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
            intermediate_size=config["intermediate_size"],
            max_seq_len=config["max_position_embeddings"],
            rms_eps=config["rms_norm_eps"],
            layer_types=tuple(config["layer_types"]),
            sliding_window=config["sliding_window"],
            num_attention_heads_per_layer=tuple(
                config["num_attention_heads_per_layer"]),
            gating=config["gating"],
            rope_parameters=rope_parameters(config["rope_parameters"]),
            num_experts=deployment["num_experts_published"],
            experts_per_token=config["num_experts_per_tok"],
            held_experts=config["num_experts"],
            first_held_expert=deployment["first_held_expert"],
            moe_intermediate_size=config["moe_intermediate_size"],
            shared_experts=(config["shared_expert_intermediate_size"]
                            // config["moe_intermediate_size"]),
            first_dense_layers=len(config["mlp_only_layers"]),
            norm_topk_prob=config["norm_topk_prob"],
            routed_scaling_factor=config["moe_routed_scaling_factor"],
            balance_over="batch", remat=training.get("remat", "none"))
        self.model = LlamaModel(self.llama, attention_fn=flash_attention_fn)
        rate = optax.linear_schedule(0.0, training["learning_rate"],
                                     training["warmup_steps"])
        self.optimizer = hvd.DistributedOptimizer(
            master_weights(optax.adamw(rate)))

    # -- what the harness jits ------------------------------------------

    def init_state(self, key):
        """As ``DecoderLM``, with an embedding of unit variance (the
        configuration's ``assumed.initialisation`` says why)."""
        params = LlamaModel(self.llama).init(key, jnp.zeros((1, 8),
                                                            jnp.int32))
        table = params["params"]["tok_emb"]
        table["embedding"] = table["embedding"] * self.llama.hidden_size ** 0.5
        params = cast_compute(params)
        return params, self.optimizer.init(params)

    # -- facts for the metric readers (benchmark/arithmetic_window.py) ----

    def _layers(self):
        c = self.llama
        return [(c.heads_of(i), c.window_of(i)) for i in range(c.num_layers)]

    def flops_per_unit(self) -> float:
        c = self.llama
        heads, windows = zip(*self._layers())
        return arithmetic_window.train_flops_per_token(
            hidden=c.hidden_size, heads_by_layer=heads,
            windows_by_layer=windows, kv_heads=c.num_kv_heads,
            head_dim=c.head_dim, gated=c.gating is not None,
            dense_layers=c.first_dense_layers, dense_ffn=c.intermediate_size,
            expert_ffn=c.moe_intermediate_size,
            shared_ffn=c.shared_experts * c.moe_intermediate_size,
            experts=c.num_experts, held=c.experts_held,
            per_token=c.experts_per_token, vocab=c.vocab_size, seq=self.seq)

    def kernel_work_per_step(self) -> dict:
        """A chip's step at what the algorithms need: every layer's
        attention over its own pairs at its own head count (``flash``, by
        pass), the sliding layers' part of it alone (``window_attn``: the
        band, whatever blocks the calls walk), and the routed layers'
        grouped products at the rows their held experts expect."""
        c = self.llama
        work = [arithmetic_window.attention_work(
            batch=self.batch // self.chips, seq=self.seq, heads=heads,
            kv_heads=c.num_kv_heads, head_dim=c.head_dim, window=window)
            for heads, window in self._layers()]

        def total(layers):
            return jax.tree.map(lambda *x: sum(x), *layers)

        routed_layers = c.num_layers - c.first_dense_layers
        rows = arithmetic_moe.expert_rows(
            tokens=self.units_per_step // self.chips,
            per_token=c.experts_per_token, held=c.experts_held,
            experts=c.num_experts)
        band = total([w for w, (_, window) in zip(work, self._layers())
                      if window is not None])
        return {
            "flash": total(work),
            "window_attn": {"flops": band["flops"], "bytes": band["bytes"]},
            "moe_experts": {
                "flops": routed_layers * arithmetic_moe.expert_products_flops(
                    rows=rows, hidden=c.hidden_size,
                    expert_ffn=c.moe_intermediate_size),
                "bytes": routed_layers * arithmetic_moe.expert_products_bytes(
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
                   **{name: layer["attn"][name]["kernel"]
                      for name in ("wq", "wk", "wv", "wg", "wo")},
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
    from horovod_tpu.ops import flash_attention

    argv = argv or sys.argv[1:]
    if len(argv) != 2:
        sys.exit("usage: python3 -m benchmark.jobs.window_moe_lm <workload> "
                 "<seed>")
    workload, seed = argv
    cell = manifest.cell(workload)
    job = build(cell["config"], cell["traffic"], cell["chips"])
    k_state, k_sample = jax.random.split(
        jax.random.key(np.uint32(int(seed) % 2 ** 32)))

    def counters(k_state, k_sample):
        params, _ = job.init_state(k_state)
        return job.routing_counters(params, job.make_batch(k_sample))

    before = flash_attention.layout_counts()
    rows, dropped, buffers = map(np.asarray,
                                 jax.jit(counters)(k_state, k_sample))
    after = flash_attention.layout_counts()
    block = flash_attention._pick_block(job.seq, flash_attention.BLOCK_Q)
    pairs = {
        str(window): flash_attention.pair_counts(job.seq, block, block, True,
                                                 window)
        for window in sorted({w for _, w in job._layers()}, key=str)}
    device = jax.devices()[0]
    print(f"[window_moe_lm] {workload} seed {seed} on {device.platform} "
          f"({device.device_kind}): {job.batch} x {job.seq} tokens; block "
          f"pairs a head at {block}-row blocks (live, crossed by an edge) by "
          f"window {pairs}; flash calls traced in place "
          f"{after['in_place'] - before['in_place']}, flat {after['flat']}; "
          f"rows gathered per held expert a layer: mean {rows.mean():.1f}, "
          f"max {rows.max()}, min {rows.min()}; by layer {rows.tolist()}; "
          f"rows dropped {dropped.tolist()}; row buffers run "
          f"{buffers.tolist()}", flush=True)
    if dropped.any():
        sys.exit("[window_moe_lm] a row was dropped")


if __name__ == "__main__":
    main()
