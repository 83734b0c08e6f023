"""Causal-LM training of a decoder with learned sparse attention over
grouped-query heads and routed experts (Keye-VL-2.0-30B-A3B's language
model) through the program's main path: ``DecoderLM``'s job with
``LlamaModel``'s layers of the kinds the configuration's file names --
QK-norm, an indexer that picks ``sa_config.topk`` of each query's causal
keys, the flash kernel's two calls over the selection, and routed layers of
which this chip holds ``num_local_experts`` of ``num_experts`` with
renormalised gates and no shared expert -- and the batch-wise balance loss
and the indexer's loss added to the cross-entropy.

    python3 -m benchmark.jobs.sparse_moe_lm <workload> <seed>

prints the layers' own counters for one sequence of the cell on the device
it finds: keys taken a query (mean and max), the share of the plain
reference's selected keys that the program selected too (a layer), rows
gathered per held expert and rows dropped; it fails where the agreement is
under the configuration's ``checks.selection_agreement_at_least``, a query
has more than ``topk`` keys or a row is dropped.  The harness hands a
metric reader no live state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

import horovod_tpu.jax as hvd
from benchmark import arithmetic_moe, arithmetic_sparse
from benchmark.jobs.decoder_lm import DecoderLM
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.losses import (balance_loss, indexer_loss,
                                    softmax_cross_entropy)
from horovod_tpu.ops.mixed_precision import cast_compute, master_weights

# What LlamaModel's layers compute, under the configuration's own keys.
REQUIRED = {"model_type": "KeyeVL2", "hidden_act": "silu",
            "tie_word_embeddings": False, "attention_bias": False,
            "decoder_sparse_step": 1, "mlp_only_layers": [],
            "use_sliding_window": False, "sliding_window": None}


def build(config: dict, traffic: dict, chips: int):
    return SparseMoELM(config, traffic, chips)


class SparseMoELM(DecoderLM):

    def __init__(self, config: dict, traffic: dict, chips: int):
        differ = {key: config[key] for key, wanted in REQUIRED.items()
                  if config[key] != wanted}
        sparse = config["sa_config"]
        if differ or sparse["indexer_num_kv_heads"] != 1 or (
                config["rope_scaling"]["rope_type"] != "default"):
            raise ValueError(f"this job trains Keye-VL-2.0's decoder layers "
                             f"({REQUIRED}, one index key a token, plain "
                             f"RoPE); the configuration states "
                             f"{differ or (sparse, config['rope_scaling'])}")
        training = config["training"]
        if (training["optimizer"], training["compute_dtype"],
                training["master_dtype"]) != ("adamw", "bfloat16", "float32"):
            raise ValueError(f"this job trains bf16 weights under fp32 "
                             f"master AdamW; asked for {training}")
        assumed = config["assumed"]
        self.config = config
        self.chips = chips
        self.seq = traffic["sequence"]
        self.batch = traffic["batch_per_chip"] * chips
        self.sample_rows = traffic["sample_per_chip"] * chips
        self.units_per_step = self.batch * self.seq
        self.alpha = assumed["aux_loss_alpha"]
        self.index_lambda = assumed["index_loss_lambda"]
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
            num_experts=config["num_experts"],
            experts_per_token=config["num_experts_per_tok"],
            held_experts=config["num_local_experts"],
            first_held_expert=config["deployment"]["first_held_expert"],
            moe_intermediate_size=config["moe_intermediate_size"],
            shared_experts=0, norm_topk_prob=config["norm_topk_prob"],
            balance_over="batch", attention_kind="sparse",
            attention_head_dim=config["head_dim"],
            qk_norm=assumed["qk_norm"],
            index_heads=sparse["indexer_num_heads"],
            index_head_dim=sparse["indexer_head_dim"],
            index_topk=sparse["topk"],
            remat=training.get("remat", "none"))
        self.model = LlamaModel(self.llama, attention_fn=flash_attention_fn)
        rate = optax.linear_schedule(0.0, training["learning_rate"],
                                     training["warmup_steps"])
        self.optimizer = hvd.DistributedOptimizer(
            master_weights(optax.adamw(rate)))

    # -- what the harness jits ------------------------------------------

    def init_state(self, key):
        """As ``DecoderLM``, with an embedding of unit variance (the
        configuration's ``assumed.initialisation`` says why: flax's default
        is 1 / hidden, and a router that reads such states is lopsided by
        the seed)."""
        params = LlamaModel(self.llama).init(key, jnp.zeros((1, 8),
                                                            jnp.int32))
        table = params["params"]["tok_emb"]
        table["embedding"] = table["embedding"] * self.llama.hidden_size ** 0.5
        params = cast_compute(params)
        return params, self.optimizer.init(params)

    def loss_fn(self, params, batch):
        logits, sown = self.model.apply(
            params, batch[:, :-1], mutable=["losses", "index_losses"])
        return (softmax_cross_entropy(logits, batch[:, 1:])
                + self.alpha * balance_loss(sown)
                + self.index_lambda * indexer_loss(sown))

    def counters(self, params, batch):
        """What the layers count of themselves on ``batch``: keys taken a
        query ``[layers, B, S]``, the selections ``[layers, B, S, S]``, rows
        gathered per held expert ``[layers, held]`` and rows dropped
        ``[layers]``."""
        _, sown = self.model.apply(params, batch[:, :-1],
                                   mutable=["sparse_stats", "moe_stats"])
        layers = range(self.llama.num_layers)

        def stack(collection, module, name):
            return jnp.stack([sown[collection][f"layer_{i}"][module][name][0]
                              for i in layers])

        return (stack("sparse_stats", "attn", "keys_taken"),
                stack("sparse_stats", "attn", "selected"),
                stack("moe_stats", "moe", "rows_per_expert"),
                stack("moe_stats", "moe", "rows_dropped"))

    # -- facts for the metric readers (benchmark/arithmetic_sparse.py) ----

    def _shape(self) -> dict:
        c = self.llama
        return dict(batch=self.batch // self.chips, seq=self.seq,
                    heads=c.num_heads, kv_heads=c.num_kv_heads,
                    head_dim=c.head_dim, index_heads=c.index_heads,
                    index_dim=c.index_head_dim, topk=c.index_topk)

    def flops_per_unit(self) -> float:
        c = self.llama
        shape = self._shape()
        del shape["batch"]
        return arithmetic_sparse.sparse_moe_train_flops_per_token(
            **shape, hidden=c.hidden_size, layers=c.num_layers,
            expert_ffn=c.moe_intermediate_size, experts=c.num_experts,
            held=c.experts_held, per_token=c.experts_per_token,
            vocab=c.vocab_size)

    def kernel_work_per_step(self) -> dict:
        """A chip's Mosaic calls in one step, at what the algorithm needs:
        the flash kernel's passes over the kept pairs of every layer, the
        selection and the indexer's loss, and the routed layers' grouped
        products at the rows their held experts expect."""
        c = self.llama
        shape = self._shape()

        def work(flops, nbytes):
            return {"flops": c.num_layers * flops(**shape),
                    "bytes": c.num_layers * nbytes(**shape)}

        forward = work(arithmetic_sparse.flash_forward_flops,
                       arithmetic_sparse.flash_forward_bytes)
        backward = work(arithmetic_sparse.flash_backward_flops,
                        arithmetic_sparse.flash_backward_bytes)
        rows = arithmetic_moe.expert_rows(
            tokens=self.units_per_step // self.chips,
            per_token=c.experts_per_token, held=c.experts_held,
            experts=c.num_experts)
        return {
            "flash": {"flops": forward["flops"] + backward["flops"],
                      "bytes": forward["bytes"] + backward["bytes"],
                      "forward": forward, "backward": backward},
            "index_select": work(arithmetic_sparse.select_flops,
                                 arithmetic_sparse.select_bytes),
            "index_loss": work(arithmetic_sparse.index_loss_flops,
                               arithmetic_sparse.index_loss_bytes),
            "moe_experts": {
                "flops": c.num_layers * arithmetic_moe.expert_products_flops(
                    rows=rows, hidden=c.hidden_size,
                    expert_ffn=c.moe_intermediate_size),
                "bytes": c.num_layers * arithmetic_moe.expert_products_bytes(
                    rows=rows, held=c.experts_held, hidden=c.hidden_size,
                    expert_ffn=c.moe_intermediate_size)}}

    # -- checks ---------------------------------------------------------

    def expected_first_loss(self) -> float:
        # ln V + 1/2 as DecoderLM; alpha times a balance loss of about 1;
        # lambda times the indexer's loss at initialisation, which the
        # configuration's file states with how it was found.
        return (super().expected_first_loss() + self.alpha
                + self.index_lambda
                * self.config["checks"]["first_index_loss"])

    def to_reference(self, tree):
        p = tree["params"]
        width = self.llama.moe_intermediate_size
        layers = []
        for i in range(self.llama.num_layers):
            layer = p[f"layer_{i}"]
            attn, moe = layer["attn"], layer["moe"]
            layers.append({
                "norm_attn": layer["norm_attn"]["scale"],
                **{name: attn[name]["kernel"] for name in (
                    "wq", "wk", "wv", "wo", "index_wq", "index_wk",
                    "index_ww")},
                "q_norm": attn["q_norm"]["scale"],
                "k_norm": attn["k_norm"]["scale"],
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

    argv = argv or sys.argv[1:]
    if len(argv) != 2:
        sys.exit("usage: python3 -m benchmark.jobs.sparse_moe_lm <workload> "
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
        taken, selected, rows, dropped = job.counters(params, sample)
        wanted = jnp.stack(reference.selection(
            job.to_reference(params), sample, cell["config"]))
        both = jnp.sum(wanted & (selected != 0), axis=(1, 2, 3))
        return (jnp.mean(taken.astype(jnp.float32)), jnp.max(taken),
                both / jnp.sum(wanted, axis=(1, 2, 3)), rows, dropped)

    mean, most, agreement, rows, dropped = map(
        np.asarray, jax.jit(counters)(k_state, k_sample))
    device = jax.devices()[0]
    print(f"[sparse_moe_lm] {workload} seed {seed} on {device.platform} "
          f"({device.device_kind}): 1 x {job.seq} tokens; keys taken a "
          f"query: mean {mean:.1f}, max {most} (topk "
          f"{job.llama.index_topk}); share of the reference's selected "
          f"keys that the program selected, by layer "
          f"{[round(float(a), 5) for a in agreement]}; rows gathered per "
          f"held expert a layer: mean {rows.mean():.1f}, max {rows.max()}, "
          f"min {rows.min()}; rows dropped {dropped.tolist()}", flush=True)
    floor = cell["config"]["checks"]["selection_agreement_at_least"]
    if (agreement.min() < floor or most > job.llama.index_topk
            or dropped.any()):
        sys.exit(f"[sparse_moe_lm] outside the configuration's checks: "
                 f"agreement under {floor}, a query with more than "
                 f"{job.llama.index_topk} keys, or a dropped row")


if __name__ == "__main__":
    main()
