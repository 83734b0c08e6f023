"""Causal-LM training through the program's main path.

``LlamaModel`` + ``flash_attention_fn`` + ``master_weights(adamw)`` +
``hvd.DistributedOptimizer``; the harness hands ``loss_fn`` and
``optimizer`` to ``hvd.make_train_step``.  Sizes come from the
configuration's file under their published (Hugging Face) keys.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import optax

import horovod_tpu.jax as hvd
from benchmark import arithmetic
from horovod_tpu.models import LlamaConfig, LlamaModel
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.losses import softmax_cross_entropy
from horovod_tpu.ops.mixed_precision import cast_compute, master_weights


def build(config: dict, traffic: dict, chips: int):
    return DecoderLM(config, traffic, chips)


class DecoderLM:
    unit = "tokens"
    has_aux = False

    def __init__(self, config: dict, traffic: dict, chips: int):
        heads, hidden = config["num_attention_heads"], config["hidden_size"]
        if config["head_dim"] * heads != hidden:
            raise ValueError("LlamaModel derives head_dim as hidden_size / "
                             "num_attention_heads; the configuration "
                             "states another")
        if config["tie_word_embeddings"] or config["hidden_act"] != "silu":
            raise ValueError("LlamaModel has an untied head and a "
                             "SiLU-gated FFN; the configuration asks for "
                             "something else")
        if config.get("total_ut_steps", 1) != 1:
            raise ValueError("LlamaModel makes one pass over the stack")
        training = config["training"]
        if (training["optimizer"], training["compute_dtype"],
                training["master_dtype"]) != ("adamw", "bfloat16", "float32"):
            raise ValueError(f"this job trains bf16 weights under fp32 "
                             f"master AdamW; asked for {training}")
        self.config = config
        self.chips = chips
        self.seq = traffic["sequence"]
        self.batch = traffic["batch_per_chip"] * chips
        self.sample_rows = traffic["sample_per_chip"] * chips
        self.units_per_step = self.batch * self.seq
        self.llama = LlamaConfig(
            vocab_size=config["vocab_size"], hidden_size=hidden,
            num_layers=config["num_hidden_layers"], num_heads=heads,
            num_kv_heads=config["num_key_value_heads"],
            intermediate_size=config["intermediate_size"],
            max_seq_len=config["max_position_embeddings"],
            rope_theta=float(config["rope_theta"]),
            rms_eps=config["rms_norm_eps"])
        self.model = LlamaModel(self.llama, attention_fn=flash_attention_fn)
        self.optimizer = hvd.DistributedOptimizer(
            master_weights(optax.adamw(training["learning_rate"])))

    # -- what the harness jits ------------------------------------------

    def init_state(self, key):
        """(params, opt_state), traced inside the harness's one set-up
        program.  Parameter shapes do not depend on the sequence, so the
        model is initialised on eight positions with dense attention."""
        init_model = LlamaModel(self.llama)
        params = cast_compute(init_model.init(
            key, jnp.zeros((1, 8), jnp.int32)))
        return params, self.optimizer.init(params)

    def make_batch(self, key, rows: int | None = None):
        return jax.random.randint(
            key, (rows or self.batch, self.seq + 1), 0,
            self.llama.vocab_size, jnp.int32)

    def loss_fn(self, params, batch):
        logits = self.model.apply(params, batch[:, :-1])
        return softmax_cross_entropy(logits, batch[:, 1:])

    # -- facts for the metric readers -----------------------------------

    def flops_per_unit(self) -> float:
        c = self.llama
        return arithmetic.decoder_train_flops_per_token(
            hidden=c.hidden_size, layers=c.num_layers, heads=c.num_heads,
            kv_heads=c.num_kv_heads, head_dim=c.head_dim,
            ffn=c.intermediate_size, vocab=c.vocab_size, seq=self.seq)

    def kernel_work_per_step(self) -> dict:
        """Operations and bytes a chip's Mosaic calls need in one step:
        the flash kernel's ``forward`` and ``backward`` pass over every
        layer, and the two together."""
        c = self.llama
        shape = dict(batch=self.batch // self.chips, seq=self.seq,
                     heads=c.num_heads, head_dim=c.head_dim)

        def work(flops, nbytes):
            return {"flops": c.num_layers * flops(**shape),
                    "bytes": c.num_layers * nbytes(**shape)}

        return {"flash": {
            **work(arithmetic.flash_step_flops, arithmetic.flash_step_bytes),
            "forward": work(arithmetic.flash_forward_flops,
                            arithmetic.flash_forward_bytes),
            "backward": work(arithmetic.flash_backward_flops,
                             arithmetic.flash_backward_bytes)}}

    # -- checks ---------------------------------------------------------

    def expected_first_loss(self) -> float:
        # Unit-variance logits at initialisation: E[loss] = ln V + 1/2
        # (PERF.md, finding 5 of PR 21).
        return (math.log(self.llama.vocab_size)
                + self.config["checks"]["first_loss_is_ln_vocab_plus"])

    def to_reference(self, tree):
        """The program's parameter (or gradient) tree in the plain
        reference's layout: a re-arrangement, so gradients map alike."""
        p = tree["params"]
        ffn = self.llama.intermediate_size
        layers = []
        for i in range(self.llama.num_layers):
            layer = p[f"layer_{i}"]
            gate_up = layer["mlp"]["w_gate_up"]["kernel"]
            layers.append({
                "norm_attn": layer["norm_attn"]["scale"],
                "wq": layer["attn"]["wq"]["kernel"],
                "wk": layer["attn"]["wk"]["kernel"],
                "wv": layer["attn"]["wv"]["kernel"],
                "wo": layer["attn"]["wo"]["kernel"],
                "norm_mlp": layer["norm_mlp"]["scale"],
                "w_gate": gate_up[:, :ffn],
                "w_up": gate_up[:, ffn:],
                "w_down": layer["mlp"]["w_down"]["kernel"],
            })
        return {"embed": p["tok_emb"]["embedding"], "layers": layers,
                "norm_f": p["norm_f"]["scale"],
                "lm_head": p["lm_head"]["kernel"]}
