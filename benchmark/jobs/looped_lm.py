"""Causal-LM training of a looped decoder (Ouro / LoopLM) through the
program's main path: ``DecoderLM``'s job with ``total_ut_steps`` passes
over the weight-shared stack, the exit gate and
``ops.losses.expected_exit_loss``, each layer recomputed in the backward
pass as the configuration's file says (``training.remat``).
"""

from __future__ import annotations

import dataclasses
import math

from benchmark.jobs.decoder_lm import DecoderLM
from horovod_tpu.models import LlamaModel
from horovod_tpu.ops.flash_attention import flash_attention_fn
from horovod_tpu.ops.losses import expected_exit_loss


def build(config: dict, traffic: dict, chips: int):
    return LoopedLM(config, traffic, chips)


def exit_entropy(passes: int) -> float:
    """H(p) of a zero gate: p = 1/2, 1/4, ..., and the last pass takes what
    is left, 2^-(T-1)."""
    p = [2.0 ** -(t + 1) for t in range(passes - 1)] + [2.0 ** -(passes - 1)]
    return -sum(x * math.log(x) for x in p)


class LoopedLM(DecoderLM):

    def __init__(self, config: dict, traffic: dict, chips: int):
        self.passes = config["total_ut_steps"]
        if self.passes < 2:
            raise ValueError("this job trains the looped model; one pass "
                             "over the stack is benchmark/jobs/decoder_lm")
        # The parent builds the one-pass model of the same sizes.
        super().__init__({**config, "total_ut_steps": 1}, traffic, chips)
        self.config = config
        self.beta = config["assumed"]["exit_entropy_beta"]
        self.llama = dataclasses.replace(
            self.llama, total_ut_steps=self.passes,
            remat=config["training"]["remat"])
        self.model = LlamaModel(self.llama, attention_fn=flash_attention_fn)

    def loss_fn(self, params, batch):
        hidden, gate_logits = self.model.apply(params, batch[:, :-1])
        return expected_exit_loss(
            lambda h: self.model.apply(params, h, method="head"),
            hidden, gate_logits, batch[:, 1:], beta=self.beta)

    # -- facts for the metric readers (benchmark/arithmetic_loop.py) ------

    def flops_per_unit(self) -> float:
        """``passes`` x (the stack + one head); nothing recomputed."""
        return self.passes * super().flops_per_unit()

    def kernel_work_per_step(self) -> dict:
        """``passes x layers`` applications of flash, each pass of the
        kernel's and their sum alike; nothing recomputed."""
        def scaled(work):
            return {what: scaled(count) if isinstance(count, dict)
                    else self.passes * count
                    for what, count in work.items()}

        return scaled(super().kernel_work_per_step())

    # -- checks ---------------------------------------------------------

    def expected_first_loss(self) -> float:
        # Every exit reads a normalised state through the one head, so each
        # has unit-variance logits at initialisation: sum_t p_t (ln V + 1/2)
        # = ln V + 1/2, less beta H(p) of the zero gate.
        return (super().expected_first_loss()
                - self.beta * exit_entropy(self.passes))

    def to_reference(self, tree):
        return {**super().to_reference(tree),
                "exit_gate": dict(tree["params"]["exit_gate"])}
