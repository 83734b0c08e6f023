"""Arithmetic of a looped decoder (``total_ut_steps`` passes over one
weight-shared stack, an exit after every pass), from shapes alone and built
on ``benchmark/arithmetic.py``'s counts.

The counting rule, which ``benchmark/jobs/looped_lm.py`` applies: what the
algorithm needs is ``passes`` times the plain decoder's count, whose one
pass is a whole walk over the stack and ends in one whole head (every
exit's logits go into the loss) -- forward once and backward twice, as
there.  What the program repeats to save memory (each layer's forward
again in the backward pass, each exit's head again) is not counted, so
``mfu`` cannot be raised by recomputing more; the gate's H multiply-adds a
token and exit are left out (1/49152 of a head).  The same holds for the
flash kernel's work: ``passes x layers`` layer applications, forward and
backward once each.
"""

from __future__ import annotations

from benchmark import arithmetic


def stack_share_of_matmul_work(*, hidden: int, layers: int, heads: int,
                               kv_heads: int, head_dim: int, ffn: int,
                               vocab: int, seq: int) -> float:
    """Share of a token's multiply-adds that the stack does (the rest is
    the head), attention counted causal: the same for any number of
    passes, each of which ends in an exit.  What a looped cell is sized
    by: the loop has to be most of the work."""
    stack = layers * (
        arithmetic.decoder_layer_matmul_params(hidden, heads, kv_heads,
                                               head_dim, ffn)
        + 2 * heads * head_dim * arithmetic.causal_pairs(seq) / seq)
    return stack / (stack + hidden * vocab)
