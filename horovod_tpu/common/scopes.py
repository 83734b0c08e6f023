"""The names the jit path gives its own work in an XLA trace.

Every ``jax.named_scope`` of ``horovod_tpu`` takes its name from this table,
and so do the documents and the benchmark's reader
(``benchmark/scopes.py``): a name is spelled here and nowhere else.  A scope
is compile-time metadata (it becomes part of each HLO operation's
``op_name``, which the profiler shows for every device operation), so it
costs nothing when the step runs.  Plain Python; JAX is imported by
``scope()`` alone, when it is called.

There is ONE way into a scope: ``with scope(NAME):``.  It enters
``jax.named_scope(NAME)`` and the compile log's span of the same name
(``common/compile_cache.py``), so the Python that JAX runs while it TRACES a
step is timed under the words that tell the step's device time apart:
``hvd.compile_spans(hvd.TRAIN_STEP_PROGRAM)`` is the tree under "tracing
``hvd_train_step`` took 12.8 s", as a trace's ``op_name``s are the tree
under "a step took 600 ms".  No ``jax.named_scope(`` stands anywhere else
in the package.  A span costs two clock reads and an append, at trace time;
a step that runs from its compiled program enters none.  While it is open a
span also holds a ``jax.profiler.TraceAnnotation`` of its name, so a profile
taken across a recompile shows these names on the host thread's line, on the
clock of the device's operations (``docs/timeline.md``).  The ``MOSAIC_*``
names (``mosaic.<kernel>``) are spans ALONE, around each Mosaic call's bind
(``pl.pallas_call(...)(...)``, which traces the kernel's body to a jaxpr,
once a call site): no ``named_scope``, nothing of them reaches the HLO.
``INIT*`` and ``IMPORT*`` name the start-up's spans (``hvd.init()`` and its
parts; the package's import from two stamps of the clock).

Two more families are spans ALONE, and name what JAX does ON BEHALF of a
piece of the program while it traces a step (the seconds its own
interpreters run between the program's scopes, which no scope can hold):

==========================  ================================================
span                        what falls under it
==========================  ================================================
``layer.<mixer>.<ffn>``     ONE call of a layer by ``LlamaModel`` (plain,
(``LAYER``, by               under ``nn.remat``, inside a looped model's
``layer_span``)              pass), named from its ``LayerSpec``
                            (``models/llama.py``): the mixer as ``MIXERS``
                            spells it, ``+window`` behind it where the
                            layer has a window, the feed-forward ``dense``
                            or ``routed``, ``none`` for the one a
                            one-sublayer layer lacks (``layer.attention.
                            dense``, ``layer.attention+window.routed``,
                            ``layer.linear_attention.routed``,
                            ``layer.mamba2.none``, ``layer.none.routed``);
                            ``layer.resnet.stage<n>`` a stage of
                            ``models/resnet.py``.  The block scopes nest
                            inside; its SELF time is what JAX did for that
                            layer outside the program's Python:
                            ``jax.checkpoint`` tracing and staging it, the
                            JVP and the partial evaluation of its jaxpr
``rule.<op>.fwd``,          a ``custom_vjp``'s forward and backward rule
``rule.<op>.bwd``           whenever JAX calls it (``rules``, handed to
(``RULE``, by ``rules``)     every ``defvjp`` of the package; ``RULES`` is
                            the registry).  ``<op>`` is the function's name
                            (``_flash``, ``rotate_pairs``, ``_live_buffers``,
                            ..; ``grouped_matmul._mosaic`` and
                            ``selective_scan._mosaic`` by their modules).
                            The scopes a rule enters nest inside; its SELF
                            time is the rule's own Python and what JAX ran
                            because the rule asked (``jax.vjp`` of a routed
                            layer's buffer and its pullback's call inside
                            ``rule._live_buffers.bwd``).  A forward rule
                            runs inside its layer's span (the JVP of what
                            ``jax.checkpoint`` staged); a backward rule at
                            the top of ``hvd.loss``, or inside the backward
                            rule that called JAX back for it
==========================  ================================================

``hvd.loss`` carries one FLAG, ``forward_seconds`` (``FORWARD_SECONDS``, by
``stamp``): the seconds from its start to the moment ``make_train_step`` had
the loss's ``jax.vjp`` and was about to call the pullback.  What began
before it is the forward half (the model's Python, staging, linearisation);
what began behind it the backward half (transposition, the backward rules).
No span brackets a half, so ``hvd.loss``'s self time keeps its meaning:
under ``hvd.loss`` and under no other span.

An operation's ``op_name`` is a path, ``jit(hvd_train_step)/.../hvd.loss/
.../mul``.  JAX wraps the components that differentiation goes through:
what the forward pass runs sits under ``jvp(...)``, what the backward pass
runs under ``transpose(jvp(...))``.  So one scope around the loss and its
gradient splits forward from backward.

====================  ====================================================
scope                 what falls under it
====================  ====================================================
``hvd.loss``          ``loss_fn`` and its gradient in ``make_train_step``:
                      ``jax.vjp`` and the pullback on one, the pair that
                      ``jax.value_and_grad`` is (flag ``forward_seconds``)
``hvd.fusion.pack``   nothing: no code of the program enters these two
``hvd.fusion.unpack`` since PR 30 (see below)
``hvd.allreduce.<a>`` every traced all-reduce over mesh axes ``<a>``
                      (``hvd.allreduce.data``; several axes joined by ``+``)
``hvd.aux_allreduce`` the ``has_aux`` state's per-leaf all-reduces
``hvd.optimizer``     the wrapped optax transformation's ``update``; with
                      ``hvd.apply`` the whole update of each large weight
                      matrix, which ``DistributedOptimizer.update`` keeps
                      out of its gradient's matmul (a barrier a leaf)
``hvd.apply``         ``optax.apply_updates``
``hvd.flash.fwd``     the flash kernel's forward Mosaic call
``hvd.flash.bwd``     its backward Mosaic call (dq, dk and dv from one call)
``hvd.rope``          the rotary rotation's Mosaic call
                      (``ops/rope.py::rotate_pairs``: q and k forward, their
                      cotangents backward, each one lane-local pass over
                      ``[B, S, H * D]``; with a per-head QK-norm
                      ``norm_rotate_pairs``, the same pass with the norm in
                      it, and backward the norm's transpose too).  Inside
                      ``hvd.block.attn``, beside the flash calls and never
                      under ``hvd.flash.*``
``hvd.attn.qknorm``   the QK-norm and the rotation of q and k
                      (``models/llama.py::LlamaAttention`` where the config
                      has ``qk_norm``): the ``RMSNorm`` module's operations
                      and the rotation behind them, or the one pass that
                      does both, so ``hvd.rope`` nests inside it; forward,
                      run again under recomputation and backward, the
                      scales' gradients among it.  Inside ``hvd.block.attn``
                      (and inside ``hvd.attn.window`` in a sliding layer); a
                      model without a QK-norm does not enter it, and its
                      rotation stays ``hvd.block.attn/../hvd.rope``
``hvd.attn.window``   a sliding-window layer's attention
                      (``models/llama.py::LlamaAttention`` where
                      ``LlamaConfig.window_of`` gives the layer a window):
                      the rotation of its q and k and the ``attention_fn``
                      call over the band, so ``hvd.rope`` and the two
                      ``hvd.flash.*`` calls nest inside it, forward, run
                      again under recomputation and backward.  Inside
                      ``hvd.block.attn``; a full-attention layer does not
                      enter it
``hvd.attn.gate``     the per-head output gate (``gating="per-head"``): the
                      ``[hidden, heads]`` projection, the sigmoid, the
                      gate's way to its head's lanes and the multiply on
                      the attention's output, and their gradients.  Inside
                      ``hvd.block.attn``, in full and sliding layers alike.
                      The element-wise gate (``gating="elementwise"``) too:
                      x times the gate's half of ``wq``, the sigmoid and
                      the multiply, lane by lane
``hvd.loop.pass``     a looped model's passes over its layer stack
                      (``LlamaModel`` with ``total_ut_steps`` > 1): the
                      scan whole, so the walks over the layers and the
                      scan's own work (stacking what the backward pass
                      needs, adding up a weight's gradient over passes)
``hvd.loop.exit``     what ends a pass, inside ``hvd.loop.pass``: the final
                      norm and the exit gate; and in
                      ``expected_exit_loss`` each exit's head, its
                      cross-entropy and both of the head's gradient
                      products, and the exit distribution.  A reader
                      asks for this scope first
``hvd.mla.latent``    latent attention's way from x to the kernel's k and
                      v (``models/llama.py::LatentAttention``): the joint
                      down-projection, the latent's norm, the
                      up-projection, the rotation of the one shared rotary
                      key, its broadcast over the heads and the
                      concatenation -- what a plain attention layer does
                      not have; and, with a query latent (``q_lora_rank``),
                      the query's down-projection, its norm and its
                      up-projection in place of ``wq`` (the rotation of
                      q's rotary part stays outside, as without one)
``hvd.hc.map``        a hyper-connected sublayer's three maps
                      (``models/llama.py::HyperConnection``, a stack with
                      ``hc_mult`` > 1): the RMS over a token's ``hc_mult``
                      streams, the ``[hc_mult * hidden, hc_mult * (hc_mult
                      + 2)]`` product, gains and biases, the two sigmoids
                      and the Sinkhorn steps on ``exp`` of the clamped
                      residual logits -- tokens on the lanes, float32 --
                      forward, run again under recomputation and backward.
                      Where the streams' Mosaic calls run
                      (``ops/hyper_connection.py``) the RMS's sum of
                      squares and the product are one call over rows of X
                      (F1); their transposes are in the call that writes
                      X's cotangent, under ``hvd.hc.mix``.  Inside
                      ``hvd.block.attn`` or ``hvd.block.ffn``, the
                      sublayer's own block
``hvd.hc.mix``        the same sublayer's two mixes of the streams
                      (``models/llama.py::_hc_read``, ``_hc_write``): the
                      read ``h_pre X`` ahead of the sublayer's norm and the
                      write ``H_res X + h_post^T y`` behind the sublayer,
                      and their gradients (the streams', the sublayer
                      output's and the three maps').  As Mosaic calls:
                      the read, the write, the write's transpose, the
                      read's on ``h_pre``, and the one call that writes X's
                      whole cotangent (the read's, the maps' product's and
                      the RMS's parts added to the write's).  Beside
                      ``hvd.hc.map`` in the block's scope; a stack with
                      ``hc_mult`` 1 enters neither
``hvd.moe.route``     the routed layer (``RoutedExperts``) before its
                      products: router, softmax, top-k, the balance loss,
                      the sort by expert and the gather of the held
                      experts' rows
``hvd.moe.experts``   the grouped gate-up and down products over the held
                      experts, the SiLU gate between them, and their
                      gradient products (see below)
``hvd.moe.combine``   the rows back in token order, weighted with their
                      gates and added up over a token's choices
``hvd.moe.shared``    the shared experts' SwiGLU, which every chip computes,
                      and its gate where it has one (``shared_expert_gate``:
                      the ``[hidden, 1]`` projection, the sigmoid and the
                      multiply)
``hvd.sparse.index``  learned sparse attention's indexer
                      (``models/llama.py::SparseAttention``,
                      ``ops/sparse_index.py``): its three projections and
                      the rotation of its queries and its key, and the
                      Mosaic call that forms the indexer's loss and the
                      gradients of its queries, key and weights in one
                      walk over the block pairs (index scores again, the
                      attention heads' second q k^T for the target)
``hvd.sparse.select`` the Mosaic call that forms a query block's index
                      scores in VMEM, finds each query's ``topk``-th
                      largest exactly and writes the selection as int8:
                      the forward scores AND the top-k (they share the
                      VMEM block, so they cannot be told apart)
``hvd.gdn.conv``      a gated delta-rule layer's way from its projections
                      to the rule's q, k and v
                      (``models/llama.py::GatedDeltaNet``): the three
                      causal depthwise convolutions, their SiLU, the L2
                      norms of q and k and q's scale: the ``jnp`` body, or
                      ``ops/short_conv.py``'s Mosaic calls (forward, again
                      under recomputation, backward) where the model's
                      ``attention_fn`` reads its operands in place
``hvd.gdn.heads``     the same layer's key heads made as many as its value
                      heads, where it has fewer (``linear_num_key_heads``
                      < ``linear_num_value_heads``): q and k copied so
                      that value head j reads key head ``j // (value /
                      key)``, and the sum over each key head's copies
                      that is the gradient; between ``hvd.gdn.conv`` and
                      ``hvd.gdn.scan``, in neither.  A layer with as many
                      of each does not enter it
``hvd.gdn.gates``     the same layer's gates: the two narrow projections,
                      log alpha and beta in float32; and behind the rule
                      the per-head RMSNorm of its output and the SiLU gate
                      on it
``hvd.gdn.scan``      the chunkwise rule itself
                      (``ops/gated_delta.py::gated_delta_rule``): cutting
                      the sequence into chunks, the products and the
                      triangular solve of every chunk, the walk over the
                      chunks that carries the state, forward, run again
                      under recomputation and backward; Mosaic calls
                      (``walk_rows``' where a head is whole lane tiles,
                      PR 64) and XLA operations alike
``hvd.gdn.solve``     INSIDE ``hvd.gdn.scan``: the inverse of every chunk's
                      unit lower-triangular system
                      (``ops/gated_delta.py::_tril_inverse``: the Mosaic
                      call and the matrices' way to its lanes and back, or
                      the ``jnp`` body's products) and its transpose's two
                      products; a reader that knows ``hvd.gdn.scan`` alone
                      walks outward to it and counts the solve there
``hvd.kda.conv``      a Kimi Delta Attention layer's way from its projections
                      to the rule's q, k and v
                      (``models/llama.py::KimiDeltaAttention``): the three
                      causal depthwise filters, their SiLU, the L2 norms of
                      q and k and q's scale, by ``ops/short_conv.py`` as
                      ``hvd.gdn.conv``'s
``hvd.kda.gates``     the same layer's gates: the low-rank projection to a
                      log-decay a key CHANNEL (two products through
                      ``head_dim`` lanes), its softplus and ``A_log``, beta,
                      all in float32; and behind the rule the per-head
                      RMSNorm of its output under the SIGMOID of the second
                      low-rank projection (``ops/gated_norm.py``)
``hvd.kda.scan``      the chunkwise rule with a decay a channel
                      (``ops/kda.py::kda_rule``): the chunks' systems A and
                      P by halves (six products a chunk), the solve
                      (``hvd.gdn.solve`` nests inside, the scalar rule's),
                      the walk that carries the state; forward, run again
                      under recomputation and backward; Mosaic calls and
                      XLA operations alike
``hvd.ssd.conv``      a Mamba-2 layer's (``models/llama.py::Mamba2``) causal
                      depthwise convolution over its x, B and C channels,
                      the filter's bias and the SiLU:
                      ``ops/short_conv.py``'s Mosaic calls where the
                      model's ``attention_fn`` reads its operands in place,
                      else its ``jnp`` body; forward, recomputed and backward
``hvd.ssd.gates``     the same layer's elementwise work around the scan:
                      ``softplus(dt + dt_bias)``; behind the scan the skip
                      ``D u``, the gate ``silu(z)`` and the grouped RMSNorm
                      BEHIND the gate (``ops/gated_norm.py``: its Mosaic
                      calls where the model's ``attention_fn`` reads its
                      operands in place, else its ``jnp`` body)
``hvd.ssd.scan``      the chunked state-space recurrence itself
                      (``ops/ssd.py::ssd_scan_rows``): where the model's
                      ``attention_fn`` reads its operands in place, ONE
                      Mosaic call forward and one backward that walk the
                      chunks with the state in VMEM (``scan_rows``), and
                      beside them XLA's few operations on ``[B, S, H]``
                      arrays (the log-decays' sums, their pieces) and the
                      joining of the cotangent's channels; else the ``jnp``
                      body: the log-decays, cutting into chunks, every
                      chunk's products, the walk as a ``while``; forward,
                      run again under recomputation and backward
``hvd.ssd.proj``      the same layer's two projections: ``in_proj [hidden,
                      2 H P + 2 G N + H]`` to z, x, B, C and dt, and
                      ``out_proj``; and their gradient products (a name
                      alone: nothing else tells them from attention's
                      projections inside ``hvd.block.attn``)
``hvd.sscan.conv``    a Mamba-1 layer's (``models/llama.py::Mamba1``) causal
                      depthwise convolution over its u channels where
                      ``in_proj`` left them, the filter's bias and the SiLU:
                      ``ops/short_conv.py``'s Mosaic calls or its ``jnp``
                      body, as ``hvd.ssd.conv``
``hvd.sscan.gates``   the same layer's way from u to the scan's step, B and
                      C: the ``[inner, rank + 2 N]`` projection, the rank's
                      ``[rank, inner]`` projection, its bias and the
                      softplus in float32; and behind the scan the gate
                      ``y silu(z)``
``hvd.sscan.scan``    the selective scan itself
                      (``ops/selective_scan.py::selective_scan``): the
                      Mosaic pair or the ``jnp`` body's chunks, forward, run
                      again under recomputation and backward, and the sums
                      over the backward call's partial gradients
``hvd.lconv.proj``    a double-gated short-convolution layer's
                      (``models/llama.py::GatedShortConv``) two projections:
                      ``in_proj [hidden, 3 hidden]`` to the gates B, C and
                      the filter's input z, and ``out_proj``; and their
                      gradient products
``hvd.lconv.conv``    the same layer's gate, filter and gate, ``C (taps * (B
                      z))``: ``ops/short_conv.py``'s gated Mosaic calls where
                      the model's ``attention_fn`` reads its operands in
                      place, else its ``jnp`` body; forward, recomputed and
                      backward, Mosaic calls and XLA operations alike
``hvd.gmu``           a gated memory unit whole (``models/llama.py::
                      GatedMemory``): ``x W_1``, the gate on the shared
                      memory ``m silu(x W_1)``, ``W_2``, and their
                      gradients.  The whole unit, because XLA fuses the gate
                      product into ``W_2``'s matmul and a fusion has one
                      ``op_name`` (a scope around the product alone read
                      nothing on the v5e, PR 54)
``hvd.attn.diff``     differential attention's own work behind its two
                      ``attention_fn`` calls
                      (``models/llama.py::DifferentialAttention``): lambda
                      from its four vectors, ``a1 - lambda a2``, the RMSNorm
                      over a head pair's value lanes and the factor ``1 -
                      lambda_init``; the calls themselves are
                      ``hvd.flash.*`` (inside ``hvd.attn.window`` in a
                      sliding layer), beside it
``hvd.block.attn``    a layer's mixer block whole
                      (``models/llama.py::LlamaLayer``): ``norm_attn``,
                      the mixer (``LlamaAttention``, ``LatentAttention``,
                      ``SparseAttention``, ``DifferentialAttention``,
                      ``GatedDeltaNet``, ``KimiDeltaAttention``, ``Mamba2``,
                      ``Mamba1``,
                      ``GatedShortConv`` or ``GatedMemory``:
                      projections, QK-norm, rotation, the ``attention_fn``
                      call or the rule, ``wo``) and the residual add.
                      ``hvd.flash.*``, ``hvd.rope``, ``hvd.attn.*``,
                      ``hvd.mla.latent``, ``hvd.sparse.*``, ``hvd.gdn.*``,
                      ``hvd.kda.*``, ``hvd.ssd.*``, ``hvd.sscan.*``, ``hvd.lconv.*``,
                      ``hvd.gmu`` and ``hvd.hc.*`` nest inside it.  In a
                      stack whose layers are ONE sublayer
                      (``LlamaConfig.hybrid_override_pattern``) a mixer
                      layer is this block alone, with the layer's one norm
``hvd.block.ffn``     a layer's feed-forward block whole: ``norm_mlp``,
                      ``SwiGLU`` or ``RoutedExperts`` (``hvd.moe.*`` and
                      ``hvd.hc.*`` nest inside it) and the residual add; in
                      a stack of one-sublayer layers a routed layer is this
                      block alone
``hvd.head``          what turns the stack's output into a loss: the final
                      norm (with a looped model's exit gate, nested in
                      ``hvd.loop.exit``), ``LlamaModel.head``'s product,
                      the cross-entropy on its logits
                      (``ops/losses.py``: ``softmax_cross_entropy``, and
                      each exit's inside ``expected_exit_loss``) and
                      their gradient products (see below)
====================  ====================================================

The attention over the selected keys itself runs in the flash kernel's two
calls, under ``hvd.flash.fwd`` and ``hvd.flash.bwd``.

XLA:TPU executes ``jax.lax.ragged_dot`` -- forward, and both gradient
products -- as Mosaic calls of its own, and names each by what it made, not
by where it came from: the call's ``op_name`` is ``ragged-dot-none`` (a
small ``ragged-dot-metadata`` call beside it turns the group sizes into
tiles), with no scope of the program's in it (read from a step compiled for
a described v5e, PR 32).  ``RAGGED_DOT_PREFIX`` is that prefix: a reader of
``hvd.moe.experts`` counts operations so named as the scope's, since the
routed layer is the program's only user of ``ragged_dot``.  Where
``ops/grouped_matmul.py`` takes its Mosaic body in its place, the calls are
the program's own (``gmm``, ``tgmm``) and carry ``hvd.moe.experts`` like any
other operation.

``FUSION_PACK`` and ``FUSION_UNPACK`` named the copies of a trace-time
gradient packer that is gone.  The constants stay because the benchmark's
reader (``benchmark/scopes.py``) looks them up; they go when a ``benchmark``
PR retires ``fusion_pack_ms`` (ROADMAP.md D15), which still counts what an
averaging all-reduce adds that is no collective, under ``hvd.allreduce.<a>``.

The three block scopes are for the ordinary work, which flax's module names
(``layer_3/attn/wq``, ``mlp/w_gate_up``, ``lm_head``) would otherwise leave
for a reader to spell.  The norm and the residual add are INSIDE a block's
scope on purpose: XLA fuses ``wo`` and ``w_down`` with the residual add and
the next norm, and a fusion has one ``op_name``, so nothing of a layer may
lie between the scopes.  With them inside, every operation under
``hvd.loss`` that is no collective lies in exactly one of the three, or is
the embedding's lookup and its scatter-add, the rotary tables, a looped
model's exit distribution and its scan's own work, or what XLA hoists.
(Read from v5e traces, PR 36: XLA:TPU names a matmul fusion by its
``dot_general``, so every product counts in its own block, and what a norm's
scope holds is the elementwise remainder.)  ``hvd.head`` is entered once on any path (the norm, the head's
product and the loss each enter it side by side; the head's two gradient
products in ``expected_exit_loss`` carry it from the forward they transpose),
never ``hvd.head/hvd.head``.  ``benchmark/dense_scopes.py`` reads the three.

The benchmark (``benchmark/scopes.py``) reads ``hvd.flash.fwd`` by name and
every other ``hvd.flash.*`` scope as the backward pass, however many calls
make that pass and whatever this table names them.

Recomputed work needs no scope of the program's: JAX names it.  What
``jax.checkpoint`` / ``nn.remat`` runs again in the backward pass carries
the component ``rematted_computation`` (``REMATTED``; JAX 0.9.0, read from
the compiled step's ``op_name``s and from a v5e trace, PR 26), and the
checkpointed function's own name stack a second time.  The passes are a
``scan``, so with ``P`` for ``hvd.loop.pass/while/body/closed_call/
LlamaModel.pass_and_exit`` a layer's first forward is ``hvd.loss/
jvp(LlamaModel)/P/layer_0/...``; its repeated forward ``hvd.loss/
transpose(jvp(LlamaModel))/P/LlamaModel.pass_and_exit/checkpoint/
rematted_computation/layer_0/...``; the backward work around it the same
path without that component.  Under ``hvd.loop.exit`` only the norm and
the gate that end a pass are recomputed.  An exit's head is not: its
forward product is ``hvd.loss/jvp(hvd.loop.exit)/while/body/closed_call/
jvp(LlamaModel.head)/lm_head/dot_general``, and its two gradient products
run in that same loop body as ``.../closed_call/transpose(jvp(
LlamaModel.head))/lm_head/dot_general`` (``expected_exit_loss`` takes the
head's ``jax.vjp`` inside its forward walk), so a reader that tells
backward work by a ``transpose(`` component still counts them there.

``FLASH_OUT_NAME`` and ``FLASH_LSE_NAME`` are no scopes but
``checkpoint_name``s: the flash kernel's output and row statistics, for a
recomputation policy that keeps them (``LlamaConfig.remat``).  So are
``SPARSE_SELECTED_NAME`` (the selection, its log-sum-exp and its counts)
and ``SPARSE_INDEX_LOSS_NAME`` (the indexer's loss and the three gradients
its walk left): kept, the recomputed forward neither scores nor selects nor
walks again.
"""

from __future__ import annotations

import contextlib
import functools

__all__ = [
    "LOSS", "FUSION_PACK", "FUSION_UNPACK", "ALLREDUCE", "AUX_ALLREDUCE",
    "OPTIMIZER", "APPLY", "FLASH_FWD", "FLASH_BWD", "ROPE",
    "ATTN_WINDOW", "ATTN_GATE", "QK_NORM",
    "LOOP_PASS", "LOOP_EXIT", "MLA_LATENT", "HC_MAP", "HC_MIX",
    "MOE_ROUTE", "MOE_EXPERTS",
    "MOE_COMBINE", "MOE_SHARED", "SPARSE_INDEX", "SPARSE_SELECT",
    "GDN_CONV", "GDN_GATES", "GDN_SCAN", "GDN_HEADS", "GDN_SOLVE",
    "KDA_CONV", "KDA_GATES", "KDA_SCAN",
    "SSD_CONV", "SSD_GATES", "SSD_SCAN", "SSD_PROJ",
    "SSCAN_CONV", "SSCAN_GATES", "SSCAN_SCAN", "LCONV_PROJ", "LCONV_CONV",
    "GMU", "ATTN_DIFF",
    "BLOCK_ATTN", "BLOCK_FFN", "HEAD",
    "RAGGED_DOT_PREFIX", "REMATTED", "FLASH_OUT_NAME", "FLASH_LSE_NAME",
    "SPARSE_SELECTED_NAME", "SPARSE_INDEX_LOSS_NAME",
    "TRAIN_STEP_PROGRAM", "allreduce_scope", "scope", "span",
    "MOSAIC", "MOSAIC_FLASH_FWD", "MOSAIC_FLASH_BWD", "MOSAIC_ROPE",
    "MOSAIC_SHORT_CONV", "MOSAIC_GDN_SOLVE", "MOSAIC_SPARSE_SELECT",
    "MOSAIC_INDEX_LOSS", "MOSAIC_PAGED_ATTENTION", "MOSAIC_SSCAN",
    "MOSAIC_GROUPED_MATMUL", "MOSAIC_GATED_NORM", "MOSAIC_SSD_SCAN",
    "MOSAIC_GDN_SCAN", "MOSAIC_HC_STREAMS", "MOSAIC_KDA_SCAN",
    "INIT", "INIT_NATIVE", "INIT_DISTRIBUTED", "INIT_CACHE",
    "IMPORT", "IMPORT_MODELS",
    "LAYER", "RULE", "RULES", "FORWARD_SECONDS", "layer_span", "rules", "stamp",
]

LOSS = "hvd.loss"
FUSION_PACK = "hvd.fusion.pack"
FUSION_UNPACK = "hvd.fusion.unpack"
ALLREDUCE = "hvd.allreduce"          # a prefix: allreduce_scope() completes it
AUX_ALLREDUCE = "hvd.aux_allreduce"
OPTIMIZER = "hvd.optimizer"
APPLY = "hvd.apply"
FLASH_FWD = "hvd.flash.fwd"
FLASH_BWD = "hvd.flash.bwd"
ROPE = "hvd.rope"
ATTN_WINDOW = "hvd.attn.window"
ATTN_GATE = "hvd.attn.gate"
QK_NORM = "hvd.attn.qknorm"
LOOP_PASS = "hvd.loop.pass"
LOOP_EXIT = "hvd.loop.exit"
MLA_LATENT = "hvd.mla.latent"
HC_MAP = "hvd.hc.map"
HC_MIX = "hvd.hc.mix"
MOE_ROUTE = "hvd.moe.route"
MOE_EXPERTS = "hvd.moe.experts"
MOE_COMBINE = "hvd.moe.combine"
MOE_SHARED = "hvd.moe.shared"
SPARSE_INDEX = "hvd.sparse.index"
SPARSE_SELECT = "hvd.sparse.select"
GDN_CONV = "hvd.gdn.conv"
GDN_GATES = "hvd.gdn.gates"
GDN_SCAN = "hvd.gdn.scan"
GDN_HEADS = "hvd.gdn.heads"
GDN_SOLVE = "hvd.gdn.solve"       # inside GDN_SCAN (and KDA_SCAN)
KDA_CONV = "hvd.kda.conv"
KDA_GATES = "hvd.kda.gates"
KDA_SCAN = "hvd.kda.scan"
SSD_CONV = "hvd.ssd.conv"
SSD_GATES = "hvd.ssd.gates"
SSD_SCAN = "hvd.ssd.scan"
SSD_PROJ = "hvd.ssd.proj"
SSCAN_CONV = "hvd.sscan.conv"
SSCAN_GATES = "hvd.sscan.gates"
SSCAN_SCAN = "hvd.sscan.scan"
LCONV_PROJ = "hvd.lconv.proj"
LCONV_CONV = "hvd.lconv.conv"
GMU = "hvd.gmu"
ATTN_DIFF = "hvd.attn.diff"
BLOCK_ATTN = "hvd.block.attn"
BLOCK_FFN = "hvd.block.ffn"
HEAD = "hvd.head"
RAGGED_DOT_PREFIX = "ragged-dot"     # XLA:TPU's own name for its calls
REMATTED = "rematted_computation"    # JAX's own component, not a scope
FLASH_OUT_NAME = "hvd.flash.out"
FLASH_LSE_NAME = "hvd.flash.lse"
SPARSE_SELECTED_NAME = "hvd.sparse.selected"
SPARSE_INDEX_LOSS_NAME = "hvd.sparse.index_loss"

# Spans of the compile log alone (no ``named_scope``): a Mosaic call's bind,
# by kernel; ``hvd.init()`` and its parts; the package's import.
MOSAIC = "mosaic."                   # a prefix: every name below starts so
MOSAIC_FLASH_FWD = MOSAIC + "flash_fwd"
MOSAIC_FLASH_BWD = MOSAIC + "flash_bwd"
MOSAIC_ROPE = MOSAIC + "rope"
MOSAIC_SHORT_CONV = MOSAIC + "short_conv"
MOSAIC_GDN_SOLVE = MOSAIC + "gdn_solve"
MOSAIC_SPARSE_SELECT = MOSAIC + "sparse_select"
MOSAIC_INDEX_LOSS = MOSAIC + "index_loss"
MOSAIC_PAGED_ATTENTION = MOSAIC + "paged_attention"
MOSAIC_SSCAN = MOSAIC + "selective_scan"
MOSAIC_GROUPED_MATMUL = MOSAIC + "grouped_matmul"
MOSAIC_GATED_NORM = MOSAIC + "gated_norm"
MOSAIC_SSD_SCAN = MOSAIC + "ssd_scan"
MOSAIC_GDN_SCAN = MOSAIC + "gdn_scan"
MOSAIC_HC_STREAMS = MOSAIC + "hc_streams"
MOSAIC_KDA_SCAN = MOSAIC + "kda_scan"
LAYER = "layer."                     # a prefix: layer_span() completes it
RULE = "rule."                       # a prefix: rules() completes it
FORWARD_SECONDS = "forward_seconds"  # a flag of the LOSS span, no span
INIT = "hvd.init"
INIT_NATIVE = "hvd.init.native"      # the C++ engine: found, loaded, started
INIT_DISTRIBUTED = "hvd.init.distributed"   # jax.distributed.initialize
INIT_CACHE = "hvd.init.cache"        # the compile cache and log switched on
IMPORT = "import horovod_tpu.jax"
IMPORT_MODELS = "import horovod_tpu.models"   # inside IMPORT

#: The name JAX reports for the program ``make_train_step`` builds: in its
#: monitoring events (``hvd.compile_log()``: tracing under this name,
#: lowering and backend compilation under ``jit(hvd_train_step)``), as the
#: first component of every operation's ``op_name``, and on the profiler's
#: ``XLA Modules`` line (``jit_hvd_train_step(...)``).
TRAIN_STEP_PROGRAM = "hvd_train_step"


def allreduce_scope(axis_name) -> str:
    """``hvd.allreduce.data``; ``hvd.allreduce.data+fsdp`` for a tuple."""
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    return ALLREDUCE + "." + "+".join(str(a) for a in axes)


def span(name: str):
    """The compile log's span ``name`` ALONE, for the names that must not
    reach the HLO (``MOSAIC_*``, ``INIT*``, ``LAYER``'s and ``RULE``'s)."""
    from horovod_tpu.common import compile_cache

    return compile_cache.span(name)


def stamp(flag: str) -> None:
    """Keep the seconds the innermost open span has run so far as its flag
    ``flag`` (``FORWARD_SECONDS`` of ``LOSS``)."""
    from horovod_tpu.common import compile_cache

    compile_cache.stamp(flag)


def layer_span(mixer, ffn=None, window=None):
    """The span around ONE call of a layer: ``layer.<mixer>.<ffn>``, the
    two as the layer's ``LayerSpec`` names them (``models/llama.py``: a key
    of ``MIXERS``, ``DENSE`` or ``ROUTED``), ``+window`` behind the mixer
    of a layer that has a window and ``none`` for a sublayer it lacks;
    ``layer.resnet.stage2`` for a stage of ``models/resnet.py``.  A span
    alone."""
    kind = f"{mixer or 'none'}{'+window' if window else ''}"
    return span(f"{LAYER}{kind}.{ffn or 'none'}")


#: ``{op: (fwd, bwd)}`` of every pair of rules that ``rules`` wrapped: one
#: entry a ``custom_vjp`` of the package (``tests/test_rule_spans.py`` counts
#: the sources' ``defvjp`` calls against it).
RULES: dict = {}


def rules(op: str, fwd, bwd) -> tuple:
    """A ``custom_vjp``'s two differentiation rules, each inside a span of
    its own whenever JAX calls it: ``f.defvjp`` of ``*rules("f", f_fwd,
    f_bwd)`` gives ``rule.f.fwd`` and ``rule.f.bwd``.  Spans alone; what else of the
    program calls ``f_fwd`` or ``f_bwd`` by name enters neither, and
    ``functools.wraps`` keeps each rule's name and signature for JAX."""
    if op in RULES:
        raise ValueError(f"two custom_vjp's rules are both named {op!r}")

    def timed(fn, name):
        @functools.wraps(fn)
        def rule(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)

        return rule

    RULES[op] = (timed(fwd, f"{RULE}{op}.fwd"), timed(bwd, f"{RULE}{op}.bwd"))
    return RULES[op]


@contextlib.contextmanager
def scope(name: str):
    """Enter ``jax.named_scope(name)`` and the compile log's span ``name``:
    the one way into a scope of this table (see the module's docstring)."""
    import jax

    from horovod_tpu.common import compile_cache

    with compile_cache.span(name), jax.named_scope(name):
        yield
