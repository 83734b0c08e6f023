"""Model zoo for the TPU-native framework.

Covers the reference's example workloads (reference ``examples/``:
MNIST convnets ×4, ImageNet ResNet-50 ×2, word2vec, synthetic ResNet
benchmark) plus the transformer families (BERT, Llama) used by the
FSDP-style baseline workloads.  All models are flax.linen modules designed
TPU-first: bfloat16 compute with float32 params, channels-last layouts,
MXU-friendly dimensions.

``LlamaConfig.total_ut_steps`` > 1 makes ``LlamaModel`` a looped LM (Ouro /
LoopLM): the one layer stack applied that many times with shared weights,
an exit gate after every pass, and ``(hidden, gate_logits)`` returned for
``ops.losses.expected_exit_loss``.  It is a training path:
``generation`` (``prefill``, ``decode_step``, ``generate`` and the paged
forms), the serve plane and the pipelined step refuse it, and
``ring_attention`` as ``attention_fn`` and the MoE block are untested with
it.  ``LlamaConfig.remat`` recomputes each layer in the backward pass.

``LlamaConfig.attention_kind = "latent"`` makes every layer's mixer
multi-head latent attention (DeepSeek-V2's MLA: keys ``qk_nope_head_dim +
qk_rope_head_dim`` wide, values ``v_head_dim`` wide, YaRN ``rope_scaling``),
and ``num_experts`` > 1 makes the layers from ``first_dense_layers`` on
routed ones: a router over all experts, ``held_experts`` of them held here
(one chip's share under expert parallelism), shared experts beside them,
rows sorted by expert with none dropped.  ``attention_kind = "sparse"``
makes it learned sparse attention over grouped-query heads (an indexer
picks ``index_topk`` of each query's causal keys, ``ops/sparse_index.py``;
docs/sparse_attention.md).  ``layer_types`` names the mixer a layer:
``"linear_attention"`` is a ``GatedDeltaNet`` (short causal convolutions,
decay and beta gates, the chunkwise gated delta rule of
``ops/gated_delta.py``) among ``"full_attention"`` layers, with OLMo 2's
block (``norm_placement="post"``, ``qk_norm_over="all"``) and softmax
layers that do not rotate (``rope_theta=None``).  ``"sliding_attention"``
layers attend through a window of ``sliding_window`` keys (the flash
kernel's two calls walk the band alone), and a stack may give each layer its
own count of query heads (``num_attention_heads_per_layer``), each layer
type its own rotary table, partial or YaRN-scaled (``rope_parameters``), a
per-head sigmoid gate on the attention's output (``gating="per-head"``) and
the routed gates a scale (``routed_scaling_factor``).  All of these are
training paths too: ``generation``, the serve plane and the pipelined step
refuse them by name.
"""

from horovod_tpu.models.mnist import MnistConvNet, MnistMLP
from horovod_tpu.models.resnet import ResNet, ResNet18, ResNet34, ResNet50, ResNet101
from horovod_tpu.models.word2vec import SkipGramModel, nce_loss
from horovod_tpu.models.bert import BertConfig, BertEncoder, BertForPretraining
from horovod_tpu.models.generation import decode_step, generate, prefill
from horovod_tpu.models.llama import LlamaConfig, LlamaModel

__all__ = [
    "MnistConvNet",
    "MnistMLP",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "SkipGramModel",
    "nce_loss",
    "BertConfig",
    "BertEncoder",
    "BertForPretraining",
    "LlamaConfig",
    "LlamaModel",
    "prefill",
    "decode_step",
    "generate",
]
