"""Test harness: force an 8-device virtual CPU platform.

Reference parity: the reference tests run under ``mpirun -np 2 pytest``
(.travis.yml:104-111).  The TPU-native equivalent (SURVEY.md §4) is a
multi-device mesh simulated on CPU via
``--xla_force_host_platform_device_count``; the platform is pinned to CPU
before first JAX use so the suite never needs (or takes) a chip.
"""

import contextlib
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Make the repo importable when pytest is run from anywhere.
sys.path.insert(0, REPO)

N_DEVICES = 8


def suite_cache_dir() -> str:
    """Where the suite keeps JAX's persistent compilation cache: under the
    temporary directory (never in the tree, which travels to other machines:
    ``horovod_tpu/common/compile_cache.py``), named by what makes an XLA:CPU
    executable unfit elsewhere (the user, the library, the machine, the
    CPU's features) and by nothing that moves: no pid, no clock, no
    ``tmp_path``.  A directory that moves never hits."""
    import hashlib
    import importlib.metadata
    import platform
    import tempfile

    features = ""
    try:
        with open("/proc/cpuinfo") as cpuinfo:
            features = next((line for line in cpuinfo
                             if line.startswith(("flags", "Features"))), "")
    except OSError:
        pass
    fit = hashlib.sha256(features.encode()).hexdigest()[:12]
    return os.path.join(
        tempfile.gettempdir(),
        f"horovod_tpu_tests_jax_cache-u{os.getuid()}"
        f"-jaxlib{importlib.metadata.version('jaxlib')}"
        f"-{platform.machine()}-{fit}")


# The suite's environment, set before ``import jax`` and in ``os.environ``
# (not by ``jax.config.update``): the multi-process tests' workers
# (``tests/*_worker.py``, the launcher's children) inherit the environment
# and not the config.  A caller's own value of any of them wins.
#
# * Eight virtual devices.
# * XLA:CPU's backend at optimisation level 0, by JAX's own
#   ``jax_disable_most_optimizations`` ("useful if the cost of optimization
#   is greater than that of running a less-optimized program").  Three fifths
#   of a CPU-program test's wall clock was LLVM optimising host code for
#   programs that then run for milliseconds (ROADMAP.md D9); the suite checks
#   what a program computes, and how fast an x86 runs it is nobody's concern.
#   XLA's HLO passes still run, so a compiled program's text is what it was;
#   the deviceless compiles for the v5e are what they were to the byte.  JAX's
#   option and not ``--xla_backend_optimization_level=0`` in ``XLA_FLAGS``,
#   which is read once a process: a step of a tiny model is three times
#   slower unoptimised, and the tests that TIME a window of steps get their
#   optimised code back (``_timed_windows_run_optimised_code`` below).  A
#   caller's ``XLA_FLAGS`` that names the level is left to decide.
# * JAX's persistent compilation cache, on for every program however small,
#   in ``suite_cache_dir()``: one directory for the six workers, their
#   subprocesses and the next run.  JAX's key holds the program, the compile
#   options (the level among them), ``XLA_FLAGS`` and the library's version,
#   so a changed program misses and nothing is invalidated by hand; delete the
#   directory to read a cold run.  Six writers are safe: with no size bound
#   (``jax/_src/lru_cache.py``, ``max_size == -1``) an entry is one file named
#   by its key, written once (``put`` returns where the file exists) and never
#   evicted or rewritten, so two workers that compile one program write the
#   same bytes; a reader that meets a file half written fails to decompress
#   it, and ``jax/_src/compiler.py::_cache_read`` turns that into a warning and
#   a compile, never into another program's executable.  (A size bound,
#   ``JAX_COMPILATION_CACHE_MAX_SIZE``, would take a file lock around every
#   read and write and walk the directory at every write.)
# * XLA's own log lines at FATAL alone.  Every executable read back from the
#   cache logs two ERROR lines of 330 bytes (``cpu_aot_loader.cc``: the LLVM
#   tuning features ``+prefer-no-gather`` and ``+prefer-no-scatter``, which
#   the compile names and the host's list of ISA features does not, on the
#   machine that compiled it), 10 MB a warm run.  They would fill every failed
#   test's captured stderr, and they fill the stderr PIPE of a worker that
#   ``run_workers`` (``tests/test_native_engine.py``) reads only after its
#   peer's: 64 KiB, a hundred hits, and the pair hangs to its timeout
#   (``tests/test_fsdp.py::test_fsdp_jax_bitwise_parity``, PR 55).  What XLA
#   refuses still arrives as the exception's text.  A caller who sets the
#   level lower gets the lines back, and sets
#   ``JAX_ENABLE_COMPILATION_CACHE=0`` beside it.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_backend_optimization_level" not in flags:
    os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={N_DEVICES}"
    ).strip()
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    os.environ["JAX_COMPILATION_CACHE_DIR"] = suite_cache_dir()
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# The tiny sizes at which tests/benchmark runs the jobs of the
# ``deepseek-v2-lite``, ``keye-vl-2.0-30b-a3b``, ``olmo-hybrid-7b``,
# ``laguna-s-2.1``, ``qwen3-next-80b-a3b`` and ``nemotron-3-nano-30b-a3b``
# configurations (and of every one since) on the CPU.  They belong beside
# ``tests/benchmark/tiny_sizes.py``'s, whose table every test of that
# directory reads by the job's name; the files there are the accepted
# benchmark's, which a PR that adds a cell may not edit, so the entry is
# made here, before any of them is imported.
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark"))
from tiny_sizes import TINY  # noqa: E402

TINY.setdefault("moe_lm", {
    # 2 heads, keys 64 + 64 wide and values 64; one dense layer and two
    # routed ones that hold experts 4 to 7 of 16, 3 choices a token.
    "config": {"hidden_size": 64, "num_attention_heads": 2,
               "num_key_value_heads": 2, "qk_nope_head_dim": 64,
               "qk_rope_head_dim": 64, "v_head_dim": 64, "kv_lora_rank": 32,
               "intermediate_size": 128, "moe_intermediate_size": 32,
               "vocab_size": 512, "num_hidden_layers": 3,
               "n_routed_experts": 4, "num_experts_per_tok": 3,
               "deployment": {"n_routed_experts_published": 16,
                              "first_held_expert": 4},
               "checks": {"first_loss_is_ln_vocab_plus": 0.5,
                          "first_loss_tolerance": 0.25,
                          "loss_must_fall": True,
                          "reference": {"parameters": "initial",
                                        "loss_abs": 0.02,
                                        "grad_rel": 0.06}}},
    "traffic": {"sequence": 128, "batch_per_chip": 2},
})

TINY.setdefault("sparse_moe_lm", {
    # 4 query heads over 2 key-value heads of 64 on a state 128 wide (so the
    # heads' width is not hidden / heads); an indexer of 2 heads of
    # 64 that keeps 64 of 256 keys; two layers that hold experts 4 to 7 of
    # 16, 3 choices a token, gates renormalised.
    "config": {"hidden_size": 128, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 64,
               "moe_intermediate_size": 32, "vocab_size": 512,
               "num_hidden_layers": 2, "num_experts": 16,
               "num_local_experts": 4, "num_experts_per_tok": 3,
               "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 2,
                             "indexer_num_kv_heads": 1, "topk": 64},
               "deployment": {"first_held_expert": 4},
               "checks": {"first_loss_is_ln_vocab_plus": 0.5,
                          "first_index_loss": 0.3,
                          "first_loss_tolerance": 0.3,
                          # A dozen steps at the start of a 2000-step
                          # warm-up move a unit-variance embedding too
                          # little to show in one second on the CPU.
                          "loss_must_fall": False,
                          "reference": {"parameters": "initial",
                                        "loss_abs": 0.02,
                                        "grad_rel": 0.5}}},
    "traffic": {"sequence": 256, "batch_per_chip": 2},
})

TINY.setdefault("hybrid_lm", {
    # Hidden 128; three linear layers of 2 heads, keys 32 and values 64 wide
    # (the published 1 : 2), 4 taps, and one softmax layer of 2 heads of 64
    # that do not rotate: the published pattern, one period.
    "config": {"hidden_size": 128, "num_attention_heads": 2,
               "num_key_value_heads": 2, "head_dim": 64,
               "linear_num_key_heads": 2, "linear_num_value_heads": 2,
               "linear_key_head_dim": 32, "linear_value_head_dim": 64,
               "intermediate_size": 256, "vocab_size": 512,
               "checks": {"first_loss_is_ln_vocab_plus": 0.5,
                          "first_loss_tolerance": 0.3,
                          # A dozen steps at the start of a 2000-step
                          # warm-up move too little to show in one second
                          # on the CPU.
                          "loss_must_fall": False,
                          # bf16 at these widths: each normalisation's
                          # backward (the post-norms, the L2 norms of q
                          # and k) projects the signal's main part out and
                          # leaves the rounding, 3.5 % behind the last
                          # layer and 11 % behind the first; float32
                          # through the same code agrees to 2e-3
                          # (test_benchmark_reference.py).
                          "reference": {"parameters": "initial",
                                        "loss_abs": 0.02,
                                        "grad_rel": 0.2}}},
    "traffic": {"sequence": 128, "batch_per_chip": 2},
})

TINY.setdefault("window_moe_lm", {
    # Hidden 128; the published pattern's first five layers (full, sliding,
    # sliding, sliding, full) at 4 and 6 query heads of 64 over 2 key-value
    # heads (groups of 2 and 3), a window of 64 keys, the full layers'
    # heads turning by half under YaRN; a dense layer and four routed ones
    # that hold experts 4 to 7 of 16, 3 choices a token, one shared expert.
    "config": {"hidden_size": 128, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 64,
               "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
               "sliding_window": 64,
               "rope_parameters": {
                   "full_attention": {
                       "rope_theta": 500000, "rope_type": "yarn",
                       "factor": 8, "original_max_position_embeddings": 64,
                       "beta_slow": 1, "beta_fast": 32,
                       "attention_factor": 1.2079441541679836,
                       "partial_rotary_factor": 0.5},
                   "sliding_attention": {
                       "rope_type": "default", "rope_theta": 10000,
                       "partial_rotary_factor": 1}},
               "intermediate_size": 256, "moe_intermediate_size": 32,
               "shared_expert_intermediate_size": 32, "vocab_size": 512,
               "num_experts": 4, "num_experts_per_tok": 3,
               "deployment": {"num_experts_published": 16,
                              "first_held_expert": 4},
               "checks": {"first_loss_is_ln_vocab_plus": 0.5,
                          "first_loss_tolerance": 0.3,
                          # A dozen steps at the start of a 2000-step
                          # warm-up move a unit-variance embedding too
                          # little to show in one second on the CPU.
                          "loss_must_fall": False,
                          "reference": {"parameters": "initial",
                                        "loss_abs": 0.02,
                                        "grad_rel": 0.1}}},
    "traffic": {"sequence": 128, "batch_per_chip": 2},
})

TINY.setdefault("hybrid_moe_lm", {
    # Hidden 128; the published pattern's one period (linear, linear, linear,
    # full): linear layers of 2 key heads serving 4 value heads, keys and
    # values 32 wide (the published 1 : 1), 4 taps; a full layer of 4 query
    # heads over 2 key-value heads of 64 of which a quarter turns, W_q a
    # query and a gate a head; every layer holds experts 4 to 7 of 16, 3
    # choices a token, beside a gated shared expert.
    "config": {"hidden_size": 128, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 64,
               "linear_num_key_heads": 2, "linear_num_value_heads": 4,
               "linear_key_head_dim": 32, "linear_value_head_dim": 32,
               "moe_intermediate_size": 32,
               "shared_expert_intermediate_size": 32, "vocab_size": 512,
               "num_experts": 4, "num_experts_per_tok": 3,
               "deployment": {"num_experts_published": 16,
                              "first_held_expert": 4},
               "checks": {"first_loss_is_ln_vocab_plus": 0.5,
                          "first_loss_tolerance": 0.3,
                          # A dozen steps at the start of a 2000-step
                          # warm-up move a unit-variance embedding too
                          # little to show in one second on the CPU.
                          "loss_must_fall": False,
                          # bf16 at these widths, as ``hybrid_lm``'s: the
                          # L2 norms of q and k and the QK-norms project the
                          # signal's main part out and leave the rounding;
                          # float32 through the same code agrees to 2e-3
                          # (tests/test_qwen3_next.py).
                          "reference": {"parameters": "initial",
                                        "loss_abs": 0.02,
                                        "grad_rel": 0.2}}},
    "traffic": {"sequence": 128, "batch_per_chip": 1},
})

TINY.setdefault("ssm_moe_lm", {
    # Hidden 64; three one-sublayer layers, one of each kind (the suite is
    # near its time limit): a Mamba-2 layer of 4 heads of 16 with a state of
    # 32 and 2 groups, chunks of 16; an attention layer of 4 query heads over
    # 2 key-value heads of 32; a routed layer that holds experts 2 to 5 of
    # 8, 2 choices a token, relu2 at width 48 beside a shared expert of 96.
    "config": {"hidden_size": 64, "num_hidden_layers": 3,
               "hybrid_override_pattern": "M*E",
               "mamba_num_heads": 4, "mamba_head_dim": 16,
               "ssm_state_size": 32, "n_groups": 2, "chunk_size": 16,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 32, "intermediate_size": 48,
               "moe_intermediate_size": 48,
               "moe_shared_expert_intermediate_size": 96,
               "vocab_size": 512, "n_routed_experts": 4,
               "num_experts_per_tok": 2,
               "deployment": {"num_experts_published": 8,
                              "first_held_expert": 2,
                              "num_hidden_layers_published": 3},
               "assumed": {"d_inner": 64, "aux_loss_alpha": 1e-4,
                           "bias_update_rate": 1e-3},
               "checks": {"first_loss_is_ln_vocab_plus": 0.5,
                          "first_loss_tolerance": 0.3,
                          # A dozen steps at the start of a 2000-step
                          # warm-up, as ``hybrid_moe_lm``.
                          "loss_must_fall": False,
                          # bf16 at these widths; float32 through the same
                          # code agrees to 1e-4 (tests/test_nemotron_h.py).
                          "reference": {"parameters": "initial",
                                        "loss_abs": 0.02,
                                        "grad_rel": 0.2}}},
    "traffic": {"sequence": 64, "batch_per_chip": 2},
})

TINY.setdefault("sambay_lm", {
    # Hidden 64; FOUR layers, the least the placement rule takes (Mamba,
    # window, Mamba + memory, full + shared k and v: the cross-decoder is
    # empty, and the suite is at its time limit; all five kinds at eight
    # layers run in ``tests/test_phi4_flash.py`` and on the chip): scans over
    # 128 channels of 16 state entries with a rank-4 step, differential
    # attention of 4 query heads over 2 key-value heads of 32 under a window
    # of 32.
    "config": {"hidden_size": 64, "num_hidden_layers": 4,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 32, "intermediate_size": 128, "vocab_size": 512,
               "sliding_window": 32,
               # No layer recomputed: a third less to compile here, and
               # ``tests/test_phi4_flash.py`` compares ``remat`` on and off.
               "training": {"optimizer": "adamw", "learning_rate": 3e-4,
                            "warmup_steps": 2000,
                            "compute_dtype": "bfloat16",
                            "master_dtype": "float32", "remat": "none"},
               "assumed": {"d_state": 16, "d_conv": 4, "expand": 2,
                           "dt_rank": 4},
               "checks": {"first_loss_is_ln_vocab_plus": 0.5,
                          "first_loss_tolerance": 0.3,
                          # A dozen steps at the start of a 2000-step
                          # warm-up, as ``hybrid_moe_lm``.
                          "loss_must_fall": False,
                          # bf16 at these widths; float32 through the same
                          # code agrees to 1e-4 (tests/test_phi4_flash.py).
                          "reference": {"parameters": "initial",
                                        "loss_abs": 0.02,
                                        "grad_rel": 0.2}}},
    # One row a step: the interpreted kernels make a step slow, and the
    # traced test needs three steps inside its one-second window.
    "traffic": {"sequence": 64, "batch_per_chip": 1},
})

TINY.setdefault("lconv_moe_lm", {
    # Hidden 128 (a lane tile: the gated filter's thirds then start at one,
    # as the cell's at 2048, and ``flash_attention_fn``'s model takes the
    # interpreted Mosaic pass); THREE layers, one of each combination the
    # stack has (a dense conv layer, a routed attention layer, a routed conv
    # layer: the suite is at its time limit; the cell's five run in
    # ``tests/test_lfm2.py`` and on the chip): a 3-tap filter, 2 query heads
    # over 1 key-value head of 64, experts 4 to 7 of 16 held, 3 choices a
    # token.
    "config": {"hidden_size": 128, "num_hidden_layers": 3,
               "layer_types": ["conv", "full_attention", "conv"],
               "num_attention_heads": 2, "num_key_value_heads": 1,
               "intermediate_size": 256, "moe_intermediate_size": 32,
               "vocab_size": 512, "num_experts": 4,
               "num_experts_per_tok": 3,
               "deployment": {"num_experts_published": 16,
                              "first_held_expert": 4},
               "checks": {"first_loss_is_ln_vocab_plus": 0.5,
                          "first_loss_tolerance": 0.3,
                          # A dozen steps at the start of a 2000-step
                          # warm-up, as ``hybrid_moe_lm``.
                          "loss_must_fall": False,
                          # bf16 at these widths; float32 through the same
                          # code agrees to 1e-4 (tests/test_lfm2.py).
                          "reference": {"parameters": "initial",
                                        "loss_abs": 0.02,
                                        "grad_rel": 0.2}}},
    "traffic": {"sequence": 64, "batch_per_chip": 2},
})

TINY.setdefault("ssm_lm", {
    # Hidden 64; TWO layers, one of each kind (the suite is near its time
    # limit; three Mamba layers to one run in ``tests/test_granite_hybrid.py``
    # and nine on the chip): a Mamba-2 mixer of 4 heads of 32 (an inner width
    # of ``mamba_expand`` 2 x 64) with a state of 16 in ONE group, chunks of
    # 16; 2 query heads over 1 key-value head of 32; each with a SwiGLU of
    # 128.  The four multipliers stay the published ones.
    "config": {"hidden_size": 64, "num_hidden_layers": 2,
               "layer_types": ["mamba", "attention"],
               "mamba_n_heads": 4, "mamba_d_head": 32, "mamba_d_state": 16,
               "mamba_chunk_size": 16,
               "num_attention_heads": 2, "num_key_value_heads": 1,
               "shared_intermediate_size": 128, "vocab_size": 512,
               "head_dim": 32,
               "checks": {"first_loss_is_ln_vocab_plus": 0.0078125,
                          "first_loss_tolerance": 0.3,
                          # A dozen steps at the start of a 2000-step
                          # warm-up, as ``hybrid_moe_lm``.
                          "loss_must_fall": False,
                          # bf16 at these widths; float32 through the same
                          # code agrees to 1e-4
                          # (tests/test_granite_hybrid.py).
                          "reference": {"parameters": "initial",
                                        "loss_abs": 0.02,
                                        "grad_rel": 0.2}}},
    "traffic": {"sequence": 64, "batch_per_chip": 2},
})

TINY.setdefault("prerouted_moe_lm", {
    # Hidden 128 (a lane tile, so ``flash_attention_fn``'s model takes the
    # interpreted Mosaic passes); TWO layers, one of each kind (the suite is
    # near its time limit; the cell's period of four runs in
    # ``tests/test_smallthinker.py`` and on the chip): a global layer that
    # does not rotate and a layer that does under a window of 64 keys, 2
    # query heads over 1 key-value head of 64; experts 4 to 7 of 16 held, 3
    # choices a token, ReGLU of 32, the router on the layer's input.
    "config": {"hidden_size": 128, "num_hidden_layers": 2,
               "rope_layout": [0, 1], "sliding_window_layout": [0, 1],
               "sliding_window_size": 64,
               "num_attention_heads": 2, "num_key_value_heads": 1,
               "head_dim": 64, "moe_ffn_hidden_size": 32,
               "vocab_size": 512, "moe_num_primary_experts": 4,
               "moe_num_active_primary_experts": 3,
               "deployment": {"num_experts_published": 16,
                              "first_held_expert": 4},
               "checks": {"first_loss_is_ln_vocab_plus": 0.5,
                          "first_loss_tolerance": 0.3,
                          # A dozen steps at the start of a 2000-step
                          # warm-up, as ``hybrid_moe_lm``.
                          "loss_must_fall": False,
                          # bf16 at these widths; float32 through the same
                          # code agrees to 1e-4 (tests/test_smallthinker.py).
                          "reference": {"parameters": "initial",
                                        "loss_abs": 0.02,
                                        "grad_rel": 0.2}}},
    "traffic": {"sequence": 128, "batch_per_chip": 2},
})

TINY.setdefault("hc_moe_lm", {
    # Hidden 128 (a lane tile, so ``flash_attention_fn``'s model takes the
    # interpreted Mosaic passes) in 4 streams; a dense layer and ONE routed
    # layer (the suite is near its time limit; two routed layers run in
    # ``tests/test_xing4.py`` and four on the chip): 2 heads, keys 64 + 64
    # wide and values 64 from a latent of 32, queries from a latent of 48;
    # experts 4 to 7 of 16 held, 3 choices a token, one shared expert; 5
    # Sinkhorn steps.
    "config": {"hidden_size": 128, "num_attention_heads": 2,
               "num_key_value_heads": 2, "qk_nope_head_dim": 64,
               "qk_rope_head_dim": 64, "v_head_dim": 64, "kv_lora_rank": 32,
               "q_lora_rank": 48, "hc_sinkhorn_iters": 5,
               "intermediate_size": 128, "moe_intermediate_size": 32,
               "vocab_size": 512, "num_hidden_layers": 2,
               "n_routed_experts": 4, "num_experts_per_tok": 3,
               "deployment": {"n_routed_experts_published": 16,
                              "first_held_expert": 4},
               "checks": {"first_loss_is_ln_vocab_plus": 0.5,
                          "first_loss_tolerance": 0.3,
                          # A dozen steps at the start of a 2000-step
                          # warm-up, as ``hybrid_moe_lm``.
                          "loss_must_fall": False,
                          # bf16 at these widths; float32 through the same
                          # code agrees to 1e-4 (tests/test_xing4.py).
                          "reference": {"parameters": "initial",
                                        "loss_abs": 0.02,
                                        "grad_rel": 0.2}}},
    "traffic": {"sequence": 128, "batch_per_chip": 2},
})

TINY.setdefault("kda_moe_lm", {
    # Hidden 128; three layers: a KDA layer over the dense SwiGLU, a latent
    # layer and a KDA layer over routed experts (the published period is
    # three KDA to one latent; the five layers of the cell run in
    # ``tests/test_kimi_linear.py`` and on the chip).  KDA: 2 heads of 64 |
    # 64 behind 4 taps (a lane tile of channels); latent attention: 2 heads,
    # keys 64 + 64 unrotated and values 64 from a latent of 32; experts 4 to
    # 7 of 16 held, 3 choices a token, one shared expert.
    "config": {"hidden_size": 128, "num_attention_heads": 2,
               "num_key_value_heads": 2, "head_dim": 64,
               "qk_nope_head_dim": 64, "qk_rope_head_dim": 64,
               "v_head_dim": 64, "kv_lora_rank": 32,
               "intermediate_size": 128, "moe_intermediate_size": 32,
               "vocab_size": 512, "num_hidden_layers": 3,
               "linear_attn_config": {
                   "kda_layers": [1, 3], "full_attn_layers": [2],
                   "num_heads": 2, "head_dim": 64,
                   "short_conv_kernel_size": 4},
               "num_experts": 4, "num_experts_per_token": 3,
               "deployment": {"num_experts_published": 16,
                              "first_held_expert": 4},
               "checks": {"first_loss_is_ln_vocab_plus": 0.5,
                          "first_loss_tolerance": 0.3,
                          # A dozen steps at the start of a 2000-step
                          # warm-up, as ``hybrid_moe_lm``.
                          "loss_must_fall": False,
                          # bf16 at these widths, as ``hybrid_moe_lm``'s;
                          # float32 through the same code agrees to 2e-3
                          # (tests/test_kimi_linear.py).
                          "reference": {"parameters": "initial",
                                        "loss_abs": 0.02,
                                        "grad_rel": 0.2}}},
    "traffic": {"sequence": 128, "batch_per_chip": 1},
})


# The files that take over 100 s of the driver's command
# (``/root/TESTS_LAST_RUN.json``: six workers, ``--dist loadfile``), longest
# first, with the seconds of each as PR 51 read them (the mean of two runs in
# a sandbox of eight cores, where a file's seconds differ by a third from run
# to run; ROADMAP.md D9 says how to read them again).  xdist hands out whole
# files, and queues them by their number of tests unless it is told not to;
# the longest files here have the fewest tests, so they started last.  A PR
# that adds a file of over 100 s adds its row.
LONGEST_FIRST = (
    "tests/benchmark/test_benchmark_cells.py",          # 427 s
    "tests/test_keye_sparse.py",                        # 336 s
    "tests/benchmark/test_benchmark_reference.py",      # 332 s
    "tests/test_qwen3_next.py",                         # 287 s
    "tests/benchmark/test_benchmark_hybrid_moe.py",     # 273 s
    "tests/test_laguna.py",                             # 271 s
    "tests/test_flash_walks.py",                        # 235 s
    "tests/test_kimi_linear.py",                        # 230 s (PR 69)
    "tests/benchmark/test_benchmark_window.py",         # 232 s
    "tests/test_olmo_hybrid.py",                        # 222 s
    "tests/test_flash_attention.py",                    # 204 s
    "tests/benchmark/test_benchmark_hybrid.py",         # 193 s
    "tests/test_deepseek_moe.py",                       # 182 s
    "tests/test_serve.py",                              # 181 s
    "tests/benchmark/test_benchmark_sparse.py",         # 176 s
    "tests/test_deepseek_model.py",                     # 160 s
    "tests/benchmark/test_benchmark_kda.py",            # 160 s (PR 69)
    "tests/test_flash_v5e_compile.py",                  # 157 s
    "tests/test_smallthinker.py",                       # 155 s (PR 63)
    "tests/test_xing4.py",                              # 150 s (PR 65)
    "tests/test_looped_llama.py",                       # 146 s
    "tests/benchmark/test_benchmark_moe.py",            # 131 s
    "tests/test_nemotron_h.py",                         # 118 s
    "tests/test_laguna_v5e_compile.py",                 # 116 s
    "tests/test_olmo_hybrid_v5e_compile.py",            # 108 s
    "tests/test_phi4_flash.py",                         # 105 s (PR 54)
    "tests/test_keye_sparse_v5e_compile.py",            # 105 s
    "tests/test_models.py",                             # 100 s
)


def _file(item) -> str:
    return os.path.relpath(str(item.path), REPO)


def longest_first(items: list) -> list:
    """``items`` with the files of ``LONGEST_FIRST`` ahead in the table's
    order and every other file behind them as collected; a file's own order
    is kept (the sort is stable, and its key is the file's alone)."""
    place = {path: n for n, path in enumerate(LONGEST_FIRST)}
    return sorted(items, key=lambda item: place.get(_file(item), len(place)))


def pytest_collection_modifyitems(config, items):
    items[:] = longest_first(items)


def pytest_configure(config):
    # The queue of files is the collection's order (``LONGEST_FIRST``): an
    # xdist that would reorder it by count of tests is told not to.  Without
    # xdist, or with one that has no such option, there is nothing to set.
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 gate")
    config.addinivalue_line(
        "markers",
        "fault: fault-injection multiproc tests; ci.sh reruns them under a "
        "hard timeout so a reintroduced hang fails fast")
    config.addinivalue_line(
        "markers",
        "scale: big-world fleet tests (64+ engine ranks / 16-rank elastic "
        "under hierarchical coordination); ci.sh runs them in the scale "
        "gate under a hard timeout")
    config.addinivalue_line(
        "markers",
        "straggler: backup-worker chaos soaks (slow-fault schedules, "
        "step-time p99 comparison); ci.sh runs them in the straggler "
        "gate under a hard timeout, separate from the fault/soak gates")
    config.addinivalue_line(
        "markers",
        "observability: fleet-telemetry / metrics-endpoint / flight-"
        "recorder tests; ci.sh runs them in the observability gate "
        "under a hard timeout (main sweep excludes the marker, tier-1 "
        "still runs them)")
    config.addinivalue_line(
        "markers",
        "linkheal: link self-healing tests (transparent data-channel "
        "reconnect under injected conn-reset/recv-stall faults); ci.sh "
        "runs them in the link-heal gate under a hard timeout")
    config.addinivalue_line(
        "markers",
        "priority: priority-scheduled communication tests "
        "(HOROVOD_PRIORITY_BANDS ordering/fusion/wave contracts); ci.sh "
        "runs them in the overlap gate under a hard timeout (main sweep "
        "excludes the marker, tier-1 still runs them)")
    config.addinivalue_line(
        "markers",
        "moe: expert-parallel MoE plane tests (variable-split alltoall "
        "dispatch/combine, dense-reference bit-parity, drop-token "
        "accounting); ci.sh runs them in the moe gate under a hard "
        "timeout (main sweep excludes the marker; tier-1 runs the ones "
        "not also marked slow — the 4-rank variants ride the gate)")
    config.addinivalue_line(
        "markers",
        "ckpt: weight-plane tests (crash-consistent sharded saves, "
        "elastic resharding restore, kill-and-resume, live serve push); "
        "ci.sh runs them in the checkpoint gate under a hard timeout "
        "(main sweep excludes the marker; tier-1 runs the ones not "
        "also marked slow — the serve-fleet pushes are slow-marked)")


# The accepted tests that pin their PR's entries as the LAST of every list
# of ``BENCHMARK.json`` (ROADMAP.md D17), and the last cell and per-layer
# metric each was written against.
_MANIFEST_THEN = {
    "test_benchmark_moe.py::test_the_manifests_new_entries":
        ("deepseek-v2-lite.train-s4k", "mla_latent_ms"),
    "test_benchmark_sparse.py::test_the_manifests_new_entries":
        ("keye-vl-2.0-30b-a3b.train-s8k-b2", "index_loss_roofline"),
    "test_benchmark_dense.py::"
    "test_the_four_entries_are_in_the_manifest_as_the_issue_put_them":
        ("keye-vl-2.0-30b-a3b.train-s8k-b2", "dense_roofline"),
    "test_benchmark_hybrid.py::test_the_manifests_new_entries":
        ("olmo-hybrid-7b.train-s8k", "gdn_scan_roofline"),
    "test_benchmark_window.py::test_the_manifests_new_entries":
        ("laguna-s-2.1.train-s8k", "attn_gate_ms"),
    "test_benchmark_gdn_solve.py::test_the_manifests_one_new_entry":
        ("qwen3-next-80b-a3b.train-s8k-b2", "gdn_solve_ms"),
    # (This one reads the cells at import, from the file as it is: its cut
    # keeps every cell, the newest named here, and ends the metrics at its.)
    "test_benchmark_startup_spans.py::test_the_manifests_ten_entries":
        ("kimi-linear-48b-a3b.train-s8k-b2", "trace_loss_self_ms"),
    "test_benchmark_qk_norm.py::test_the_manifests_one_new_entry":
        ("phi-4-mini-flash.train-s8k", "diff_attn_ms"),
    "test_benchmark_ssm_moe.py::test_the_manifests_new_entries":
        ("lfm2-24b-a2b.train-s8k-b2", "lconv_conv_roofline"),
    # (It holds every list that names its cell to be its own PR's: PR 67's
    # nine start-up metrics list all sixteen cells.)
    "test_benchmark_hc.py::test_the_manifests_entries_are_the_issues":
        ("xing4.0-29b-a4b.train-s8k", "hc_mix_roofline"),
}


@pytest.fixture(autouse=True)
def _manifest_as_its_test_knew_it(request, monkeypatch):
    """``tests/benchmark/test_benchmark_moe.py::test_the_manifests_new_
    entries`` (PR 32) and its namesakes in ``test_benchmark_sparse.py``
    (PR 34) and ``test_benchmark_hybrid.py`` (PR 38) pin their PR's entries
    (``test_benchmark_window.py``'s, PR 42, the cells its new metrics list;
    ``test_benchmark_gdn_solve.py``'s, PR 47, its one metric;
    ``test_benchmark_startup_spans.py``'s, PR 52, its ten;
    ``test_benchmark_qk_norm.py``'s, PR 48, the cells that norm q and k a
    head; ``test_benchmark_ssm_moe.py``'s, PR 50, the one cell its four
    ``ssd_*`` metrics listed; ``test_benchmark_hc.py``'s, PR 65, every
    list that names its cell)
    as the LAST of every list of
    ``BENCHMARK.json`` and count the cells, and a later PR may neither
    edit those files nor put its entries anywhere but last.  So each of
    those tests reads the manifest cut back to the entries it was written
    against; every other test reads the file as it is."""
    then = next((cut for test, cut in _MANIFEST_THEN.items()
                 if request.node.nodeid.endswith(test)), None)
    if then is None:
        return
    last_cell, last_metric = then
    from benchmark import manifest

    whole = manifest.load()

    def upto(entries, last):
        names = [entry["name"] for entry in entries]
        return entries[:names.index(last) + 1]

    cells = upto(whole["workloads"], last_cell)
    known = {cell["name"] for cell in cells}

    def cut(metrics, last):
        return [{**m, **({"workloads": [w for w in m["workloads"]
                                        if w in known]}
                         if "workloads" in m else {})}
                for m in upto(metrics, last)]

    then = {**whole,
            "configs": upto(whole["configs"], cells[-1]["config"]),
            "workloads": cells,
            "end_to_end": cut(whole["end_to_end"], "setup_s"),
            "per_layer": cut(whole["per_layer"], last_metric)}
    real_cell = manifest.cell
    monkeypatch.setattr(manifest, "load", lambda: then)
    monkeypatch.setattr(manifest, "cell",
                        lambda workload, listed=None: real_cell(
                            workload, listed or then))


class CompileCounts:
    """What share of a run was compilation, from JAX's own events (the ones
    ``horovod_tpu/common/compile_cache.py``'s log listens to): requests to
    the persistent cache, its hits, and the seconds inside the backend,
    compiling a program or reading it back.  Of this process alone: what a
    test's subprocesses compile is not in it."""

    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    HIT = "/jax/compilation_cache/cache_hits"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.requests = self.hits = self.programs = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        self.requests += event == self.REQUEST
        self.hits += event == self.HIT

    def _on_duration(self, event, seconds, **_):
        if event == self.BACKEND:
            self.programs += 1
            self.seconds += seconds

    def row(self) -> dict:
        return {"requests": self.requests, "hits": self.hits,
                "programs": self.programs, "seconds": round(self.seconds, 1)}


_COMPILE_COUNTS = CompileCounts()
_WORKERS_COUNTS = {}            # on xdist's controller: worker id -> row


def pytest_sessionfinish(session):
    # An xdist worker has no terminal: its row travels to the controller.
    if hasattr(session.config, "workeroutput"):
        session.config.workeroutput["compile_counts"] = _COMPILE_COUNTS.row()


@pytest.hookimpl(optionalhook=True)
def pytest_testnodedown(node, error):
    row = getattr(node, "workeroutput", {}).get("compile_counts")
    if row:
        _WORKERS_COUNTS[node.gateway.id] = row


def pytest_terminal_summary(terminalreporter):
    rows = dict(sorted(_WORKERS_COUNTS.items())) or {
        "main": _COMPILE_COUNTS.row()}
    if len(rows) > 1:
        rows["all"] = {k: round(sum(row[k] for row in rows.values()), 1)
                       for k in next(iter(rows.values()))}
    terminalreporter.section("XLA compilation in the test processes")
    terminalreporter.line(
        f"persistent cache at {jax.config.jax_compilation_cache_dir}; "
        f"jax_disable_most_optimizations="
        f"{jax.config.read('jax_disable_most_optimizations')}, "
        f"XLA_FLAGS={os.environ['XLA_FLAGS']}")
    for worker, row in rows.items():
        share = row["hits"] / row["requests"] if row["requests"] else 0.0
        terminalreporter.line(
            f"{worker}: {row['programs']} programs, {row['seconds']} s in "
            f"the backend (compiling or reading back); persistent cache "
            f"{row['hits']} hits / {row['requests']} requests = {share:.3f}")


@contextlib.contextmanager
def optimised():
    """What is compiled inside is compiled as a user's program would be, not
    at the suite's cheap level."""
    cheap = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", False)
    try:
        yield
    finally:
        jax.config.update("jax_disable_most_optimizations", cheap)


@pytest.fixture
def optimised_code():
    """For the test that is about the optimised program: one that runs its
    programs for longer than they take to compile, or that states two
    programs' results equal to the last bit (unoptimised, a loop's body and
    the same body beside the loop round differently).  A file asks for it by
    ``pytestmark = pytest.mark.usefixtures("optimised_code")``."""
    with optimised():
        yield


@pytest.fixture(scope="session", autouse=True)
def _timed_windows_run_optimised_code():
    """The one place where the suite cares how fast an x86 runs a program:
    ``benchmark/run.py::run`` counts the steps that complete inside a window
    of one second and refuses under three.  Unoptimised, a tiny cell's step is
    2.4 to 4.5 times slower (PR 55), and under the suite's load some cells
    complete three or four steps as it is.  So whatever ``run`` compiles it
    compiles under ``optimised``; everything else in the process keeps the
    suite's level."""
    from benchmark import run as harness

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(harness, "run", optimised()(harness.run))
        yield


@pytest.fixture(scope="session")
def n_devices():
    assert len(jax.devices()) == N_DEVICES
    return N_DEVICES


@pytest.fixture(scope="session", autouse=True)
def _hvd_init():
    import horovod_tpu as hvd

    hvd.init()
    yield
