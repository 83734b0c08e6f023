"""Tiny sizes at which the tests run the benchmark's jobs on the CPU, keyed
by the job a configuration names.  Widths here mean nothing; the cells'
own files keep the published ones."""

TINY = {
    "decoder_lm": {
        "config": {"hidden_size": 64, "num_attention_heads": 2,
                   "num_key_value_heads": 2, "head_dim": 32,
                   "intermediate_size": 128, "vocab_size": 512,
                   "num_hidden_layers": 2,
                   # bf16 at these widths: a few per cent on gradients.
                   "checks": {"first_loss_is_ln_vocab_plus": 0.5,
                              "first_loss_tolerance": 0.25,
                              "loss_must_fall": True,
                              "reference": {"parameters": "initial",
                                            "loss_abs": 0.02,
                                            "grad_rel": 0.05}}},
        "traffic": {"sequence": 128, "batch_per_chip": 2},
    },
    "image_classifier": {
        "config": {"stage_sizes": [1, 1, 1, 1], "width": 8,
                   "num_classes": 10, "image_size": 32,
                   # Eight channels and eight images under bf16 batch norm
                   # are noisy: the tight comparison is the fp32 one of
                   # test_benchmark_reference.py.
                   "checks": {"first_loss_is_ln_classes_plus": 0.0,
                              "first_loss_tolerance": 2.0,
                              "loss_must_fall": False,
                              "reference": {"parameters": "live",
                                            "loss_abs": 0.1,
                                            "grad_rel": 0.5}}},
        "traffic": {"batch_per_chip": 8, "sample_per_chip": 8},
    },
}
