"""The benchmark of horovod_tpu: training cells on the TPU v5e.

``python -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once.  Everything that
belongs to one configuration, one traffic mix, one job family, one
reference or one metric is a file of its own, found by the name the
manifest gives it (``benchmark/manifest.py``); ``PERF.md`` says what each
number means.
"""
