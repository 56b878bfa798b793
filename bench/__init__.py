"""The chip benchmark of the serving engine.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the TPU it finds and prints one
JSON result line.  Everything that belongs to one model configuration, one
traffic mix, one cell or one per-layer metric is a file of its own under
``bench/configs``, ``bench/traffic``, ``bench/cells`` and ``bench/metrics``,
found by the name ``BENCHMARK.json`` gives it; what belongs to one
architecture is a file under ``bench/arch``, found by the configuration's
``model_type``.
"""
