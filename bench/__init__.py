"""Chip benchmark of the served path: one command, cells defined by data.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU the process finds.  What
belongs to one configuration, traffic mix, cell or per-layer metric lives
in a file of its own, found by name:

  bench/configs/<config>.json   sizes as run, what was cut, the deployment
  bench/configs/<config>.py     weights from the seed and the plain float32
                                reference of the same architecture
  bench/traffic/<mix>.json      arrival process and length distributions
  bench/cells/<cell>.json       rate, serving knobs, the correctness limit
  bench/metrics/<metric>.py     one reducer per per-layer metric

``bench/peaks.json`` holds the chip peaks keyed by ``device_kind``;
``bench/flops.py`` the operations and bytes of each call, from shapes.
"""
