"""kf_benchmarks_tpu: a TPU-native benchmark framework.

A ground-up JAX/XLA/pjit re-design of the capabilities of
``Panlichen/kf-benchmarks`` (reference ``scripts/tf_cnn_benchmarks``):
high-performance CNN training benchmarks with pluggable data-parallel
strategies, including TPU-native equivalents of the KungFu distributed
optimizers (synchronous SGD via ``psum``, pair-averaging gossip via
``ppermute``, synchronous model averaging).

Layer map (mirrors reference SURVEY layer map):
  cli.py            -- CLI entry (ref: tf_cnn_benchmarks.py)
  flags.py          -- ParamSpec registry / absl bridge (ref: flags.py)
  params.py         -- Params + validation (ref: benchmark_cnn.py:953-1034)
  benchmark.py      -- core runtime driver (ref: benchmark_cnn.py)
  parallel/         -- parallelism strategies (ref: variable_mgr*.py)
  ops/              -- collectives: spec parser, packing (ref: allreduce.py)
  models/           -- model zoo + builder (ref: models/, convnet_builder.py)
  data/             -- datasets + preprocessing (ref: datasets.py, preprocessing.py)
  utils/            -- logging, timing, cluster helpers (ref: cnn_util.py)
"""

__version__ = "0.1.0"
