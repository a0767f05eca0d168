"""The benchmark of kf_benchmarks_tpu: harness, data files, reductions.

Everything the driver's check depends on lives here (and in
``BENCHMARK.json``): the program under test is reached only through the
three calls ``cli.main`` makes. See ``benchmarks/README.md``.
"""
