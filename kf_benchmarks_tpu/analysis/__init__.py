"""Static analysis of the framework: program contracts + hazard lint.

TPU-NATIVE-ONLY subsystem (no single reference file to cite; the
reference analog is its reliance on GRAPH-MODE STRUCTURE -- variable
scopes, collective op counts, staging-area wiring -- asserted by
inspecting the built tf.Graph before any session ran. Here the
compiled XLA program plays the graph's role, so the same guarantees
are checked by lowering ``jit`` programs without executing them. See the
graph-structure-assumptions section of MIGRATION.md and COVERAGE.md.)

Two layers:

* ``contracts`` / ``audit`` / ``baseline`` -- the **program-contract
  auditor**: trace (never execute) the train step for a
  ``BenchmarkParams`` config on the abstract 8-device mesh via
  ``jit(...).lower(...).compile()``, extract a structured
  :class:`~kf_benchmarks_tpu.analysis.contracts.ProgramContract`
  (collective inventory with wire dtypes and loop placement, host
  transfers, optimizer-apply scope, donation, largest live buffers),
  check every earned invariant per config (``audit``), and diff
  against checked-in goldens (``baseline``,
  ``tests/golden_contracts/*.json``).

* ``lint`` -- the **hazard lint**: an AST pass over the repo encoding
  the repo's conventions (version gates need a comment naming the
  missing API, step-line format literals single-sourced, flags must be cross-validated or carry an explicit
  no-validation marker, reference citations per module). Pure stdlib:
  importing ``lint`` never imports jax.

* ``autotune`` -- the **contract-driven autotuner**: the auditor's
  tracing machinery turned search oracle. Candidates over the tuned
  program-shaping knobs are pruned statically against memory/
  collective bounds (never executed), cost-ranked from the contract
  inventory, confirmed with differential measured probes, and emitted
  as a versioned tuned-config table ``--autotuned_config`` applies at
  startup; the same module's ``warm`` precompiles every
  (table x compile-ledger) program shape into the persistent XLA
  cache ahead of a hardware window.

CLI: ``python -m kf_benchmarks_tpu.analysis`` (see ``__main__``);
CI entry: ``python run_tests.py --audit``.
"""
