"""The step's plan (train_step.plan_step): which exchange and which
optimizer application a configuration gets, asked without tracing
anything.

One row per mode in which the factor data plane must not engage
(tests/test_factor_exchange.py MUST_NOT_ENGAGE, whose lowered programs
are pinned there), per strategy that reduces by the plain mean (its
positive control), and two that neither table has: FSDP under
accumulation, and KungFu sync_sgd on a 4x1 mesh with accumulation 2.
"""

import pytest

from kf_benchmarks_tpu import benchmark
from kf_benchmarks_tpu import params as params_lib
from kf_benchmarks_tpu import train_step as train_step_lib
from kf_benchmarks_tpu.train_step import Apply, Exchange

from test_factor_exchange import MOMENTUM, MUST_NOT_ENGAGE, N_REPLICAS

# What each MUST_NOT_ENGAGE mode gets instead of the factored mean.
# forward_only / eval: the plan is the TRAIN step's, which such a run
# builds and never dispatches (their pinned program is the eval step).
KINDS = {
    "one_chip_kungfu_sync": (Exchange.STRATEGY, Apply.PLAIN),
    "one_chip_replicated": (Exchange.STRATEGY, Apply.PLAIN),
    "independent": (Exchange.STRATEGY, Apply.PLAIN),
    "kungfu_async_sgd": (Exchange.STRATEGY, Apply.PLAIN),
    "kungfu_sma": (Exchange.STRATEGY, Apply.PLAIN),
    "async_ps_sequential_apply": (Exchange.STRATEGY, Apply.SEQUENTIAL),
    "async_ps_sgd_sum": (Exchange.STRATEGY, Apply.PLAIN),
    "reducer_spec_planner": (Exchange.STRATEGY, Apply.PLAIN),
    "reducer_repacking": (Exchange.STRATEGY, Apply.PLAIN),
    "reducer_small_grad_aggregation": (Exchange.STRATEGY, Apply.PLAIN),
    "reducer_hierarchical_copy": (Exchange.STRATEGY, Apply.PLAIN),
    "reducer_compact_wire": (Exchange.STRATEGY, Apply.PLAIN),
    "zero_sharded_state": (Exchange.ZERO_SCATTER, Apply.SHARD),
    "fsdp_sharded_params": (Exchange.FSDP_IN_BACKWARD, Apply.SHARD),
    "num_grad_accum_2": (Exchange.STRATEGY, Apply.PLAIN),
    "track_grad_noise_scale": (Exchange.STRATEGY, Apply.PLAIN),
    "model_axis_2": (Exchange.ZERO_SCATTER, Apply.SHARD),
    "forward_only": (Exchange.FACTORED_MEAN, Apply.PLAIN),
    "eval": (Exchange.FACTORED_MEAN, Apply.PLAIN),
}

PLAIN_MEAN = {
    "kungfu_sync_sgd": dict(variable_update="kungfu"),
    "replicated": dict(variable_update="replicated"),
    "parameter_server": dict(variable_update="parameter_server"),
    "horovod": dict(variable_update="horovod"),
    "collective_all_reduce": dict(variable_update="collective_all_reduce"),
    "replicated_8x1_mesh": dict(variable_update="replicated",
                                mesh_shape="8x1"),
}

CASES = [(mode, MUST_NOT_ENGAGE[mode]) + KINDS[mode]
         for mode in sorted(MUST_NOT_ENGAGE)]
CASES += [(name, kw, Exchange.FACTORED_MEAN, Apply.PLAIN)
          for name, kw in PLAIN_MEAN.items()]
CASES += [
    ("fsdp_accum_2", dict(shard_optimizer_state=True, shard_params=True,
                          num_grad_accum=2, **MOMENTUM),
     Exchange.FSDP_SCATTER, Apply.SHARD),
    ("kungfu_sync_sgd_4x1_accum_2",
     dict(variable_update="kungfu", num_devices=4, mesh_shape="4x1",
          num_grad_accum=2), Exchange.STRATEGY, Apply.PLAIN),
]


def test_every_pinned_mode_has_a_row():
  assert set(KINDS) == set(MUST_NOT_ENGAGE)


@pytest.mark.parametrize("overrides,exchange,apply",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_plan(overrides, exchange, apply):
  kw = dict(model="trivial", batch_size=4, device="cpu",
            num_devices=N_REPLICAS, num_batches=2)
  kw.update(overrides)
  kw.pop("program", None)
  bench = benchmark.BenchmarkCNN(params_lib.make_params(**kw))
  plan = train_step_lib.plan_step(bench.strategy, bench.params, bench.mesh,
                                  bench.model)
  assert (plan.exchange, plan.apply) == (exchange, apply)
  # What the kinds imply, so that no stage has to ask anything else.
  assert plan.sharded_state is (apply is Apply.SHARD)
  assert plan.sharded_params is (exchange in (Exchange.FSDP_IN_BACKWARD,
                                              Exchange.FSDP_SCATTER))
  assert plan.num_grad_accum == (overrides.get("num_grad_accum") or 1)
  if exchange is Exchange.FACTORED_MEAN:
    assert bench.strategy.plain_mean and plan.data_replicas > 1
    assert plan.num_grad_accum == 1 and not plan.noise_scale


@pytest.mark.parametrize("flag", ["overlap_gradient_reduction", "mkl"])
def test_flags_that_went_are_unknown(flag):
  """--overlap_gradient_reduction (the in-backward fork the chip refused)
  and the 39 reference flags with no TPU meaning (``mkl`` for all of
  them; MIGRATION.md lists them) are not defined: no table explains
  them, the parser's own error does."""
  with pytest.raises(ValueError, match=f"Unknown param: {flag}"):
    params_lib.make_params(**{flag: True})
