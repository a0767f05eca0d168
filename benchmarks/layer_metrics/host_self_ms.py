"""Host time per timed iteration that NO span of the program names: the
``self`` time of the ``train`` iteration (its length less every span
inside it: dispatch, the blocking fetch, the handling of the resolved
step, the wait for input, the garbage collector), mean over the
iterations the program's account holds (``stats["step_account"]``,
benchmarks/step_account.py) without those this harness's profiler
distorted. The honesty check on the host spans, as ``unscoped_ms`` is on
the device scopes: what grows here is loop body that wants a span. None
where the program keeps no such account."""

LAYER = "driver_loop"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "samples_per_sec"


def read(run):
  from benchmarks import step_account
  return step_account.self_ms(run)
