#!/usr/bin/env python3
"""The controls of the language-model cells: the benchmark's own command
on a program with ONE fault planted from outside. Each has to come out
NOT ``correct``, by the number of the cell's reference check
(``benchmarks/named_checks/<config>_reference_agrees.py``) named beside
it; PERF.md section 6 (PR 27, PR 32, PR 39) has the chip's readings.

    python3 experiments/lm_precision_control.py [--fault <name>] \
        --workload <cell> --seed <n> --seconds 10 --trace 0

Every configuration:

* ``router_bf16`` (the default; the lower-precision control): the ROUTER
  of ``mla_moe_lm`` computed in bfloat16 (product, sigmoid, top-k and
  renormalisation), the nearest precision below the float32 the
  configuration states. Fails ``router_scores_err`` and
  ``choice_mismatch_share``, both read from the timed step program.
* ``state_unchanged``: the step program the check calls moves Adam's
  moments and leaves the parameters where they were. Fails
  ``param_change_err``, which reads 1.
* ``half_batch``: the losses over the first half of the batch (glm), of
  the one sequence (trinity-mini). Fails every ``grad_err.*``.
* ``no_scaling``: the routed experts' part without its
  ``routed_scaling_factor`` / ``route_scale``. Fails ``layer_output_err``.

glm-4.7-flash alone:

* ``no_mtp``: the MTP term left out of the loss. Fails ``step_loss_err``
  and ``grad_err.eh_proj``.

trinity-mini alone (each ONE departure from the published layer):

* ``window_2047`` / ``window_2049``: the window layers see one key fewer
  / one more. Fails ``window_edge_err``.
* ``rope_in_full``: the full layers rotate q and k like the window
  layers. Fails ``layer_output_err``.
* ``no_gate``: the core's output reaches ``o_proj`` without its gate.
  Fails ``layer_output_err``.
* ``no_post_norms``: the norms AFTER the two sublayers pass their input
  through. Fails ``layer_output_err``.

nemotron-3-nano-30b-a3b alone (each ONE departure from the published
layer, but the first, which is the lower-precision control of its
state-space layers):

* ``scan_bf16``: the scan's running sums, exponentials and carried state
  in bfloat16, the nearest precision below the float32 the configuration
  states (``ssd.scan_plan`` hands bfloat16 steps to the einsums on every
  backend: the kernels of PR 40 take float32 steps alone). Fails
  ``scan_carry_err`` and ``layer_output_err``.
* ``no_chunk_carry``: no state passes from a chunk to the next (every
  chunk starts from zero). Fails ``scan_carry_err``, which reads 0.5 or
  more. Planted in the form that RUNS: the kernels carry the state in a
  VMEM scratch and never call ``ssd.carried_states``, so the control
  also hands ``ssd_scan`` the einsums' plan (``scan_plan`` wrapped to
  say ``"xla"`` whatever the backend) and loses the carry there.
* ``gate_after_norm``: the Mamba mixer's gate applied AFTER its grouped
  norm (Mamba-2's other published order). Fails ``layer_output_err``.
* ``experts_gated``: the expert's form taken for the other families':
  ``silu(u) * u`` in place of ``relu(u)^2`` between its two matrices,
  routed and shared alike. Fails ``layer_output_err``.

The program has no option for any of this: ``plant`` steers it from
outside, here and in ``tests/benchmarks/test_bench_lm.py`` /
``test_bench_afmoe.py`` / ``test_bench_nemotron_h.py``.
"""

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

FAULTS = ("router_bf16", "state_unchanged", "half_batch", "no_mtp",
          "no_scaling", "window_2047", "window_2049", "rope_in_full",
          "no_gate", "no_post_norms", "scan_bf16", "no_chunk_carry",
          "gate_after_norm", "experts_gated")


def plant(fault, setattr_=setattr):
  """Plant ``fault`` in ``mla_moe_lm`` / ``BenchmarkCNN``; ``setattr_``
  is ``monkeypatch.setattr`` (``raising=False``) in a test, so that the
  fault goes with the test."""
  import jax
  import jax.numpy as jnp
  from kf_benchmarks_tpu import benchmark
  from kf_benchmarks_tpu.models import mla_moe_lm
  model = mla_moe_lm.MLAMoELMModel
  if fault == "router_bf16":
    make = model.make_module
    setattr_(model, "make_module", lambda self, *a, **k: make(
        self, *a, **k).clone(router_dtype=jnp.bfloat16))
  elif fault == "half_batch":
    losses = model.losses

    def half(self, heads, labels):
      # Half of the sequences; of the one sequence where there is one.
      axis = 0 if labels.shape[0] > 1 else 1
      cut = lambda x: jax.lax.slice_in_dim(x, 0, x.shape[axis] // 2,
                                           axis=axis)
      hidden = tuple(h if h is None else cut(h) for h in heads.hidden)
      return losses(self, heads._replace(hidden=hidden), cut(labels))
    setattr_(model, "losses", half)
  elif fault == "no_mtp":
    setattr_(model, "loss_function", lambda self, result, labels:
             self.losses(result.logits[0], labels)[0])
  elif fault == "no_scaling":
    load = mla_moe_lm.load_lm_config
    setattr_(mla_moe_lm, "load_lm_config", lambda *a, **k:
             dataclasses.replace(load(*a, **k), routed_scaling_factor=1.0))
  elif fault in ("window_2047", "window_2049"):
    load = mla_moe_lm.load_lm_config
    off = -1 if fault == "window_2047" else 1

    def one_off(*a, **k):
      cfg = load(*a, **k)
      return dataclasses.replace(cfg, sliding_window=cfg.sliding_window + off)
    setattr_(mla_moe_lm, "load_lm_config", one_off)
  elif fault == "rope_in_full":
    # A window no sequence reaches is the causal half (parallel/
    # sequence.py), and a layer with a window rotates q and k.
    window = mla_moe_lm.LMConfig.window
    setattr_(mla_moe_lm.LMConfig, "window", lambda self, i: (
        2 ** 30 if window(self, i) is None else window(self, i)))
  elif fault == "no_gate":
    setattr_(mla_moe_lm, "gated", lambda core, gate: core)
  elif fault == "no_post_norms":
    import flax.linen as nn

    class PassThrough(mla_moe_lm.RMSNorm):
      @nn.compact
      def __call__(self, x):
        self.param("scale", nn.initializers.ones, (x.shape[-1],),
                   self.param_dtype)
        return x.astype(jnp.float32)
    norm = mla_moe_lm.Block.norm
    setattr_(mla_moe_lm.Block, "norm", lambda self, name: (
        PassThrough(self.cfg.rms_norm_eps, self.param_dtype, name=name)
        if name.startswith("post_") and self.cfg.post_norms
        else norm(self, name)))
  elif fault == "scan_bf16":
    make = model.make_module
    setattr_(model, "make_module", lambda self, *a, **k: make(
        self, *a, **k).clone(scan_dtype=jnp.bfloat16))
  elif fault == "no_chunk_carry":
    from kf_benchmarks_tpu.ops import ssd
    carried, plan = ssd.carried_states, ssd.scan_plan
    setattr_(ssd, "carried_states", lambda own, total: jnp.zeros_like(
        carried(own, total)))
    setattr_(ssd, "scan_plan", lambda *a, **k: dataclasses.replace(
        plan(*a, **k), implementation="xla"))
  elif fault == "gate_after_norm":
    from kf_benchmarks_tpu.ops import ssd

    def norm_then_gate(y, z, scale, groups, eps):
      return ssd.group_norm(y.astype(jnp.float32), scale, groups,
                            eps) * jax.nn.silu(z.astype(jnp.float32))
    setattr_(ssd, "gated_norm", norm_then_gate)
  elif fault == "experts_gated":
    from kf_benchmarks_tpu.parallel import expert
    # Under a name of its own: the routed path's rounds are jitted with
    # the activation's NAME as a static argument, so that no trace of the
    # right form is served to the faulty one (or the other way round).
    setattr_(expert, "ACTIVATIONS", dict(
        expert.ACTIVATIONS, self_gated=lambda u: jax.nn.silu(u) * u))
    load = mla_moe_lm.load_lm_config
    setattr_(mla_moe_lm, "load_lm_config", lambda *a, **k:
             dataclasses.replace(load(*a, **k),
                                 expert_activation="self_gated"))
  elif fault == "state_unchanged":
    def get(self):
      step = self.__dict__.get("timed_step")
      if step is None:
        return None

      def unchanged(state, *batch):
        old = jax.device_get(state.params)     # the state is donated
        new, metrics = step(state, *batch)
        shardings = jax.tree.map(lambda x: x.sharding, new.params)
        new = new.replace(params=None)         # room for the old ones
        return new.replace(params=jax.tree.map(jax.device_put, old,
                                               shardings)), metrics
      return unchanged
    # An instance attribute of the run's BenchmarkCNN: a property of the
    # class wraps whatever is assigned to it.
    setattr_(benchmark.BenchmarkCNN, "timed_step", property(
        get, lambda self, step: self.__dict__.__setitem__("timed_step",
                                                          step)))
  else:
    raise SystemExit(f"--fault {fault}: one of {', '.join(FAULTS)}")


def main():
  argv = sys.argv[1:]
  fault = "router_bf16"
  if "--fault" in argv:
    at = argv.index("--fault")
    fault = argv[at + 1]
    del argv[at:at + 2]
  plant(fault)
  sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
  import run
  return run.main(argv)


if __name__ == "__main__":
  sys.exit(main())
