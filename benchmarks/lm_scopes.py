"""The device time of a language model's own named scopes, and the
kernels launched under them, from the traced run's file: the shared
reader of the ``*_attention_ms``, ``attention_core*_ms``, ``moe_*_ms``
and ``lm_head_ms`` metrics and of the roofline shares
(``benchmarks/layer_metrics/``).

The program (``kf_benchmarks_tpu/models/mla_moe_lm.py``) names scopes
INSIDE the step's ``forward`` scope; an operation's ``op_name`` carries
them as path components through remat and the transpose
(``jvp(mla_attention)``, ``transpose(jvp(mla_attention))``), so a scope's
time here is forward, the forward that remat repeats, and backward
together. They are components other than the four the step itself names,
so ``spans.py``'s six parts and ``check_scopes`` do not see them.

``scope_ms(run, file, include, exclude)``: milliseconds a step of leaf
operations whose ``op_name`` has a component of scope ``include`` and
none of ``exclude``, over the steady window ``spans.py`` uses, mean over
the chips. None in an untraced run, where no file was written, or where
the trace names no such scope at all (a program without it, as the
parent of the PR that added it: the metric is then left out of the
line, and nothing raises).

``kernel_launches(run, file, include)``: of the same leaf operations
under ``include``, the custom calls (a Pallas kernel is one), counted:
``{kernel: launches a step}``, the kernel being the instruction's name
without its number (``gmm``, ``splash_mha_fwd_residuals``). What a
roofline share's numerator counts passes by: how often remat, a
``custom_vjp``'s own recomputation and XLA's merging of equal
computations make a kernel run is decided when the step is compiled,
and only the trace says it. None where ``scope_ms`` is None.
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks import harness
from benchmarks import spans
from benchmarks import xplane

# Path components of an op_name that are one of these scopes, however
# many transforms wrap them.
COMPONENT_RE = re.compile(r"^(?:[a-z_]+\()*([A-Za-z_][A-Za-z0-9_]*)\)*$")

# A Pallas kernel in the device's line of operations.
KERNEL_OPCODE = "custom-call"

_cache: Dict[Tuple[str, float], List[Tuple[int, List]]] = {}


def components(op_name: str) -> frozenset:
  out = set()
  for component in op_name.split("/"):
    m = COMPONENT_RE.match(component)
    if m:
      out.add(m.group(1))
  return frozenset(out)


def _leaves(path: str):
  """Per device: (whole steps, [(seconds, components, kernel)] of the
  leaf operations inside the steady window); ``kernel`` is a custom
  call's name without its number, None for any other operation."""
  key = (path, os.path.getmtime(path))
  if key in _cache:
    return _cache[key]
  _cache.clear()
  trace = spans.load(path)
  names = spans.op_names(path)
  out = []
  for dev in trace.devices:
    window = spans._window(dev, xplane.SKIP_STEPS)
    if window is None:
      continue
    lo, hi, steps = window
    by_label = {xplane.parse_op(event)[0]: components(op_name)
                for plane, table in names.items()
                if xplane.DEVICE_PLANE_RE.match(plane) and int(
                    xplane.DEVICE_PLANE_RE.match(plane).group(1)) ==
                dev.device
                for event, op_name in table.items()}
    inside = [dataclasses.replace(e, start=max(e.start, lo),
                                  end=min(e.end, hi))
              for e in dev.ops if min(e.end, hi) > max(e.start, lo)]
    leaves, _ = xplane.split_leaves(inside)
    out.append((steps, [
        (e.end - e.start, by_label.get(e.name, frozenset()),
         re.split(r"[. ]", e.name, maxsplit=1)[0]
         if e.opcode == KERNEL_OPCODE else None) for e in leaves]))
  _cache[key] = out
  return out


def _under(run, metric_file: str, include: str, exclude: Sequence[str]):
  """Per device (whole steps, the leaves under ``include`` and none of
  ``exclude``); None in an untraced run, without a file, or where no
  leaf names ``include`` at all."""
  if run.reduction is None:
    return None
  path = xplane.find_xplane(os.path.join(
      spans.root_of(metric_file), harness.TRACE_DIR, run.cell["name"]))
  if path is None:
    return None
  per_device = [(steps, [leaf for leaf in leaves if include in leaf[1]])
                for steps, leaves in _leaves(path)]
  if not any(leaves for _, leaves in per_device):
    return None
  return [(steps, [leaf for leaf in leaves
                   if not any(x in leaf[1] for x in exclude)])
          for steps, leaves in per_device]


def scope_ms(run, metric_file: str, include: str,
             exclude: Sequence[str] = ()) -> Optional[float]:
  per_device = _under(run, metric_file, include, exclude)
  if per_device is None:
    return None
  return sum(1e3 * sum(leaf[0] for leaf in leaves) / steps
             for steps, leaves in per_device) / len(per_device)


def kernel_launches(run, metric_file: str, include: str
                    ) -> Optional[Dict[str, float]]:
  per_device = _under(run, metric_file, include, ())
  if per_device is None:
    return None
  out: Dict[str, float] = {}
  for steps, leaves in per_device:
    for _, _, kernel in leaves:
      if kernel is not None:
        out[kernel] = out.get(kernel, 0.0) + 1.0 / (steps * len(per_device))
  return out
