"""The benchmark's ``counter_sum_scaled`` and ``idle_overlap`` readers in
tier-1: the cases of perfbench/tests/test_host_spans.py (planted counters
and a planted trace, no jax), collected here because perfbench's own tests
are run by hand."""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "perfbench", "tests", "test_host_spans.py")
_spec = importlib.util.spec_from_file_location("perfbench_host_spans", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)
globals().update({name: obj for name, obj in vars(_cases).items()
                  if name.startswith("test_") or name == "planted"})
