"""What decides every PR, held by tier-1: `benchmarks/tests/test_correct.py`
loaded from where it stands and its tests collected here, as they are.

- `test_control_is_not_correct`: each control (the plain reference with one
  thing lowered under what the configuration states) comes out not correct;
- `test_altered_answer_is_not_correct`: a whole run of a cell on the CPU at
  a small size is `correct`, and the same run with one count altered where
  the broker produces it is not;
- `test_selfcheck`: the benchmark's files and its recorded trace pass
  `benchmarks/selfcheck.py`, and each function of that module's `HARNESS`;
- the five `HARNESS` functions beside `files_and_recorded_trace`, by name
  (each takes only the fixtures it names, so pytest collects it as it
  stands): a `benchmark` PR can then turn `HARNESS` into plain `test_*`
  functions and drop its dispatcher without tier-1 losing a case.

A control that passes, a planted fault that `correct` lets through, or a
benchmark file that breaks the contract's rules fails tier-1.  Nothing under
`benchmarks/` is copied or edited: that module runs as the driver's
checkout has it.
"""
import importlib.util
import os

import pytest

from pixie_tpu.metadata import state as mdstate

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "benchmarks_test_correct",
    os.path.join(ROOT, "benchmarks", "tests", "test_correct.py"))
_correct = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_correct)

test_control_is_not_correct = _correct.test_control_is_not_correct
test_altered_answer_is_not_correct = _correct.test_altered_answer_is_not_correct
test_selfcheck = _correct.test_selfcheck
test_readers_of_a_window_served_off_the_chip = (
    _correct.readers_of_a_window_served_off_the_chip)
test_trace_without_a_device_plane = _correct.trace_without_a_device_plane
test_probes_by_model_key = _correct.probes_by_model_key
test_arm_flips_by_model_key = _correct.arm_flips_by_model_key
test_chips_reach_the_agent = _correct.chips_reach_the_agent


@pytest.fixture(autouse=True)
def _keep_metadata_state():
    """A run of a cell installs the configuration's node as the process's
    metadata state; the files that run after this one on the same worker
    get back the one they would have found."""
    old = mdstate.global_manager()
    yield
    mdstate.set_global_manager(old)
