"""What decides every PR, held by tier-1: `benchmarks/tests/test_correct.py`
loaded from where it stands and its tests collected here, as they are.

- `test_control_is_not_correct`: each control (the plain reference with one
  thing lowered under what the configuration states) comes out not correct;
- `test_altered_answer_is_not_correct`: a whole run of a cell on the CPU at
  a small size is `correct`, and the same run with one count altered where
  the broker produces it is not;
- `test_selfcheck`: the benchmark's files and its recorded trace pass
  `benchmarks/selfcheck.py`.

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


@pytest.fixture(autouse=True)
def _keep_metadata_state():
    """A run of a cell installs the configuration's node as the process's
    metadata state; the files that run after this one on the same worker
    get back the one they would have found."""
    old = mdstate.global_manager()
    yield
    mdstate.set_global_manager(old)
