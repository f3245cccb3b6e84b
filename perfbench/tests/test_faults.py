"""Each fault a cell can have, planted under the timed path, makes the run
come out not correct; the same run without it is correct."""
import os
import subprocess
import sys

import pytest

from conftest import REPO, run_cell, tiny


def _faulted(name, driver):
    import jax
    from perfbench import faults
    jax.clear_caches()
    return faults.FAULTS[driver][name]()


@pytest.mark.parametrize("cell", ["covtype.ca32", "internlm2.decode_sat"])
def test_sound_run_is_correct(cell):
    import jax
    jax.clear_caches()
    res = run_cell(tiny(cell))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_solve_fault_is_caught(fault):
    undo = _faulted(fault, "solve")
    try:
        res = run_cell(tiny("covtype.ca32"))
    finally:
        undo()
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_serve_fault_is_caught(fault):
    undo = _faulted(fault, "serve")
    try:
        res = run_cell(tiny("internlm2.decode_sat"))
    finally:
        undo()
    assert not res["correct"], res["checks"]


FOUR = """
import sys, json
sys.path[:0] = [{repo!r}, {tests!r}]
from conftest import tiny, run_cell
from perfbench import faults
ld = tiny("covtype.ca32")
ld["cell"] = dict(ld["cell"], chips=4)
undo = faults.FAULTS["solve"]["{fault}"]() if "{fault}" else None
print("RESULT", json.dumps(run_cell(ld)["correct"]))
"""


@pytest.mark.parametrize("fault", ["", "no_exchange"])
def test_four_chip_exchange(fault):
    """The sharded solve on four CPU devices: correct, and not correct
    without its all-reduce."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR.format(repo=str(REPO), tests=os.path.dirname(__file__),
                       fault=fault)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")]
    assert line, out.stderr[-3000:]
    assert line[0] == ("RESULT true" if not fault else "RESULT false")
