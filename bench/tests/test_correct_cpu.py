"""``correct`` comes out false for each cell's control (the plain
reference put in the program's place, in a lower precision than the
configuration states) and for each fault planted under the window."""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT), str(pathlib.Path(__file__).parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run  # noqa: E402
from test_cells_cpu import tiny_run, x64  # noqa: E402,F401


@pytest.mark.parametrize("workload,control", [
    ("parsec_si.panel_reuse", "f32"),
    ("parsec_si.panel_reuse", "bf16"),
    ("parsec_si.fresh_blocks", "f32"),
    ("lsms_kkr_c64.atoms112", "bf16"),
])
def test_control_is_not_correct(workload, control):
    app = run.load_module("apps", run.cell(workload)["config"])
    line = tiny_run(workload,
                    replace=lambda st: app.control(st, control))
    assert not line["correct"], line["checks"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def _stale(fn):
    """The step returns its state unchanged: the first answer again."""
    first = []

    def broken(*args):
        out = np.asarray(fn(*args))
        if not first:
            first.append(out)
        return first[0]
    return broken


def _half(fn):
    """Half of the batch (the block's states, the right-hand sides)
    left out: those columns come back as zeros."""
    def broken(*args):
        out = np.array(fn(*args))
        out[:, out.shape[1] // 2:] = 0
        return out
    return broken


def _altered(fn):
    """One answer altered where it is produced, by a part in ten
    thousand of the largest entry: a thousand units in the last place
    of complex64, far more of fp64."""
    def broken(*args):
        out = np.array(fn(*args))
        out[0, 0] += 1e-4 * np.max(np.abs(out))
        return out
    return broken


#: the callable of each configuration's step that produces its answers
PRODUCER = {"parsec_si": "product", "lsms_kkr_c64": "solve"}


@pytest.mark.parametrize("fault", [_stale, _half, _altered],
                         ids=["unchanged", "half_batch", "altered"])
@pytest.mark.parametrize("workload", ["parsec_si.panel_reuse",
                                      "lsms_kkr_c64.atoms112"])
def test_fault_under_the_window_is_not_correct(workload, fault):
    name = PRODUCER[run.cell(workload)["config"]]
    line = tiny_run(workload,
                    replace=lambda st: {name: fault(getattr(st, name))})
    assert not line["correct"], line["checks"]
    assert line["failed"] > 0
