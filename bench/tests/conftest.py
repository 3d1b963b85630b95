"""CPU tests of the benchmark at tiny sizes (run by hand:
``python -m pytest bench/tests -q``).  The harness runs here on the CPU
with the program's XLA paths; every cell's configuration and traffic are
shrunk in scale, never in kind."""
import copy
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import pytest  # noqa: E402

from harness.common import Cell  # noqa: E402


def tiny_cell(workload: str) -> Cell:
    """The named cell at a size the CPU runs in seconds."""
    cell = copy.deepcopy(Cell(workload))
    cfg = cell.config
    cfg["model"]["n_nodes"] = 4
    cfg["dataset"].update(n_train=64, n_test=32, t_min=2, t_max=10)
    cell.traffic.update(slots=8, warmup_steps=6, check={"streams": 4},
                        session={"utterances_per_class": 2})
    cell.traffic["arrivals"] = dict(cell.traffic["arrivals"], sessions=10)
    return cell


@pytest.fixture
def cpu():
    import jax
    return jax.devices("cpu")[:1]
