"""CPU tests of the benchmark: cells whose driver ``tests/tiny.py`` does
not cut are cut by ``tests/tiny_moe.py``, so that every cell runs at a
tiny size in ``test_controls`` and ``test_runs``."""
from chipbench.tests import tiny, tiny_moe

_cell = tiny.cell


def _tiny_cell(name: str):
    cell, config, traffic = _cell(name)
    if cell["driver"] == "train_moe":
        tiny_moe.cut(cell, config, traffic)
    return cell, config, traffic


tiny.cell = _tiny_cell
