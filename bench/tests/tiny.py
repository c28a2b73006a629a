"""A cell shrunk to what the CPU runs in seconds, for the tests: the same
files, configuration and traffic kinds at a toy scale. Nothing here is a
measurement."""
from __future__ import annotations

import copy

from bench import cells


def shrink(cell: cells.Cell) -> cells.Cell:
    config = copy.deepcopy(cell.config)
    config["data"].update(n_base=4000, n_train=2000, n_pool=64)
    config["build"]["nlist"] = 16
    config["build"].pop("cap", None)  # the build sizes lists to the data
    config["engine"]["nprobe"] = min(config["engine"]["nprobe"], 4)
    mix = copy.deepcopy(cell.traffic)
    if mix["kind"] == "closed":
        mix["outstanding"] = 8
    else:
        mix["rate_qps"] = 20.0
    return cell._replace(config=config, traffic=mix)
