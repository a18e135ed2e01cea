"""Cells of the benchmark cut to a size a test run can hold."""

import copy

import pytest

from bench import harness

#: per cell, the keys of its configuration's network and of its traffic
#: that a test shrinks
TINY = {
    "s5.sim": (dict(sizes=[32, 64, 64, 32]),
               dict(steps=16, check_sample=2)),
    "sdconv64.sim": (dict(in_hw=[16, 16], conv=[
        dict(channels=4, kernel=3, stride=2),
        dict(channels=8, kernel=3, stride=2)]),
        dict(steps=16, check_sample=2)),
    "s5.search": (dict(sizes=[32, 64, 64, 32]),
                  dict(steps=16, population_size=16, generations=3,
                       check_sample=1)),
}


def tiny(name: str) -> harness.Cell:
    cell = copy.copy(harness.resolve(name))
    net, traffic = TINY[name]
    cell.config = copy.deepcopy(cell.config)
    cell.config["network"].update(net)
    cell.traffic = dict(cell.traffic, **traffic)
    return cell


@pytest.fixture(params=sorted(TINY))
def tiny_cell(request):
    return tiny(request.param)
