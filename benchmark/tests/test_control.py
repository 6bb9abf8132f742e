"""The control (the reference one precision down, in the program's
place) has to come out as not correct, in every cell."""

import json
import os

import control
import pytest
import run

CELLS = [w["name"] for w in run.load_json(run.ROOT, "BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails(workload, seed):
    res = control.control_cell(workload, seed, requests=16, rehearse=True)
    assert res["fails"], json.dumps(res)
    for c in res["classes"].values():
        # every single request of the control lies over the limit
        assert c["least"] > 3 * c["limit"], json.dumps(res)


def test_bf16_rounding():
    lowprec = run.load_module(".", "lowprec")
    import numpy as np

    x = np.array([1.0, 1.00390625, 1.005859375, 3.14159265, 43200.0])
    got = lowprec.to_bf16(x).astype(np.float64)
    # 8 bits of mantissa: 1 + 2^-8 ties to even (1.0); the next rounds up
    assert got.tolist() == [1.0, 1.0, 1.0078125, 3.140625, 43264.0]
