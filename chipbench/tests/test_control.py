"""The control, the reference in bfloat16 put in the program's place, fails
each cell's limits; the program at the same size passes them."""

import pytest
from conftest import SEED, run_tiny

import harness

CELLS = ("blobs-d10.fit", "topics-d5.serve")


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_a_limit(cell):
    out, ctx = run_tiny(cell, seed=SEED + 1, seconds=1.0)
    assert out["correct"] is True, out["checks"]
    traffic = harness.load_module("traffic", ctx.cell.spec["traffic"])
    ctx.engine = getattr(ctx, "engine", None) or _Closed()
    ok, checks = harness.compare(traffic.control(ctx), ctx.cell.spec["limits"])
    assert not ok, checks


class _Closed:
    def close(self):
        pass
