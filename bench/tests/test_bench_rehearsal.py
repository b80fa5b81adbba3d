"""A CPU rehearsal of one whole run of each cell at smoke size: set-up, the
served window through the front door and the load generator, the
reference check, and the result line."""
import pytest

import run
from smoke import SECONDS, SEED, smoke_cell, steer_to_cpu


@pytest.mark.parametrize("workload,traced", [("olmo-chat", True),
                                             ("olmo-batch", False)])
def test_one_run(monkeypatch, workload, traced):
    steer_to_cpu(monkeypatch)
    cell = smoke_cell(workload)
    res = run.run(cell, SEED, SECONDS, traced)
    assert list(res)[-1] == "check"
    assert res["correct"] is True, res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    want = cell.per_layer if traced else cell.end_to_end
    got = set(res["metrics"])
    # no TPU plane in a CPU trace: the device metrics stay silent
    silent = {m["name"] for m in want if m["source"] == "device_trace"}
    assert got == {m["name"] for m in want} - silent
    for v in res["metrics"].values():
        assert v["value"] > 0
