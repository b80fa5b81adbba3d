"""The control, and the timed path broken underneath: each makes a whole
run (CPU, smoke size) come out not correct.  The faults a served cell can
have: a token altered where it is produced, and a decode step that leaves
the KV state unchanged.  (A batch mean and a cross-chip exchange do not
exist in these one-chip serving cells.)"""
import numpy as np
import pytest

import calibrate
import run
from smoke import SECONDS, SEED, smoke_cell, steer_to_cpu


def _altered_token(monkeypatch):
    from repro.serving.stage_engine import _StageEngineBase
    vocab = smoke_cell("olmo-chat").conf["vocab_size"]
    monkeypatch.setattr(_StageEngineBase, "sample",
                        lambda self, logits, temperature:
                        (int(np.argmax(logits)) + 1) % vocab)


def _kv_unchanged(monkeypatch):
    from repro.serving.stage_engine import PagedStageEngine
    step = PagedStageEngine._decode_step

    def stale(self, items):
        k, v = self.pool.k, self.pool.v
        out = step(self, items)
        self.pool.k, self.pool.v = k, v     # CPU: nothing was donated
        return out
    monkeypatch.setattr(PagedStageEngine, "_decode_step", stale)


@pytest.mark.parametrize("fault", [_altered_token, _kv_unchanged],
                         ids=["token_altered", "kv_unchanged"])
def test_fault_is_not_correct(monkeypatch, fault):
    steer_to_cpu(monkeypatch)
    fault(monkeypatch)
    res = run.run(smoke_cell("olmo-chat"), SEED, SECONDS, False)
    gap = res["check"]["max_logit_gap"]
    assert res["correct"] is False, gap
    assert gap["value"] > 2 * gap["limit"]


def test_control_is_not_correct(monkeypatch):
    """The reference in the program's place at fp8 weights and bf16
    activations reads above the limit that the program reads below."""
    steer_to_cpu(monkeypatch)
    cell = smoke_cell("olmo-batch")
    res = run.run(cell, SEED, SECONDS, False, keep_check_state=True)
    state = res.pop("_state")
    gap = res["check"]["max_logit_gap"]
    assert res["correct"] is True
    assert calibrate.control_gap(cell, state) > gap["limit"] > gap["value"]
