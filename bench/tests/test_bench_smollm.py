"""SmolLM-360M's served program against the float32 Llama reference
(``references/llama.py``), at a small size that keeps its heads: 15 query
heads over 5 kv heads of 64 (G = 3), so a page of 16 tokens is 40 stored
rows of two records each.  Then one whole ``smollm-chat`` run at that size,
as ``test_bench_rehearsal.py`` rehearses the OLMo cells."""
import json

import numpy as np
import pytest

import run
from smoke import SECONDS, SEED, smoke_cell, steer_to_cpu

ARCH = run.load_module(run.BENCH / "models" / "llama.py")
REF = run.load_module(run.BENCH / "references" / "llama.py")
SMALL = dict(num_hidden_layers=2, intermediate_size=256, vocab_size=512,
             max_position_embeddings=256)
# bf16 weights, activations and KV pages against the float32 reference on
# the same weights: 0.026-0.037 over seeds 1-3 at this size, where logits
# reach 2.4-3.2 and the control (fp8 weights, bf16 activations) reads
# 0.34-0.44.  0.1 leaves the program ~3x room and the control ~3x over.
LOGIT_TOL = 0.1
PROMPTS = (100, 37)       # chunks of 64 end mid-page; one row ends early
STEPS = 10


def small_conf():
    conf = json.loads((run.BENCH / "configs" / "smollm-360m.json")
                      .read_text())
    return dict(conf, **SMALL)


def served_logits(conf, w, seed):
    """Logits of chunked prefill (its last prompt token) and of ``STEPS``
    greedy decode steps of two rows batched together, through the paged
    stage engine over the whole model; and the served sequences."""
    from repro.core.placement import LayerRange
    from repro.serving import EngineConfig
    from repro.serving.stage_engine import DecodeItem, PagedStageEngine
    cfg = ARCH.program_config(conf)
    ec = EngineConfig(max_batch=2, max_len=256, prompt_len=64, eos_token=-1)
    eng = PagedStageEngine(cfg, w, LayerRange(0, cfg.num_layers), ec,
                           page_size=conf["serving"]["page_size"])
    rng = np.random.RandomState(seed)
    seqs = [list(rng.randint(0, conf["vocab_size"], n)) for n in PROMPTS]
    got = [{} for _ in seqs]
    slots = [eng.alloc_slot(i) for i in range(len(seqs))]
    for s, q, g in zip(slots, seqs, got):
        assert eng.ensure(s, len(q) + STEPS)
        for off in range(0, len(q), 64):
            out = eng.prefill_chunk(s, q[off:off + 64], 0, off)
        g[len(q) - 1] = np.asarray(out)
    for _ in range(STEPS):
        items = [DecodeItem(slot=s, pos=len(q), entry=0,
                            token=int(np.argmax(g[len(q) - 1])))
                 for s, q, g in zip(slots, seqs, got)]
        for q, it in zip(seqs, items):
            q.append(it.token)
        for q, g, o in zip(seqs, got, eng.decode_stage(items)):
            g[len(q) - 1] = o.logits
    tokens = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for i, q in enumerate(seqs):
        tokens[i, :len(q)] = q
    return got, tokens


def widest(got, logits):
    return max(float(np.abs(g[p] - logits[i, p]).max())
               for i, g in enumerate(got) for p in g)


@pytest.mark.parametrize("seed", [1, 2])
def test_served_logits_match_reference(seed):
    conf = small_conf()
    cfg = ARCH.program_config(conf)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim) == \
        (15, 5, 64)
    w = ARCH.make_weights(conf, seed)
    got, tokens = served_logits(conf, w, seed)
    assert sum(len(g) for g in got) == len(PROMPTS) * (1 + STEPS)
    ref = REF.logits(conf, w, tokens)
    assert widest(got, ref) < LOGIT_TOL
    # the tolerance is tight enough: the control, and a model whose
    # RMSNorm gains were dropped, each read over it
    assert widest(got, REF.logits(conf, w, tokens, control=True)) \
        > LOGIT_TOL
    no_gain = {**w, "final_norm": {"scale": 0 * w["final_norm"]["scale"]}}
    assert widest(got, REF.logits(conf, no_gain, tokens)) > LOGIT_TOL


def test_one_smollm_chat_run(monkeypatch):
    steer_to_cpu(monkeypatch)
    # the harness frees every live array after the window; in a test
    # worker that would delete session fixtures later tests still use
    monkeypatch.setattr(run, "free_device_memory", lambda: None)
    cell = smoke_cell("smollm-chat")
    cell.conf = dict(cell.conf, hidden_size=960, num_attention_heads=15,
                     num_key_value_heads=5, **SMALL)
    res = run.run(cell, SEED, SECONDS, True)
    assert res["correct"] is True, res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    # no TPU plane in a CPU trace: the device metrics stay silent
    want = {m["name"] for m in cell.per_layer
            if m["source"] != "device_trace"}
    assert set(res["metrics"]) == want
    rows = res["metrics"]["decode_rows_mean.smollm-chat"]["value"]
    assert 1 <= rows <= cell.conf["serving"]["max_batch"]
