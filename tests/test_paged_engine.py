"""Paged-KV engine tests: dense-engine equivalence, chunked long-prompt
prefill (no truncation), pool accounting, admission control/preemption, and
the engine bugfix regressions (truncation, max_len, max_new_tokens=1).

The smoke model + its f32 cast come from tests/harness.py //
tests/conftest.py (``gqa_model`` is session-scoped there)."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core import LayerRange
from repro.models import decode_step, init, prefill
from repro.models.paged import num_paged_layers
from repro.serving import (Engine, EngineConfig, PagedEngine, Request,
                           make_stage_engine)
from repro.serving.stage_engine import _greedy
from repro.serving.trace import TRACER

from harness import decode_rows, f32, prefill_slots, random_prompts


def _reference_greedy(cfg, params, prompt, n_tokens, max_len=64):
    """Hand-rolled prefill + decode loop (greedy)."""
    tok = jnp.asarray(prompt, jnp.int32)[None, :]
    logits, caches = prefill(cfg, params, tok, max_len=max_len)
    out = [int(jnp.argmax(logits[0]))]
    pos = jnp.asarray([len(prompt)], jnp.int32)
    for t in range(n_tokens - 1):
        logits, caches = decode_step(cfg, params,
                                     jnp.asarray([out[-1]], jnp.int32),
                                     caches, pos + t)
        out.append(int(jnp.argmax(logits[0])))
    return out


# --- equivalence -------------------------------------------------------------

def test_paged_matches_dense_engine_greedy(gqa_model):
    """Paged engine must match the dense engine token-for-token at temp 0,
    with several concurrent requests, and free every page at the end."""
    cfg, params = gqa_model
    ec = EngineConfig(max_batch=4, max_len=64, prompt_len=16)
    prompts = random_prompts(cfg, (10, 5, 16, 12, 7, 14), seed=0)

    dense = Engine(cfg, params, ec)
    paged = PagedEngine(cfg, params, ec, page_size=16)
    d_reqs = [Request(i, p, max_new_tokens=6) for i, p in enumerate(prompts)]
    p_reqs = [Request(i, p, max_new_tokens=6) for i, p in enumerate(prompts)]
    for r in d_reqs:
        dense.submit(r)
    for r in p_reqs:
        paged.submit(r)
    dense.run_until_done(max_iters=200)
    paged.run_until_done(max_iters=200)
    for dr, pr in zip(d_reqs, p_reqs):
        assert dr.done and pr.done
        assert pr.output == dr.output, (pr.request_id, pr.output, dr.output)
    assert paged.pool.used == 0


def test_paged_hybrid_stack_dense_fallback():
    """Stack mixing mamba/MoE blocks with GQA attention: paged decode for
    the attention layers + dense fallback elsewhere still matches the dense
    engine token-for-token."""
    cfg = f32(get_smoke_config("jamba_1_5_large_398b"))
    assert 0 < num_paged_layers(cfg) < cfg.num_layers  # genuinely hybrid
    params = init(cfg, jax.random.key(2))
    prompt = random_prompts(cfg, (11,), seed=1)[0]

    dense = Engine(cfg, params, EngineConfig(max_batch=2, max_len=48,
                                             prompt_len=16))
    paged = PagedEngine(cfg, params, EngineConfig(max_batch=2, max_len=48,
                                                  prompt_len=16), page_size=8)
    r1, r2 = Request(0, prompt, max_new_tokens=6), \
        Request(0, prompt, max_new_tokens=6)
    dense.submit(r1)
    paged.submit(r2)
    dense.run_until_done(50)
    paged.run_until_done(50)
    assert r2.output == r1.output
    assert paged.pool.used == 0


# --- long prompts (truncation bugfix) ---------------------------------------

def test_paged_long_prompt_not_truncated(gqa_model):
    """A prompt 3x prompt_len prefills in chunks — every token must count
    (the dense engine used to silently keep only the last prompt_len)."""
    cfg, params = gqa_model
    ec = EngineConfig(max_batch=2, max_len=64, prompt_len=16)
    prompt = (np.arange(48) * 7) % cfg.vocab_size        # 3x prompt_len
    eng = PagedEngine(cfg, params, ec, page_size=16)
    req = Request(0, prompt, max_new_tokens=5)
    eng.submit(req)
    eng.run_until_done(max_iters=50)
    assert req.done
    assert req.output == _reference_greedy(cfg, params, prompt, 5)
    assert eng.pool.used == 0


def test_dense_engine_refuses_to_truncate(gqa_model):
    """Regression: Engine._admit used to drop prompt[:-prompt_len] silently;
    it must now raise instead."""
    cfg, params = gqa_model
    eng = Engine(cfg, params, EngineConfig(max_batch=2, max_len=64,
                                           prompt_len=16))
    with pytest.raises(ValueError, match="truncate"):
        eng.submit(Request(0, np.arange(48) % cfg.vocab_size))


@pytest.mark.parametrize("engine_cls", [Engine, PagedEngine])
def test_empty_prompt_rejected(gqa_model, engine_cls):
    cfg, params = gqa_model
    eng = engine_cls(cfg, params, EngineConfig(max_batch=2, max_len=32,
                                               prompt_len=16))
    with pytest.raises(ValueError, match="empty"):
        eng.submit(Request(0, np.zeros((0,), np.int32)))


def test_paged_rejects_prompt_over_budget(gqa_model):
    cfg, params = gqa_model
    eng = PagedEngine(cfg, params, EngineConfig(max_batch=2, max_len=32,
                                                prompt_len=16))
    with pytest.raises(ValueError, match="budget"):
        eng.submit(Request(0, np.arange(40) % cfg.vocab_size))


# --- max_len enforcement (out-of-range decode bugfix) ------------------------

@pytest.mark.parametrize("engine_cls", [Engine, PagedEngine])
def test_request_terminates_at_length_budget(gqa_model, engine_cls):
    """prompt + output exceeding max_len must finish cleanly at the budget
    (positions used to grow past the cache and write out of range)."""
    cfg, params = gqa_model
    ec = EngineConfig(max_batch=2, max_len=24, prompt_len=16)
    prompt = np.arange(10) % cfg.vocab_size
    eng = engine_cls(cfg, params, ec)
    req = Request(0, prompt, max_new_tokens=1000)
    eng.submit(req)
    eng.run_until_done(max_iters=100)
    assert req.done and req.finish_reason == "length"
    # prefill emits 1 token at pos S, decode fills positions S..max_len-1
    assert len(req.output) == ec.max_len - len(prompt) + 1
    assert not eng.active.any()
    # budget-terminated greedy output must equal an unbounded reference's
    # first tokens (i.e. termination didn't corrupt the cache mid-stream)
    ref = _reference_greedy(cfg, params, prompt, len(req.output), max_len=64)
    assert req.output == ref


# --- first-token bookkeeping (max_new_tokens=1 / eos bugfix) -----------------

@pytest.mark.parametrize("engine_cls", [Engine, PagedEngine])
def test_single_token_request_never_seats(gqa_model, engine_cls):
    """A max_new_tokens=1 request is fully served by prefill: it must not
    occupy a slot nor decode an extra token."""
    cfg, params = gqa_model
    eng = engine_cls(cfg, params, EngineConfig(max_batch=2, max_len=64,
                                               prompt_len=16))
    req = Request(0, np.arange(8) % cfg.vocab_size, max_new_tokens=1)
    eng.submit(req)
    produced = eng.step()
    assert req.done and len(req.output) == 1
    assert produced == 0 and not eng.active.any()
    if engine_cls is PagedEngine:
        assert eng.pool.used == 0


def test_eos_on_first_token_finishes_immediately(gqa_model):
    cfg, params = gqa_model
    prompt = np.arange(8) % cfg.vocab_size
    # find what greedy emits first, then make that the eos token
    first = _reference_greedy(cfg, params, prompt, 1)[0]
    eng = PagedEngine(cfg, params, EngineConfig(max_batch=2, max_len=64,
                                                prompt_len=16,
                                                eos_token=first))
    req = Request(0, prompt, max_new_tokens=32)
    eng.submit(req)
    eng.step()
    assert req.done and req.output == [first]
    assert req.finish_reason == "stop"
    assert eng.pool.used == 0


# --- pool admission control / preemption -------------------------------------

def test_pool_admission_blocks_then_completes(gqa_model):
    """A pool holding ~2 requests' pages with 4 slots must serve 6 requests
    to completion by blocking admission, never overflowing."""
    cfg, params = gqa_model
    ec = EngineConfig(max_batch=4, max_len=32, prompt_len=16)
    L = num_paged_layers(cfg)
    pool_pages = 1 + 2 * (32 // 16) * L      # two full budgets + scratch
    eng = PagedEngine(cfg, params, ec, num_pages=pool_pages, page_size=16)
    rng = np.random.RandomState(3)
    reqs = [Request(i, rng.randint(0, cfg.vocab_size, size=(9,)),
                    max_new_tokens=6) for i in range(6)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done(max_iters=500)
    for r in reqs:
        assert r.done and len(r.output) == 6
    assert eng.pool.used == 0


def test_pool_preempts_newest_when_exhausted(gqa_model):
    """With a pool that fits exactly one full-budget request, concurrent
    decodes must preempt (recompute) rather than overflow — and everyone
    still finishes with the right number of tokens."""
    cfg, params = gqa_model
    ec = EngineConfig(max_batch=4, max_len=32, prompt_len=16)
    L = num_paged_layers(cfg)
    prompts = [np.random.RandomState(4).randint(0, cfg.vocab_size, size=(10,))
               for _ in range(4)]
    eng = PagedEngine(cfg, params, ec, num_pages=1 + (32 // 16) * L,
                      page_size=16)
    reqs = [Request(i, p, max_new_tokens=8) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done(max_iters=500)
    assert any(r.preemptions > 0 for r in reqs)
    # recompute-on-readmit keeps already-generated tokens: greedy output
    # must equal a run with an unconstrained pool
    calm = PagedEngine(cfg, params, ec, page_size=16)
    calm_reqs = [Request(i, p, max_new_tokens=8)
                 for i, p in enumerate(prompts)]
    for r in calm_reqs:
        calm.submit(r)
    calm.run_until_done(max_iters=500)
    for r, cr in zip(reqs, calm_reqs):
        assert r.done and len(r.output) == 8
        assert r.output == cr.output, (r.request_id, r.output, cr.output)
    assert eng.pool.used == 0


def test_pool_too_small_for_one_request_raises(gqa_model):
    cfg, params = gqa_model
    with pytest.raises(ValueError, match="cannot hold"):
        PagedEngine(cfg, params, EngineConfig(max_batch=2, max_len=64,
                                              prompt_len=16), num_pages=3)


# --- greedy tokens taken on the device ----------------------------------------

# small widths of the two benchmark configurations' attention, at the served
# dtype (bf16 logits): MHA as OLMo-1B, GQA at G = 3 as SmolLM-360M
ATTN_SHAPES = {
    "mha": lambda: get_smoke_config("olmo_1b"),
    "gqa3": lambda: dataclasses.replace(get_smoke_config("smollm_360m"),
                                        d_model=96, num_heads=6,
                                        num_kv_heads=2),
}
STAGE_EC = EngineConfig(max_batch=3, max_len=48, prompt_len=16)
B = STAGE_EC.max_batch + 1          # rows of every step, scratch included
STEPS = 3


def _seated_engine(shape: str, paged: bool, rng_seed: int = 0):
    """A whole-model stage engine with three prompts prefilled."""
    cfg = ATTN_SHAPES[shape]()
    assert cfg.num_heads // cfg.num_kv_heads == {"mha": 1, "gqa3": 3}[shape]
    eng = make_stage_engine(cfg, init(cfg, jax.random.key(0)),
                            LayerRange(0, cfg.num_layers), STAGE_EC,
                            paged=paged, rng_seed=rng_seed)
    # whole chunks, so prefill is one program
    prompts = random_prompts(cfg, (32, 16, 16), seed=1)
    toks = prefill_slots(eng, prompts, paged=paged,
                         chunk=STAGE_EC.prompt_len,
                         budget=max(map(len, prompts)) + STEPS)
    return cfg, eng, prompts, toks


def _last_fetch():
    return next(s for s in reversed(TRACER.spans())
                if s.name == "helix.engine.fetch")


def _counter(name: str) -> int:
    return TRACER.counters.get(name, 0)


@pytest.mark.parametrize("shape", sorted(ATTN_SHAPES))
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_decode_token_is_host_argmax_of_logits(shape, paged):
    """The final stage's ``DecodeOut.token`` is the host's ``np.argmax``
    of the same step's logits, at the float64 the host sampled in."""
    _, eng, prompts, toks = _seated_engine(shape, paged)
    for t in range(STEPS):
        outs = decode_rows(eng, prompts, toks, t, [True] * len(prompts))
        for o in outs:
            assert o.h is None and o.logits.dtype == jnp.bfloat16
            assert o.token == int(np.argmax(np.asarray(o.logits,
                                                       np.float64)))
        toks = [o.token for o in outs]


def test_greedy_ids_take_the_first_maximum():
    logits = np.asarray([[1, 3, 3, 0], [2, 2, 2, 2], [-1, -5, -1, 7],
                         [0, -2, 0, -2]], jnp.bfloat16)
    ids = np.asarray(jax.jit(_greedy)(jnp.asarray(logits)))
    assert ids.dtype == np.int32
    assert ids.tolist() == [1, 0, 3, 0]
    assert ids.tolist() == np.argmax(logits.astype(np.float64), -1).tolist()


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_greedy_step_copies_ids_only(paged):
    """A step of greedy rows copies ``B x 4`` bytes of ids and no logits;
    a row that asks brings the (B, V) logits back, to it alone; and either
    way the step is the one program the first step compiled."""
    cfg, eng, prompts, toks = _seated_engine("gqa3", paged)
    decode_rows(eng, prompts, toks, 0, [True] * 3)
    compiles = _counter("compiles")
    rows = _counter("decode.logits_rows")
    outs = decode_rows(eng, prompts, toks, 1, [False] * 3)
    assert _last_fetch().attrs["bytes"] == B * 4
    assert _counter("decode.logits_rows") == rows
    assert all(o.logits is None and o.h is None and type(o.token) is int
               for o in outs)
    outs = decode_rows(eng, prompts, [o.token for o in outs], 2,
                       [False, True, False])
    assert _last_fetch().attrs["bytes"] == B * cfg.vocab_size * 2 + B * 4
    assert _counter("decode.logits_rows") == rows + B
    assert [o.logits is not None for o in outs] == [False, True, False]
    assert _counter("compiles") == compiles


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_mixed_batch_samples_as_before(paged):
    """Rows at temperature > 0 in a batch with greedy rows draw the same
    tokens from the same seeded RNG as when every row's logits came to the
    host and every row went through ``sample()``."""
    temps = (0.0, 0.8, 1.3)
    _, new, prompts, toks = _seated_engine("mha", paged, rng_seed=7)
    _, old, _, _ = _seated_engine("mha", paged, rng_seed=7)
    got, want, off_argmax = [toks], [toks], 0
    for t in range(STEPS):
        outs = decode_rows(new, prompts, got[-1], t, [T > 0 for T in temps])
        got.append([o.token if T <= 0 else new.sample(o.logits, T)
                    for o, T in zip(outs, temps)])
        off_argmax += sum(g != o.token for g, o in zip(got[-1], outs))
        outs = decode_rows(old, prompts, want[-1], t, [True] * 3)
        want.append([old.sample(o.logits, T) for o, T in zip(outs, temps)])
    assert got == want
    assert off_argmax > 0        # the sampled rows did draw from the RNG
