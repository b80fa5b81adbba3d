"""ClusterRuntime: execute IWRR pipelines across per-node stage engines.

This is the execution plane the paper's runtime scheduling (§4) assumes: the
MILP places layer slices on nodes, max-flow IWRR walks per-request pipelines,
and *this* module actually runs them — each node owns a stage engine over its
assigned ``LayerRange``, activations hop between nodes through a pluggable
``Transport``, and every node continuously batches whatever stage-work (from
any request, entering at any layer) is resident each iteration.

Event loop: a virtual-clock heap of deliveries.  Prefill hops execute inline
as they arrive (per-request; chunked across stages for all-paged stacks);
decode inputs accumulate in per-node inboxes and run as batched
``decode_stage`` calls per node per iteration — per-node continuous batching.

Pipelined decode (the steady state the paper's max-flow bound §4 assumes):
each request carries an in-flight window of up to ``max_inflight`` decode
passes that are launched but not yet confirmed by the coordinator.  After
sampling token t, the *final stage* speculatively launches the pass for
token t+1 straight to stage 0 — one hop instead of the two-hop
final->coordinator->stage-0 round trip — while token t travels back.  The
coordinator confirms tokens strictly in order (out-of-order arrivals are
buffered per request), applies the stop rules (eos / max_new_tokens /
max_len), and cancels any speculative in-flight passes on completion,
preemption, or failover by bumping the job epoch, which every in-flight
delivery checks.  Launching reserves KV for the new position on *every*
stage node up front, so a mid-pipeline token never lands on an exhausted
pool.  Decode stays autoregressive: pass t+1 exists only once pass t left
the final stage, so a single pass per request is ever inside the stages and
token t+1 always attends to token t's cache write (the stage engine rejects
duplicate-slot batches as the invariant check).  ``max_inflight=1``
degenerates to the classic one-outstanding-token walk (final stage waits
for the coordinator).

Memory: admission takes a slot (and, paged, the prompt's pages) on *every*
stage node up front; completion and preemption release KV on every node of
the pipeline.  When a pool runs dry mid-decode the newest resident request is
preempted pipeline-wide (recompute-on-readmit keeps its generated tokens).

Scheduler feedback: after every iteration the runtime writes each node's true
pool occupancy into the scheduler's ``KVEstimator`` (``sync``), and installs
real pool capacities at startup — IWRR masking reflects actual paged usage
rather than arrival-time reservations drifting from reality.

Routing: every admitted job carries a ``Route`` (prefill pipeline, decode
pipeline, KV handoffs).  With ``transport.direct_links`` the runtime passes
forward specs (``fwd=(dst_node, tag)``) to forward-capable engines, so stage
workers push activation frames straight to the next stage's worker and only
a ``StagedRef`` (and sampled tokens) return to the coordinator — k+1
transport hops per decode token instead of the star topology's 2k.  Both
transports keep per-(src, dst) hop/byte counters surfaced via ``describe()``.

Disaggregation: a placement whose ``meta["roles"]`` tags nodes
prefill/decode/mixed splits into per-role sub-placements, each re-planned
with max-flow into its own scheduler; prompt passes run on the prefill
replica, then each prefill stage's KV (paged: gathered pages + int8 scales,
verbatim) ships to the decode nodes that need it (``export_kv`` →
``import_kv``, over peer links when available).  Decode launches gate on
``kv_pending`` draining; prefill-only slots are released once their
handoffs land.

Speculative decoding (``draft_cfg``/``spec_tokens``): a small draft model
sharing the target's vocab lives AT the coordinator (a dense full-model
``StageEngine``).  Each round the draft proposes γ tokens autoregressively;
the target verifies all γ+1 positions in ONE pass through the decode
pipeline (the stage engines run it as position-ordered sub-batches, so the
KV write history — including int8 page requantization — is byte-identical
to γ+1 ordinary decode steps).  The final stage returns the greedy argmax
vector; the coordinator accepts the longest matching draft prefix, confirms
those tokens in order (plus the bonus token at full acceptance), and on the
first mismatch bumps the job epoch (extending the PR 4 ``cancelled_inflight``
path — straggling duplicates of the dead pass cannot decode after the
rollback) and synchronously rolls every decode stage node back to the
accepted prefix (``rollback`` RPC: page-frontier truncation + int8 frontier-
page restore).  Greedy speculative output is byte-identical to
non-speculative greedy for ANY draft — acceptance rate only changes speed.
Speculation requires ``temperature <= 0``; other requests (and requests
that find the draft's slots full) serve non-speculatively.  Spec jobs keep
exactly one verify pass in flight and launch only from the coordinator
(the draft lives there), so they compose with ``max_inflight`` windows,
disaggregated prefill (launches stay gated on ``kv_pending``) and failover
unchanged.

Failover: ``fail_node`` drops a node's engine and requeues every in-flight
request whose route crossed it; after the planner replans, ``apply_plan``
rebuilds engines whose slices changed, swaps IWRR weights
(``update_weights`` when the placement survived, a fresh scheduler
otherwise), and the requeued requests re-prefill (prompt + generated
tokens) on fresh routes.  A role-less replacement plan drops the runtime
back to mixed (one unified scheduler, no handoffs).
"""
from __future__ import annotations

import dataclasses
import heapq
import os
import queue as _queue
import socket as _socket
import subprocess
import sys
import threading
import time
from collections import defaultdict, deque
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import jax
import numpy as np

from ..configs.base import ModelConfig
from ..core.cluster import COORDINATOR
from ..core.placement import LayerRange
from ..models.paged import all_blocks_paged
from ..models.stage import stage_num_paged_layers
from .engine import EngineConfig, Request
from .kv_pool import full_rectangle_pages, pages_for_vram
from .stage_engine import (DecodeItem, PagedStageEngine, StageEngine,
                           make_stage_engine)
from .trace import TRACER
from .transport import (RemoteStageEngine, SocketTransport, WorkerChannel,
                        WorkerDied)


# ---------------------------------------------------------------------------
# Transport
# ---------------------------------------------------------------------------

class Transport:
    """Moves stage payloads (activations / token ids) between nodes.

    ``send`` must eventually call ``deliver(payload)``; implementations may
    move real bytes (RPC) or just model the delay.  The runtime binds
    ``schedule(delay_s, fn)`` at construction so in-process transports can
    put deliveries on the runtime's virtual clock.
    """

    def bind(self, schedule: Callable[[float, Callable[[], None]], None]
             ) -> None:
        self._schedule = schedule

    def send(self, src: str, dst: str, payload: Any, nbytes: float,
             deliver: Callable[[Any], None]) -> None:
        raise NotImplementedError


class InProcessTransport(Transport):
    """Same-process transport: payloads are handed over by reference after an
    optional modelled link delay (latency + nbytes/bandwidth).  This is the
    seam a real RPC transport plugs into later.

    ``direct_links`` models the routed worker-to-worker topology (the
    default): a stage->stage send costs one (src, dst) hop.  With
    ``direct_links=False`` the transport models the legacy coordinator-
    mediated star: every stage->stage send is charged as TWO physical hops
    — (src, COORDINATOR) then (COORDINATOR, dst) — with both link delays
    paid back to back, exactly the round trip a reply-driven socket run
    pays when the activation returns as the RPC reply before being staged
    to the next worker.  Hop and byte counters reflect the physical route
    either way, so the 2k -> k per-pass reduction is measurable."""

    def __init__(self, default_delay_s: float = 0.0,
                 link_delay_s: Optional[Mapping[Tuple[str, str], float]] = None,
                 bandwidth_bytes_per_s: float = 0.0, *,
                 direct_links: bool = True):
        self.default_delay_s = default_delay_s
        self.link_delay_s = dict(link_delay_s or {})
        self.bandwidth = bandwidth_bytes_per_s
        self.direct_links = direct_links
        self.transfers: Dict[Tuple[str, str], int] = defaultdict(int)
        self.bytes_sent: Dict[Tuple[str, str], float] = defaultdict(float)
        # runtime-maintained one-liners appended to describe() (e.g. the
        # speculation counters, shown next to the hop/byte counters)
        self.annotations: Dict[str, str] = {}

    def delay(self, src: str, dst: str, nbytes: float) -> float:
        d = self.link_delay_s.get((src, dst), self.default_delay_s)
        if self.bandwidth > 0:
            d += nbytes / self.bandwidth
        return d

    def _count(self, src: str, dst: str, nbytes: float) -> None:
        self.transfers[(src, dst)] += 1
        self.bytes_sent[(src, dst)] += nbytes

    def send(self, src: str, dst: str, payload: Any, nbytes: float,
             deliver: Callable[[Any], None]) -> None:
        if (self.direct_links or src == COORDINATOR or dst == COORDINATOR):
            self._count(src, dst, nbytes)
            self._schedule(self.delay(src, dst, nbytes),
                           lambda: deliver(payload))
            return
        # star route: src -> coordinator (RPC reply) -> dst (staging)
        self._count(src, COORDINATOR, nbytes)
        self._count(COORDINATOR, dst, nbytes)
        d = (self.delay(src, COORDINATOR, nbytes)
             + self.delay(COORDINATOR, dst, nbytes))
        self._schedule(d, lambda: deliver(payload))

    def describe(self) -> str:
        frags = [f"{s}->{d}={n}/{self.bytes_sent[(s, d)]:.0f}B"
                 for (s, d), n in sorted(self.transfers.items())]
        mode = "direct" if self.direct_links else "star"
        extra = "".join(f" {v}" for _, v in
                        sorted(getattr(self, "annotations", {}).items()))
        return f"hops[{mode}: " + ", ".join(frags) + "]" + extra


# ---------------------------------------------------------------------------
# Runtime
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Route:
    """Per-job compiled dataflow: which nodes run the prompt pass, which
    run decode passes, and which KV handoffs bridge the two replica groups.
    For plain (non-disaggregated) placements prefill and decode are the
    same pipeline and there are no handoffs.  Routes are compiled at every
    (re)admission, so failover replans rebuild them for free.

    ``handoffs`` maps a prefill stage index to the ``(decode node, global
    layers)`` exports due once that stage's final prompt chunk lands —
    layers are matched by global index, so any pair of prefill/decode
    layer splits composes."""

    prefill: Any                      # RequestPipeline for prompt passes
    decode: Any                       # RequestPipeline for decode passes
    handoffs: Dict[int, List[Tuple[str, List[int]]]] = \
        dataclasses.field(default_factory=dict)

    @property
    def disaggregated(self) -> bool:
        return self.prefill is not self.decode

    @property
    def nodes(self) -> set:
        return ({st.node for st in self.prefill.stages}
                | {st.node for st in self.decode.stages})


@dataclasses.dataclass
class _Job:
    req: Request
    pipe: Any = None                 # decode RequestPipeline (== route.decode)
    route: Optional[Route] = None    # compiled dataflow (kept across preempt)
    kv_pending: set = dataclasses.field(default_factory=set)
                                     # (prefill stage idx, decode node) KV
                                     # handoffs not yet imported: decode
                                     # cannot launch until this empties
    slots: Dict[str, int] = dataclasses.field(default_factory=dict)
    pos: int = 0                     # tokens confirmed resident in caches
    epoch: int = 0                   # bumped on preempt/requeue/complete:
                                     # stale in-flight messages die
    seq: int = -1                    # admission order (preemption victims)
    # -- in-flight decode window (reset on every (re)admission) ----------
    next_j: int = 0                  # output index the next launched pass
                                     # will produce
    next_pos: int = 0                # cache position of the next pass
    inbox: Dict[int, int] = dataclasses.field(default_factory=dict)
                                     # out-of-order sampled tokens by index
    # -- delivery hardening (a Transport may duplicate or reorder) -------
    seen: set = dataclasses.field(default_factory=set)
                                     # dedup keys of deliveries already run
    # -- speculative decoding (draft-model) ------------------------------
    draft_slot: Optional[int] = None  # coordinator draft-engine slot
    draft_pos: int = 0               # next draft row to feed (rows below
                                     # hold tokens the draft has consumed)
    spec_drafts: List[int] = dataclasses.field(default_factory=list)
                                     # γ proposals of the in-flight verify
    spec_base: int = 0               # cache position of the verify pass
    hop_next: Dict[int, int] = dataclasses.field(default_factory=dict)
                                     # per-stage next expected chunk offset
    hop_stash: Dict[int, Dict[int, Any]] = dataclasses.field(
        default_factory=dict)        # reordered chunks awaiting predecessors
    t_queued: float = 0.0            # tracer clock: when it last joined
                                     # the admission queue

    @property
    def resumed(self) -> bool:
        return bool(self.req.output)

    @property
    def inflight(self) -> int:
        """Decode passes launched whose token the coordinator has not yet
        confirmed (includes sampled tokens still travelling back)."""
        return self.next_j - len(self.req.output)


class ClusterRuntime:
    """Orchestrates one stage engine per placed node (see module docstring).

    ``plan`` is a ``repro.core.planner.Plan``; engines are built from its
    placement, with paged pools sized from each node's own VRAM (capped at
    the full rectangle, floored at one max_len request).
    """

    def __init__(self, cfg: ModelConfig, params, plan, engine_cfg: EngineConfig,
                 *, paged: bool = True, page_size: int = 16,
                 kv_dtype: Optional[str] = None,
                 pool_pages: Optional[Mapping[str, int]] = None,
                 transport: Optional[Transport] = None,
                 rng_seed: int = 0,
                 max_inflight: int = 1,
                 engine_factory: Optional[Callable[["ClusterRuntime", str,
                                                    LayerRange], Any]] = None,
                 stall_timeout_s: float = 60.0,
                 draft_cfg: Optional[ModelConfig] = None, draft_params=None,
                 spec_tokens: int = 4,
                 realtime: Optional[bool] = None):
        if max_inflight < 1:
            raise ValueError(f"max_inflight must be >= 1, got {max_inflight}")
        self.cfg = cfg
        self.params = params
        self.ec = engine_cfg
        self.paged = paged
        self.max_inflight = max_inflight
        self.page_size = page_size
        self.kv_dtype = kv_dtype
        self.pool_pages = dict(pool_pages or {})
        self.rng_seed = rng_seed
        self.stall_timeout_s = stall_timeout_s
        self._engine_factory = engine_factory
        self.cluster = plan.cluster
        self.placement = plan.placement
        self.profile = plan.model
        if plan.model.num_layers != cfg.num_layers:
            raise ValueError(f"plan covers {plan.model.num_layers} layers; "
                             f"{cfg.name} has {cfg.num_layers}")
        self._build_role_schedulers(plan)
        self.transport = transport or InProcessTransport()
        # realtime transports (sockets) finish deliveries on their own
        # threads: they get a thread-safe mailbox drained by step(), and the
        # loop runs on the wall clock.  Virtual-clock transports keep the
        # deterministic event heap, unless ``realtime=True`` forces the wall
        # clock (the online front door over an in-process transport), in
        # which case modelled link delays become real timers feeding the
        # same mailbox.
        auto = bool(getattr(self.transport, "realtime", False))
        self.realtime = auto if realtime is None else bool(realtime)
        self._mailbox: "_queue.Queue" = _queue.Queue()
        self._ingest: "_queue.Queue" = _queue.Queue()
        # jobs (not control messages) sitting in _ingest: cancel markers and
        # call_soon thunks ride the same FIFO, so qsize() would overcount
        # pending work — this counter tracks real submissions only
        self._ingest_jobs = 0
        self._ingest_lock = threading.Lock()
        self._listeners: Dict[int, Tuple[Optional[Callable[[int], None]],
                                         Optional[Callable[[Request], None]]]
                              ] = {}
        self._stop_serving = threading.Event()
        self._t0 = time.monotonic()
        if auto:
            self.transport.bind(lambda d, fn: self._mailbox.put(fn))
        elif self.realtime:
            self.transport.bind(self._deliver_realtime)
        else:
            self.transport.bind(lambda d, fn: self._push(self._now + d, fn))
        self._chunked = paged and all_blocks_paged(cfg)

        # -- speculative decoding: coordinator-side draft model ----------
        self.spec_tokens = spec_tokens
        self.draft_cfg = draft_cfg
        self.draft = None
        if draft_cfg is not None:
            if draft_params is None:
                raise ValueError("draft_cfg given without draft_params")
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft {draft_cfg.name} vocab {draft_cfg.vocab_size} "
                    f"!= target {cfg.name} vocab {cfg.vocab_size}")
            if spec_tokens < 1:
                raise ValueError(
                    f"spec_tokens must be >= 1, got {spec_tokens}")
            # a full tiny model living at the coordinator; dense positional
            # caches make rejected speculative rows free to overwrite, and
            # sharing engine_cfg keeps slot/row budgets aligned with the
            # target's
            self.draft = StageEngine(draft_cfg, draft_params,
                                     LayerRange(0, draft_cfg.num_layers),
                                     engine_cfg, rng_seed=rng_seed)
        self.spec_proposed = 0       # draft tokens sent to verification
        self.spec_accepted = 0       # draft tokens matching target greedy
        self.spec_rejected = 0       # draft tokens rolled back
        self.spec_rounds = 0         # verify round trips
        self.spec_confirmed = 0      # tokens confirmed by verify rounds
                                     # (accepted prefix + 1 per round)

        self.workers: Dict[str, Any] = {}   # node -> worker process handle
        self.engines: Dict[str, Any] = {}
        for node, rng in sorted(self.placement.assignment.items()):
            self.engines[node] = self._make_engine(
                node, rng, self.placement.assignment)
        self._sync_kv(capacities=True)

        self.queue: deque = deque()      # _Job awaiting admission
        self.jobs: Dict[int, _Job] = {}  # request_id -> active job
        self._ready: Dict[str, List[dict]] = defaultdict(list)
        self._events: List = []
        self._eseq = 0
        self._jseq = 0
        self._now = 0.0
        self.tokens_produced = 0
        self.completed = 0
        # speculative in-flight passes cancelled by an early stop (eos/len)
        self.cancelled_inflight = 0
        # client-initiated teardowns (``cancel()``): requests ended before
        # finishing, with KV/slots released on every stage node
        self.cancelled_requests = 0
        # per-node decode telemetry for the autoscaler's straggler detector:
        # cumulative wall seconds inside decode passes and tokens batched
        # through them (written on the loop thread; readers snapshot-copy)
        self.node_decode_s: Dict[str, float] = defaultdict(float)
        self.node_decode_tokens: Dict[str, int] = defaultdict(int)
        # request_id -> the pipeline it was (last) served on, for
        # introspection: drivers assert multi-stage serving actually happened
        self.served: Dict[int, Any] = {}
        # virtual-clock latency: first-token confirm time, and mean
        # per-token decode latency recorded at completion
        self._vfirst: Dict[int, float] = {}
        self.decode_latencies: Dict[int, float] = {}

    # -- engine construction ------------------------------------------------
    def _engine_spec(self, node: str, rng: LayerRange) -> Dict[str, Any]:
        """Paged/dense choice + pool sizing for a node's slice — shared by
        local construction and the worker-init payload, so a remote node's
        pool is sized exactly as a local one's would be."""
        n_paged = stage_num_paged_layers(self.cfg, rng)
        if not self.paged or n_paged == 0:
            # hybrid models can hand a node an all-SSM/MLA slice with no
            # paged block at all — that node serves dense even in paged mode
            return {"paged": False, "num_pages": None, "kv_dtype": None}
        rect = full_rectangle_pages(self.cfg, max_batch=self.ec.max_batch,
                                    max_len=self.ec.max_len,
                                    page_size=self.page_size,
                                    paged_layers=n_paged)
        if node in self.pool_pages:
            pages = self.pool_pages[node]
        else:
            # int8 pages cost ~half the bytes, so the same VRAM yields ~2x
            # the pages (still capped at the full rectangle)
            pages = pages_for_vram(self.cfg,
                                   self.cluster.nodes[node].vram_bytes,
                                   page_size=self.page_size,
                                   layers_on_node=rng.num_layers,
                                   max_pages=rect,
                                   kv_dtype=self.kv_dtype)
            # floor: one full-budget request must always fit
            blocks = -(-self.ec.max_len // self.page_size)
            pages = max(pages, 1 + blocks * n_paged)
        return {"paged": True, "num_pages": pages, "kv_dtype": self.kv_dtype}

    def _make_engine(self, node: str, rng: LayerRange,
                     assignment: Mapping[str, LayerRange]):
        """Build ``node``'s engine on its own device: node *i* in sorted
        placement order gets ``jax.devices()[i % n]``, so one process drives
        every chip of its host with one stage per chip (on one device this
        is the default device, as before)."""
        devs = jax.devices()
        dev = devs[sorted(assignment).index(node) % len(devs)]
        with jax.default_device(dev):
            if self._engine_factory is not None:
                return self._engine_factory(self, node, rng)
            spec = self._engine_spec(node, rng)
            if not spec["paged"]:
                return StageEngine(self.cfg, self.params, rng, self.ec,
                                   rng_seed=self.rng_seed)
            return PagedStageEngine(self.cfg, self.params, rng, self.ec,
                                    num_pages=spec["num_pages"],
                                    page_size=self.page_size,
                                    kv_dtype=spec["kv_dtype"],
                                    rng_seed=self.rng_seed)

    # -- role schedulers (disaggregated prefill/decode) -----------------------
    def _build_role_schedulers(self, plan) -> None:
        """Install the IWRR scheduler(s).  When the placement carries
        replica roles (``meta["roles"]``: node -> prefill|decode|mixed)
        with genuinely distinct prefill and decode groups, each role gets
        its own scheduler over its own sub-placement (max-flow recomputed
        on the role's subgraph, KV estimation over the role's nodes);
        otherwise one scheduler serves both, as before."""
        roles = (plan.placement.meta or {}).get("roles") or {}
        pre = {n for n, r in roles.items() if r in ("prefill", "mixed")}
        dec = {n for n, r in roles.items() if r in ("decode", "mixed")}
        if not (pre and dec) or pre == dec:
            self.scheduler = plan.make_scheduler()
            self.sched_prefill = self.scheduler
            return
        from ..core.placement import Placement
        from ..core.planner import plan as _plan

        def sub(nodes: set):
            p = Placement({n: plan.placement.assignment[n] for n in nodes},
                          plan.placement.num_layers,
                          meta=dict(plan.placement.meta))
            bad = p.validate()
            if bad:
                raise ValueError(
                    f"role group {sorted(nodes)} does not cover the model "
                    f"on its own: {bad}")
            return _plan(plan.cluster, plan.model, placement=p)

        self.scheduler = sub(dec).make_scheduler()
        self.sched_prefill = sub(pre).make_scheduler()

    @property
    def disaggregated(self) -> bool:
        return self.sched_prefill is not self.scheduler

    # -- event machinery ----------------------------------------------------
    def _push(self, t: float, fn: Callable[[], None]) -> None:
        self._eseq += 1
        heapq.heappush(self._events, (t, self._eseq, fn))

    def _send(self, src: str, dst: str, payload, nbytes: float,
              deliver: Callable[[Any], None]) -> None:
        self.transport.send(src, dst, payload, nbytes, deliver)

    def _act_bytes(self, n_tokens: int) -> float:
        elt = {"bfloat16": 2, "float32": 4}[self.cfg.param_dtype]
        return float(n_tokens * self.cfg.d_model * elt)

    def _kv_bytes(self, tokens: int, n_layers: int) -> float:
        return float(self.profile.kv_bytes_per_token_layer
                     * tokens * n_layers)

    def _fwd_spec(self, eng, dst: Optional[str]
                  ) -> Optional[Tuple[str, int]]:
        """Forward spec ``(dst node, staging tag)`` when this engine's
        output can be pushed worker-to-worker instead of riding the RPC
        reply: the transport advertises direct links, both endpoints are
        forward-capable workers, and the destination is a node (tokens to
        the coordinator always come back on the reply)."""
        if dst is None or dst == COORDINATOR:
            return None
        if not getattr(self.transport, "direct_links", False):
            return None
        alloc = getattr(self.transport, "alloc_tag", None)
        if alloc is None or not getattr(eng, "forward_capable", False):
            return None
        if not getattr(self.engines.get(dst), "forward_capable", False):
            return None
        return (dst, alloc())

    # -- public API ---------------------------------------------------------
    def clock(self) -> float:
        """Seconds on the runtime's own clock: wall time since construction
        (monotonic) for realtime runs, the virtual event clock otherwise.
        EVERY per-request timestamp (``submitted_s`` / ``first_token_s`` /
        ``finished_s``) is stamped from here — one monotonic base, so TTFT
        and TPOT can never go negative when the system wall clock
        (``time.time``) steps under NTP, and they are defined on
        virtual-clock runs too."""
        if self.realtime:
            return time.monotonic() - self._t0
        return self._now

    def _deliver_realtime(self, d: float, fn: Callable[[], None]) -> None:
        """Delivery sink for realtime-over-in-process runs: a modelled link
        delay becomes a real timer into the thread-safe mailbox."""
        if d > 0:
            threading.Timer(d, self._mailbox.put, args=(fn,)).start()
        else:
            self._mailbox.put(fn)

    def submit(self, req: Request, *,
               on_token: Optional[Callable[[int], None]] = None,
               on_done: Optional[Callable[[Request], None]] = None) -> None:
        """Queue a request.  Thread-safe: the online front door calls this
        from HTTP handler threads while ``serve_forever`` steps — the job
        lands in an ingest queue that only the loop thread drains into the
        admission deque.  Raises ``ValueError`` for requests that could
        never serve (mapped to HTTP 400 by the front door).

        ``on_token`` fires on the loop thread once per token the
        coordinator *confirms*, in strict output order — in-flight
        ``max_inflight`` windows and speculative verify rounds never stream
        unconfirmed tokens.  ``on_done`` fires once at completion."""
        if len(req.prompt) == 0:
            raise ValueError("empty prompt")
        if len(req.prompt) > self.ec.max_len:
            raise ValueError(f"prompt of {len(req.prompt)} tokens exceeds "
                             f"max_len {self.ec.max_len}; refusing to "
                             "truncate")
        if req.temperature > 0 and self.draft is not None:
            raise ValueError(
                f"temperature {req.temperature} > 0 is incompatible with "
                f"speculative decoding (spec_tokens={self.spec_tokens}): "
                "verification accepts draft tokens by greedy argmax, so "
                "sampled acceptance would silently change the output "
                "distribution; serve sampled requests on a runtime "
                "without a draft model")
        req.submitted_s = self.clock()
        if on_token is not None or on_done is not None:
            self._listeners[req.request_id] = (on_token, on_done)
        job = _Job(req, t_queued=TRACER.clock())
        TRACER.record("helix.request.submit", job.t_queued, job.t_queued,
                      request=req.request_id)
        with self._ingest_lock:
            self._ingest_jobs += 1
        self._ingest.put(job)
        self._mailbox.put(lambda: None)   # wake an idle serve loop

    def cancel(self, request_id: int) -> None:
        """Cancel a request from any thread (the front door calls this when
        a streaming client disconnects).  Rides the same FIFO ingest queue
        as ``submit``, so a cancel issued after a submit can never be
        processed before its job has landed — the loop thread tears the
        request down in ``_do_cancel``: epoch bump (every in-flight decode
        pass, speculative verify round, and disaggregated KV handoff dies
        on delivery), KV/slots released on every stage node, ``on_done``
        fired once with ``finish_reason="cancelled"``.  Unknown or
        already-finished ids are a no-op."""
        self._ingest.put(("cancel", request_id))
        self._mailbox.put(lambda: None)   # wake an idle serve loop

    def call_soon(self, fn: Callable[[], None]) -> None:
        """Run ``fn`` on the loop thread at the next step — the thread-safe
        door through which the autoscaler applies loop-affine mutations
        (``apply_plan``, ``update_weights``, ``fail_node``) while
        ``serve_forever`` runs."""
        self._ingest.put(fn)
        self._mailbox.put(lambda: None)

    def pending(self) -> int:
        """Requests accepted but not finished (ingest + admission queue +
        live jobs) — the front door's 429 admission signal.  Thread-safe:
        reads container sizes and a lock-guarded counter only."""
        with self._ingest_lock:
            ingest = self._ingest_jobs
        return ingest + len(self.queue) + len(self.jobs)

    def _drain_ingest(self) -> None:
        """Move thread-safe submissions into the admission deque and run
        cross-thread control messages (loop thread only —
        ``fail_node``/``apply_plan`` iterate the deque).  Everything rides
        ONE FIFO queue so ordering across kinds is preserved: a cancel
        enqueued after its submit always drains after the job exists."""
        while True:
            try:
                item = self._ingest.get_nowait()
            except _queue.Empty:
                return
            if isinstance(item, _Job):
                with self._ingest_lock:
                    self._ingest_jobs -= 1
                self.queue.append(item)
            elif isinstance(item, tuple) and item and item[0] == "cancel":
                self._do_cancel(item[1])
            else:
                item()               # call_soon thunk

    def _do_cancel(self, request_id: int) -> None:
        """Loop-thread teardown of a queued or live request.  The epoch
        bump invalidates every delivery still addressed to the job —
        decode tokens, staged activation hops, spec verify results, and
        prefill->decode KV handoffs all check the epoch on arrival — and
        ``_release_all`` frees slots/KV on every node holding any (all
        decode stages, prefill-only replicas, the coordinator draft
        engine), so pools drain even mid-handoff."""
        job = self.jobs.pop(request_id, None)
        if job is None:
            for q in self.queue:
                if q.req.request_id == request_id:
                    job = q
                    break
            if job is None:
                return               # finished or never seen: no-op
            self.queue.remove(job)
        req = job.req
        if req.done:
            return
        self.cancelled_inflight += max(0, job.inflight)
        job.epoch += 1
        job.inbox = {}
        job.kv_pending = set()
        self._release_all(job)
        req.done = True
        req.finish_reason = "cancelled"
        req.finished_s = self.clock()
        self._vfirst.pop(request_id, None)
        self.cancelled_requests += 1
        cb = self._listeners.pop(request_id, None)
        if cb is not None and cb[1] is not None:
            cb[1](req)

    def _idle(self) -> bool:
        return not (self.queue or self.jobs or self._events or self._ready
                    or self._mailbox.qsize() or self._ingest.qsize())

    def _inflight_work(self) -> bool:
        """Work whose progress depends on future deliveries: live jobs,
        scheduled events, or stage-work awaiting a decode pass.  A
        non-empty admission queue alone is NOT in-flight — it drains the
        moment running work frees capacity (and never can if nothing is
        running)."""
        return bool(self.jobs or self._events or self._ready)

    def run_until_done(self, max_iters: int = 100000) -> None:
        for _ in range(max_iters):
            if self._idle():
                return
            if self.step():
                continue
            # realtime (socket) transports complete deliveries on their own
            # threads: no local progress just means the bytes are still in
            # flight — block on the mailbox instead of declaring a stall
            if self.realtime and self._await_delivery(self.stall_timeout_s):
                continue
            raise RuntimeError(
                "runtime stalled: queued requests cannot be admitted "
                "(cluster slots/pools too small?); " + self._state())
        if self._idle():
            return                   # finished exactly on the last step
        raise RuntimeError(
            f"not done after {max_iters} iterations; " + self._state())

    def serve_forever(self) -> None:
        """Online event loop: step while accepting thread-safe ``submit()``
        from other threads.  Unlike ``run_until_done`` the workload is
        OPEN — ``_idle()`` means "waiting for the next request", not
        "done", so idle waits block on the mailbox indefinitely and the
        stall timer is armed only while in-flight work exists (an idle
        server is not stalled).  Returns once ``stop_serving()`` has been
        called and everything in flight has drained."""
        while True:
            if self.step():
                continue
            if self._idle() and self._stop_serving.is_set():
                return
            if self._inflight_work():
                # a delivery must land within the stall budget, or the run
                # is declared wedged with diagnostics
                if not self._await_delivery(self.stall_timeout_s):
                    raise RuntimeError(
                        "runtime stalled with work in flight; "
                        + self._state())
            elif self.queue:
                # admission-blocked with nothing running: capacity can
                # never free up (the pool floor guarantees one max-budget
                # request always fits, so this is a genuine wedge)
                raise RuntimeError(
                    "queued requests cannot be admitted "
                    "(cluster slots/pools too small?); " + self._state())
            else:
                # idle: block until a submission or stop_serving wakes us
                self._await_delivery(None)

    def stop_serving(self) -> None:
        """Ask ``serve_forever`` to exit once in-flight work drains.
        Callable from any thread; submissions already accepted are still
        served (the front door stops accepting new ones first)."""
        self._stop_serving.set()
        self._mailbox.put(lambda: None)   # wake a blocked idle wait

    def _await_delivery(self, timeout_s: Optional[float] = None) -> bool:
        """Block for the next transport delivery or ingest wake-up.
        ``timeout_s=None`` blocks indefinitely — the right mode when
        nothing is in flight; a bounded wait is armed only over in-flight
        work, so a deadlocked run still fails fast with diagnostics
        instead of hanging CI."""
        try:
            with TRACER.span("helix.idle"):
                fn = self._mailbox.get(timeout=timeout_s)
        except _queue.Empty:
            return False
        with TRACER.span("helix.deliver"):
            fn()
        return True

    def _state(self) -> str:
        """Queue / in-flight diagnostics for stall and iteration-budget
        errors — never return silently with work outstanding.  Transports
        that can stall (bounded socket queues) append their per-link
        report, so a wedged link is named in the error."""
        windows = {j.req.request_id: f"{len(j.req.output)}+{j.inflight}"
                   for j in self.jobs.values()}
        ready = {n: len(v) for n, v in self._ready.items() if v}
        describe = getattr(self.transport, "describe", None)
        extra = f" transport={describe()}" if callable(describe) else ""
        spec = self._spec_note()
        with self._ingest_lock:
            ingest = self._ingest_jobs
        return (f"queued={len(self.queue) + ingest} "
                f"in_flight(confirmed+window)={windows} "
                f"pending_events={len(self._events)} ready={ready} "
                f"cancelled_requests={self.cancelled_requests} "
                f"now={self._now:.6f}" + (f" {spec}" if spec else "") + extra)

    def step(self) -> bool:
        """One runtime iteration: admit, drain deliveries due now, then one
        batched decode per node with resident stage-work.  Returns whether
        anything progressed."""
        with TRACER.span("helix.step"):
            if self.realtime:
                self._now = max(self._now, time.monotonic() - self._t0)
            with TRACER.span("helix.admit"):
                self._drain_ingest()
                progressed = self._admit()
            with TRACER.span("helix.deliver"):
                progressed = self._deliver() or progressed
            for node in [n for n, v in self._ready.items() if v]:
                work = self._ready.pop(node)
                work = [w for w in work if w["job"].epoch == w["epoch"]]
                if work:
                    self._decode_node(node, work)
                    progressed = True
            with TRACER.span("helix.sync_kv"):
                self._sync_kv()
            return progressed

    def _deliver(self) -> bool:
        """Run the deliveries due now: virtual-clock events, then the
        wall-clock mailbox.  Returns whether any ran."""
        ran = False
        if self._events:
            self._now = max(self._now, self._events[0][0])
            while self._events and self._events[0][0] <= self._now + 1e-12:
                _, _, fn = heapq.heappop(self._events)
                fn()
                ran = True
        while True:                  # wall-clock deliveries (socket runs)
            try:
                fn = self._mailbox.get_nowait()
            except _queue.Empty:
                break
            fn()
            ran = True
        return ran

    # -- KV feedback --------------------------------------------------------
    def _sync_kv(self, capacities: bool = False) -> None:
        scheds = [self.scheduler]
        if self.sched_prefill is not self.scheduler:
            scheds.append(self.sched_prefill)
        for sched in scheds:
            kv = sched.kv
            if kv is None:
                continue
            for node, eng in self.engines.items():
                if node not in kv.capacity_tokens:
                    continue             # the other role group's node
                if capacities:
                    kv.capacity_tokens[node] = float(eng.kv_tokens_capacity())
                kv.sync(node, float(eng.kv_tokens_used()))

    # -- admission ----------------------------------------------------------
    def _prefill_tokens(self, job: _Job) -> np.ndarray:
        """Tokens to prefill: the prompt, plus — after preemption/failover —
        all generated output but the last token (recompute; the last token
        restarts decode)."""
        prompt = np.asarray(job.req.prompt, np.int32)
        if len(job.req.output) > 1:
            prompt = np.concatenate(
                [prompt, np.asarray(job.req.output[:-1], np.int32)])
        return prompt

    def _compile_route(self, job: _Job) -> None:
        """Compile the job's dataflow.  Disaggregated placements schedule a
        pipeline per role and derive the KV handoffs bridging them (decode
        layer l ships from the prefill stage that computed l, unless the
        same node plays both parts and the KV is already home)."""
        if not self.disaggregated:
            pipe = self.scheduler.schedule()
            job.route = Route(prefill=pipe, decode=pipe)
            job.pipe = pipe
            return
        d = self.scheduler.schedule()
        p = self.sched_prefill.schedule()
        handoffs: Dict[int, List[Tuple[str, List[int]]]] = {}
        for sd in d.stages:
            for si, sp in enumerate(p.stages):
                if sp.node == sd.node:
                    continue            # mixed node: KV stays in its slot
                common = [l for l in range(sd.layers.start, sd.layers.end)
                          if sp.layers.start <= l < sp.layers.end]
                if common:
                    handoffs.setdefault(si, []).append((sd.node, common))
        job.route = Route(prefill=p, decode=d, handoffs=handoffs)
        job.pipe = d

    def _admit(self) -> bool:
        progressed = False
        while self.queue:
            job = self.queue[0]
            if job.route is None:
                try:
                    self._compile_route(job)
                except RuntimeError:
                    break               # no route (mid-replan): wait
            S = len(self._prefill_tokens(job))
            need = min(S + 1, self.ec.max_len)
            nodes: List[str] = []       # prefill-first union, slot per node
            for st in (*job.route.prefill.stages, *job.route.decode.stages):
                if st.node not in nodes:
                    nodes.append(st.node)
            taken: List[Tuple[str, int]] = []
            ok = True
            for node in nodes:
                eng = self.engines.get(node)
                slot = eng.alloc_slot(job.req.request_id) if eng else None
                if slot is None or not eng.ensure(slot, need):
                    if slot is not None:
                        eng.free_slot(slot)
                    ok = False
                    break
                taken.append((node, slot))
            if not ok:
                for node, slot in taken:
                    self.engines[node].release(slot)
                break                   # FIFO: wait for running work to free
            self.queue.popleft()
            TRACER.record("helix.request.queued", job.t_queued,
                          TRACER.clock(), request=job.req.request_id,
                          resumed=int(job.resumed))
            job.slots = dict(taken)
            job.pos = S
            job.kv_pending = {(si, dst)
                              for si, hs in job.route.handoffs.items()
                              for dst, _ in hs}
            # open the in-flight window: the first decode pass consumes the
            # last known token at position S and produces output index
            # ``next_j`` (a fresh request's prefill token is index 0, so its
            # first decode pass produces index 1; a resumed request restarts
            # from its last confirmed token)
            job.next_j = len(job.req.output) if job.resumed else 1
            job.next_pos = S
            job.inbox = {}
            job.seen = set()
            job.hop_next = {}
            job.hop_stash = {}
            # speculation: take a draft slot and prefill the draft with the
            # same tokens the target saw; greedy-only — sampled requests
            # (and requests that find the draft full) serve non-speculative
            job.draft_slot = None
            job.draft_pos = 0
            if self.draft is not None and job.req.temperature <= 0:
                dslot = self.draft.alloc_slot(job.req.request_id)
                if dslot is not None:
                    self.draft.prefill_stage(dslot,
                                             self._prefill_tokens(job), 0)
                    job.draft_slot = dslot
                    job.draft_pos = job.pos
            job.seq = self._jseq
            self._jseq += 1
            self.jobs[job.req.request_id] = job
            self.served[job.req.request_id] = job.pipe
            self._dispatch_prefill(job)
            progressed = True
        return progressed

    def _dispatch_prefill(self, job: _Job) -> None:
        tokens = self._prefill_tokens(job)
        first = job.route.prefill.stages[0].node
        if self._chunked:
            chunk = tokens[:max(1, self.ec.prompt_len)]
            self._send(COORDINATOR, first, chunk,
                       len(chunk) * self.profile.token_bytes,
                       self._hop(job, 0, off=0))
        else:
            self._send(COORDINATOR, first, tokens,
                       len(tokens) * self.profile.token_bytes,
                       self._hop(job, 0, off=None))

    # -- prefill hops -------------------------------------------------------
    def _hop(self, job: _Job, si: int, off: Optional[int]
             ) -> Callable[[Any], None]:
        epoch = job.epoch
        return lambda payload: self._prefill_at(job, epoch, si, payload, off)

    def _prefill_at(self, job: _Job, epoch: int, si: int, x,
                    off: Optional[int]) -> None:
        """Delivery guard for prefill payloads: drop duplicates, and execute
        chunks strictly in offset order per stage (a transport is allowed to
        duplicate and reorder; KV writes are not allowed to)."""
        if job.epoch != epoch:
            return                      # preempted/requeued mid-flight
        if off is None:                 # single-shot prefill: one hop/stage
            if ("pf", si) in job.seen:
                return
            job.seen.add(("pf", si))
            self._prefill_exec(job, epoch, si, x, None)
            return
        expect = job.hop_next.get(si, 0)
        if off < expect:
            return                      # duplicate of an executed chunk
        if off > expect:                # overtook a predecessor: wait
            job.hop_stash.setdefault(si, {})[off] = x
            return
        self._prefill_exec(job, epoch, si, x, off)
        while job.epoch == epoch:       # run any chunks unblocked by this one
            nxt = job.hop_next.get(si, 0)
            stash = job.hop_stash.get(si, {})
            if nxt not in stash:
                break
            self._prefill_exec(job, epoch, si, stash.pop(nxt), nxt)

    def _chunk_tokens(self, job: _Job, off: Optional[int]) -> int:
        """Token count of the prefill payload at offset ``off`` — derived
        from the request, not the payload (socket runs deliver opaque
        staged-payload handles)."""
        total = len(self._prefill_tokens(job))
        if off is None:
            return total
        return min(max(1, self.ec.prompt_len), total - off)

    def _prefill_exec(self, job: _Job, epoch: int, si: int, x,
                      off: Optional[int]) -> None:
        stages = job.route.prefill.stages
        st = stages[si]
        eng = self.engines[st.node]
        slot = job.slots[st.node]
        entry = st.layers.start
        n_tok = self._chunk_tokens(job, off)
        last = si == len(stages) - 1
        nxt = None if last else stages[si + 1].node
        # route-driven forwarding: the engine RPC carries the next hop, so
        # a worker pushes its activation frame straight to the next stage's
        # worker and replies with only an ack (the StagedRef the runtime
        # then routes)
        fwd = self._fwd_spec(eng, nxt)
        if self._chunked:
            out = eng.prefill_chunk(slot, x, entry, off,
                                    **({"fwd": fwd} if fwd else {}))
        else:
            out = eng.prefill_stage(slot, x, entry,
                                    **({"fwd": fwd} if fwd else {}))
        if off is not None:
            job.hop_next[si] = off + n_tok
        if not last:
            self._send(st.node, nxt, out, self._act_bytes(n_tok),
                       self._hop(job, si + 1, off))
        if self._chunked and si == 0:
            # stage 0 freed: stream the next chunk in behind this one
            tokens = self._prefill_tokens(job)
            nxt_off = off + n_tok
            if nxt_off < len(tokens):
                chunk = tokens[nxt_off:nxt_off + max(1, self.ec.prompt_len)]
                self._send(COORDINATOR, st.node, chunk,
                           len(chunk) * self.profile.token_bytes,
                           self._hop(job, 0, off=nxt_off))
        stage_done = off is None or off + n_tok >= job.pos
        if stage_done:
            # this stage's KV is complete: ship it to the decode replica(s)
            # that will read these layers (disaggregated placements only)
            for dst, lays in job.route.handoffs.get(si, []):
                self._start_handoff(job, epoch, si, dst, lays)
        if last and stage_done:
            # final chunk left the final stage: out is last-token logits
            if job.resumed:
                tok = job.req.output[-1]      # sampled before eviction
            else:
                tok = eng.sample(out, job.req.temperature)
            self._send(st.node, COORDINATOR, tok, self.profile.token_bytes,
                       lambda t: self._on_first_token(job, epoch, t))
            # at depth >= 2 decode starts here — the first pass leaves for
            # stage 0 while the prefill token travels to the coordinator.
            # Depth 1 always waits for the coordinator (also for resumed
            # requests, whose token needs no confirmation): the documented
            # classic walk, so depth-1 latency is comparable on any trace.
            if self.max_inflight > 1:
                self._maybe_launch(job, st.node, int(tok), job.next_j)

    # -- KV handoff (disaggregated prefill -> decode) ------------------------
    def _start_handoff(self, job: _Job, epoch: int, si: int, dst: str,
                       layers: List[int]) -> None:
        """Ship one prefill stage's filled KV (prompt tokens x ``layers``)
        to a decode replica.  Over direct links the export is pushed
        worker-to-worker (int8 pages + scales travel as-is); otherwise the
        payload rides the reply and the transport stages it — either way
        the decode launch stays gated on ``kv_pending``."""
        st = job.route.prefill.stages[si]
        eng = self.engines.get(st.node)
        if eng is None or st.node not in job.slots:
            return                      # mid-failover: the job will requeue
        fwd = self._fwd_spec(eng, dst)
        payload = eng.export_kv(job.slots[st.node], job.pos, layers,
                                **({"fwd": fwd} if fwd else {}))
        self._send(st.node, dst, payload, self._kv_bytes(job.pos,
                                                         len(layers)),
                   lambda p, jb=job, e=epoch, s=si, d=dst:
                   self._finish_handoff(jb, e, s, d, p))

    def _finish_handoff(self, job: _Job, epoch: int, si: int, dst: str,
                        payload) -> None:
        if job.epoch != epoch:
            return
        key = ("kv", si, dst)
        if key in job.seen:
            return                      # duplicated delivery (chaos link)
        job.seen.add(key)
        eng = self.engines.get(dst)
        if eng is None or dst not in job.slots:
            return
        eng.import_kv(job.slots[dst], job.pos, payload)
        job.kv_pending.discard((si, dst))
        self._maybe_release_prefill(job)
        if not job.kv_pending and job.req.output:
            # the first token may have confirmed while KV was in flight —
            # its launch attempt was gated; relaunch now that decode can run
            self._maybe_launch(job, COORDINATOR, int(job.req.output[-1]),
                               len(job.req.output))
            self._drain_inbox(job)

    def _maybe_release_prefill(self, job: _Job) -> None:
        """Free prefill-only nodes' slots (and KV) once every handoff out
        of them has landed — long prompts stop holding decode-side pools,
        which is the point of disaggregating."""
        if not job.route.disaggregated:
            return
        decode_nodes = {st.node for st in job.route.decode.stages}
        pending_src = {job.route.prefill.stages[s].node
                       for s, _ in job.kv_pending}
        for st in job.route.prefill.stages:
            if st.node in decode_nodes or st.node in pending_src:
                continue
            slot = job.slots.pop(st.node, None)
            if slot is not None:
                eng = self.engines.get(st.node)
                if eng is not None:
                    eng.release(slot)

    # -- token arrivals (coordinator) ----------------------------------------
    def _confirm(self, job: _Job, tok: int) -> None:
        """Confirm ONE token at the coordinator: append it to the visible
        output, stamp the first-token time (on the runtime clock, so it is
        defined for virtual-clock runs too), and stream it to any listener.
        Every confirmed token — classic walk, in-flight window drain, or
        speculative verify acceptance — flows through here, so SSE streams
        see tokens strictly in confirmation order."""
        req = job.req
        req.output.append(int(tok))
        self.tokens_produced += 1
        if req.first_token_s is None:
            req.first_token_s = self.clock()
        self._vfirst.setdefault(req.request_id, self._now)
        cb = self._listeners.get(req.request_id)
        if cb is not None and cb[0] is not None:
            cb[0](int(tok))

    def _stop_reason(self, job: _Job) -> Optional[str]:
        req = job.req
        if int(req.output[-1]) == self.ec.eos_token:
            return "stop"
        if len(req.output) >= req.max_new_tokens:
            return "length"
        if job.pos >= self.ec.max_len:
            return "length"
        return None

    def _on_first_token(self, job: _Job, epoch: int, tok: int) -> None:
        """Prefill's token reached the coordinator (resumed requests re-send
        their last confirmed token instead of sampling a new one)."""
        if job.epoch != epoch:
            return
        if ("first",) in job.seen:
            return                      # duplicated delivery (chaos link)
        job.seen.add(("first",))
        req = job.req
        if not job.resumed:
            self._confirm(job, int(tok))
            reason = self._stop_reason(job)
            if reason is not None:
                self._complete(job, reason)
                return
        # depth 1 (or a closed window at prefill time): the first decode
        # pass launches from here, exactly the classic walk.  The expected
        # index is the one consuming our newest confirmed token — if the
        # final stage already launched it, this is a no-op.
        self._maybe_launch(job, COORDINATOR, int(req.output[-1]),
                           len(req.output))
        # a reordering transport may have delivered decode tokens first
        self._drain_inbox(job)

    def _on_decode_token(self, job: _Job, epoch: int, j: int, tok: int
                         ) -> None:
        """A sampled token arrived.  Confirm strictly in output order —
        arrivals ahead of the expected index wait in the job's inbox."""
        if job.epoch != epoch:
            return
        if j < len(job.req.output):
            return                      # duplicate of a confirmed token
        job.inbox[j] = int(tok)
        self._drain_inbox(job)

    def _drain_inbox(self, job: _Job) -> None:
        req = job.req
        while len(req.output) in job.inbox:
            t = job.inbox.pop(len(req.output))
            self._confirm(job, t)
            job.pos += 1
            reason = self._stop_reason(job)
            if reason is not None:
                self._complete(job, reason)
                return
            self._maybe_launch(job, COORDINATOR, t, len(req.output))

    # -- speculative verify results (coordinator) -----------------------------
    def _on_spec_result(self, job: _Job, epoch: int, j: int, greedy) -> None:
        """A verify pass's greedy vector reached the coordinator: accept
        the longest draft prefix, confirm those tokens (plus the bonus
        token) strictly in order, and on the first mismatch bump the epoch
        and roll every decode stage node back to the accepted prefix."""
        if job.epoch != epoch:
            return
        key = ("spec", j, epoch)
        if key in job.seen:
            return                      # duplicated delivery (chaos link)
        job.seen.add(key)
        req = job.req
        drafts = job.spec_drafts
        greedy = [int(t) for t in np.asarray(greedy).reshape(-1)]
        gamma = len(greedy) - 1
        a = 0
        while a < gamma and drafts[a] == greedy[a]:
            a += 1
        self.spec_accepted += a
        self.spec_rejected += gamma - a
        base = job.spec_base
        # draft rows base+1..base+min(a, γ-1) hold proposals the target
        # just confirmed — the draft need not re-consume them next round
        job.draft_pos = max(job.draft_pos, base + 1 + min(a, gamma - 1))
        for t in greedy[:a + 1]:
            self._confirm(job, int(t))
            self.spec_confirmed += 1
            job.pos += 1
            reason = self._stop_reason(job)
            if reason is not None:
                # early stop inside the accepted prefix: completion releases
                # every slot wholesale — no rollback needed
                self._complete(job, reason)
                self._spec_annotate()
                return
        if a < gamma:
            # rejection: cancel the optimistic window (the PR 4
            # cancelled_inflight path) and bump the epoch so straggling
            # duplicates of the dead pass cannot decode after the rollback
            keep = base + a + 1
            self.cancelled_inflight += max(0, job.inflight)
            job.epoch += 1
            job.next_j = len(req.output)
            job.next_pos = keep
            self._rollback_job(job, keep)
        self._spec_annotate()
        self._maybe_launch(job, COORDINATOR, int(req.output[-1]),
                           len(req.output))

    def _rollback_job(self, job: _Job, keep: int) -> None:
        """Synchronously truncate the job's KV to ``keep`` rows on every
        decode stage node (an RPC for remote engines), so the relaunched
        pass cannot race the rollback.  The draft engine needs no rollback:
        its dense caches are positional and ``draft_pos`` already points at
        the last confirmed row."""
        done = set()
        for st in job.pipe.stages:
            if st.node in done:
                continue
            done.add(st.node)
            eng = self.engines.get(st.node)
            slot = job.slots.get(st.node)
            if eng is None or slot is None:
                continue
            eng.rollback(slot, keep)

    def _spec_note(self) -> str:
        if self.draft is None:
            return ""
        return (f"spec[proposed={self.spec_proposed} "
                f"accepted={self.spec_accepted} "
                f"rejected={self.spec_rejected} "
                f"rate={self.spec_acceptance_rate:.2f} "
                f"tokens/rt={self.spec_tokens_per_round_trip:.2f}]")

    def _spec_annotate(self) -> None:
        ann = getattr(self.transport, "annotations", None)
        if ann is not None:
            ann["spec"] = self._spec_note()

    @property
    def spec_acceptance_rate(self) -> float:
        """Fraction of draft proposals the target's greedy pass accepted."""
        return self.spec_accepted / max(1, self.spec_proposed)

    @property
    def spec_tokens_per_round_trip(self) -> float:
        """Tokens confirmed per verify round trip (1 + accepted prefix;
        the in-flight-window-only baseline is 1 by construction)."""
        return self.spec_confirmed / max(1, self.spec_rounds)

    # -- decode pass launch (window) -----------------------------------------
    def _spec_gamma(self, job: _Job) -> int:
        """Draft length for the next verify round, clamped so every
        position could still be confirmed: the round produces output
        indices ``next_j .. next_j+γ`` (full acceptance exactly reaches
        ``max_new_tokens``) and writes cache rows ``next_pos .. next_pos+γ``
        (staying under ``max_len``)."""
        return max(0, min(self.spec_tokens,
                          job.req.max_new_tokens - job.next_j - 1,
                          self.ec.max_len - 1 - job.next_pos))

    def _draft_propose(self, job: _Job, gamma: int) -> List[int]:
        """Run the coordinator-side draft autoregressively: catch up on
        confirmed tokens it has not yet consumed (one multi-token decode
        over rows ``draft_pos..next_pos``), then propose ``gamma`` greedy
        tokens.  Rejected speculative rows from earlier rounds are simply
        overwritten — dense caches are positional and mask by pos."""
        eng, slot = self.draft, job.draft_slot
        req = job.req
        P = len(req.prompt)
        p = job.next_pos

        def tok_at(r: int) -> int:
            # row r >= P holds output[r - P] (prefill fed prompt+output
            # contiguously, so this covers resumed requests too)
            return int(req.prompt[r]) if r < P else int(req.output[r - P])

        catch = [tok_at(r) for r in range(job.draft_pos, p + 1)]
        out = eng.decode_stage([DecodeItem(slot=slot, pos=job.draft_pos,
                                           entry=0, tokens=catch,
                                           want_logits=False)])[0]
        cur = int(np.atleast_1d(out.token)[-1])
        drafts = [cur]
        for s in range(1, gamma):
            out = eng.decode_stage([DecodeItem(slot=slot, pos=p + s,
                                               entry=0, token=cur,
                                               want_logits=False)])[0]
            cur = out.token
            drafts.append(cur)
        job.draft_pos = p + 1        # rows 0..p are now confirmed-consumed
        return drafts

    def _maybe_launch(self, job: _Job, src: str, tok: int, expect_j: int
                      ) -> None:
        """Launch the decode pass producing output index ``expect_j`` if no
        one else has (the final stage races the coordinator for it), the
        hard budgets allow it to ever be confirmed, and the in-flight window
        has room.  Sampled-token speculation (eos still unseen by the
        coordinator) launches anyway — completion cancels it by epoch.

        Jobs holding a draft slot launch *verify* passes instead: γ draft
        proposals ride with the confirmed token as one multi-token pass.
        Only the coordinator can launch them (the draft lives there), and
        exactly one verify pass is in flight per request — the optimistic
        window ``next_j = j+γ+1`` closes the window until the round
        confirms or rolls back."""
        req = job.req
        spec = job.draft_slot is not None
        if spec and src != COORDINATOR:
            return                   # final stage cannot draft
        if req.done or job.next_j != expect_j:
            return
        if job.kv_pending:
            return                   # decode KV still in flight from prefill
        if job.next_j >= req.max_new_tokens or job.next_pos >= self.ec.max_len:
            return                   # pass could never be confirmed
        if spec and job.inflight != 0:
            return                   # one verify round in flight at a time
        if job.inflight >= self.max_inflight and not spec:
            return                   # window full: coordinator relaunches
        gamma = self._spec_gamma(job) if spec else 0
        pos, j, epoch = job.next_pos, job.next_j, job.epoch
        if not self._reserve_inflight(job, pos + gamma + 1):
            return                   # job itself was preempted reserving
        first = job.pipe.stages[0].node
        if gamma >= 1:
            drafts = self._draft_propose(job, gamma)
            job.spec_drafts = drafts
            job.spec_base = pos
            job.next_j = j + gamma + 1     # optimistic: rolled back on
            job.next_pos = pos + gamma + 1  # rejection (epoch bump)
            self.spec_rounds += 1
            self.spec_proposed += gamma
            toks = np.asarray([int(tok)] + drafts, np.int32)
            self._send(src, first, toks,
                       (gamma + 1) * self.profile.token_bytes,
                       lambda t, e=epoch, p=pos, jj=j, n=gamma + 1:
                       self._enqueue_decode(job, e, 0, 0, None, p, jj,
                                            toks=t, spec=True, nt=n))
            return
        job.next_j = j + 1
        job.next_pos = pos + 1
        self._send(src, first, int(tok), self.profile.token_bytes,
                   lambda t, e=epoch, p=pos, jj=j:
                   self._enqueue_decode(job, e, 0, int(t), None, p, jj))

    def _enqueue_decode(self, job: _Job, epoch: int, si: int, tok: int,
                        h, pos: int, j: int, toks=None, spec: bool = False,
                        nt: int = 1) -> None:
        """Delivery guard for decode stage-work: a duplicated delivery of
        the same (stage, output-index) pass is dropped — running it twice
        would double-decode the pass (and two copies in one batch would
        trip the engine's duplicate-slot invariant).  The epoch is part of
        the key: after a rejected verify rolls a job back, the same output
        index relaunches under a bumped epoch and must not be mistaken for
        a duplicate of the cancelled pass."""
        if job.epoch != epoch:
            return
        key = ("dw", si, j, epoch)
        if key in job.seen:
            return
        job.seen.add(key)
        node = job.pipe.stages[si].node
        self._ready[node].append(dict(job=job, epoch=epoch, si=si, tok=tok,
                                      h=h, pos=pos, j=j, toks=toks,
                                      spec=spec, nt=nt))

    def _grow_or_preempt(self, eng, node: str, job: _Job, tokens: int
                         ) -> bool:
        """Grow ``job``'s KV on ``node`` to hold ``tokens``, preempting the
        newest resident request (pipeline-wide) while the pool is dry.
        Returns False when the victim chain reached ``job`` itself."""
        epoch = job.epoch
        while not eng.ensure(job.slots[node], tokens):
            live = [j for j in self.jobs.values() if node in j.slots]
            victim = max(live, key=lambda j: j.seq)
            self._preempt(victim)
            if job.epoch != epoch:
                return False
        return True

    def _reserve_inflight(self, job: _Job, tokens: int) -> bool:
        """Reserve KV for an in-flight token on every stage node *at launch*
        so it can never land mid-pipeline on an exhausted pool; returns
        False when the job itself got preempted making room."""
        for st in job.pipe.stages:
            eng = self.engines.get(st.node)
            if eng is None or st.node not in job.slots:
                return False         # mid-failover: the job will requeue
            if not self._grow_or_preempt(eng, st.node, job, tokens):
                return False
        return True

    # -- decode (per-node continuous batching) -------------------------------
    def _decode_node(self, node: str, work: List[dict]) -> None:
        """All stage-work resident at ``node`` this iteration.  At most one
        decode pass per request is ever inside the stages (pass t+1 is born
        at the final stage only after pass t exits it), so ``work`` holds at
        most one item per request — ``stage_engine._assemble`` rejects
        duplicate cache slots if that invariant is ever broken."""
        eng = self.engines.get(node)
        if eng is None:
            return
        # grow pools oldest-first, as a backstop: launch-time reservation
        # makes this a cheap no-op unless another request raced the pool dry
        for w in sorted(work, key=lambda w: w["job"].seq):
            job = w["job"]
            if job.epoch != w["epoch"]:
                continue
            self._grow_or_preempt(eng, node, job, w["pos"] + w.get("nt", 1))
        while work:
            batch = [w for w in work[:self.ec.max_batch]
                     if w["job"].epoch == w["epoch"]]
            work = work[self.ec.max_batch:]
            if not batch:
                continue
            items = [DecodeItem(slot=w["job"].slots[node], pos=w["pos"],
                                entry=w["job"].pipe.stages[w["si"]]
                                .layers.start,
                                token=w["tok"], h=w["h"],
                                tokens=w.get("toks"),
                                want_logits=w["job"].req.temperature > 0)
                     for w in batch]
            fwds = None
            if getattr(eng, "forward_capable", False) and \
                    getattr(self.transport, "direct_links", False):
                fwds = []
                for w in batch:
                    pipe = w["job"].pipe
                    nxt = (None if w["si"] == len(pipe.stages) - 1
                           else pipe.stages[w["si"] + 1].node)
                    fwds.append(self._fwd_spec(eng, nxt))
            with TRACER.span("helix.decode", node=node,
                             rows=len(batch)) as sp:
                if fwds and any(f is not None for f in fwds):
                    outs = eng.decode_stage(items, fwds=fwds)
                else:
                    outs = eng.decode_stage(items)
            # straggler telemetry: wall seconds per batched token, per node
            self.node_decode_s[node] += sp.t1 - sp.t0
            self.node_decode_tokens[node] += sum(
                w.get("nt", 1) for w in batch)
            with TRACER.span("helix.sample", rows=len(batch)):
                self._route_outputs(node, eng, batch, outs)

    def _route_outputs(self, node: str, eng, batch: List[dict],
                       outs) -> None:
        """After a decode call: take the greedy token at the final stage
        (or sample one, at temperature > 0) and send it to the coordinator
        (launching the next pass), or send the activations on to the next
        stage."""
        for w, out in zip(batch, outs):
            job, si, epoch, j = w["job"], w["si"], w["epoch"], w["j"]
            if si == len(job.pipe.stages) - 1:
                if w.get("spec"):
                    # verify pass: no sampling, no node-side launch —
                    # the device's greedy ids (one per verified
                    # position) return to the coordinator, which owns
                    # acceptance and rollback
                    greedy = np.asarray(out.token, np.int32).reshape(-1)
                    self._send(node, COORDINATOR, (j, greedy),
                               len(greedy) * self.profile.token_bytes,
                               lambda p, jb=job, e=epoch:
                               self._on_spec_result(jb, e, p[0], p[1]))
                    continue
                tok = out.token if job.req.temperature <= 0 else \
                    eng.sample(out.logits, job.req.temperature)
                self._send(node, COORDINATOR, (j, tok),
                           self.profile.token_bytes,
                           lambda p, jb=job, e=epoch:
                           self._on_decode_token(jb, e, p[0], p[1]))
                # speculative: token j leaves for the coordinator while
                # the pass for j+1 leaves for stage 0
                self._maybe_launch(job, node, tok, j + 1)
            else:
                nxt = job.pipe.stages[si + 1].node
                n = w.get("nt", 1)
                self._send(node, nxt, out.h, self._act_bytes(n),
                           lambda h, jb=job, e=epoch, s=si + 1,
                           p=w["pos"], jj=j, sp=w.get("spec", False),
                           nn=n:
                           self._enqueue_decode(jb, e, s, 0, h, p, jj,
                                                spec=sp, nt=nn))

    # -- completion / preemption ---------------------------------------------
    def _release_all(self, job: _Job) -> None:
        for node, slot in job.slots.items():
            eng = self.engines.get(node)
            if eng is not None:
                eng.release(slot)
        job.slots = {}
        if job.draft_slot is not None and self.draft is not None:
            self.draft.release(job.draft_slot)
        job.draft_slot = None
        job.draft_pos = 0

    def _complete(self, job: _Job, reason: str) -> None:
        req = job.req
        req.done = True
        req.finish_reason = reason
        req.finished_s = self.clock()
        # cancel speculative in-flight passes (a stop confirmed while token
        # t+1 is mid-pipeline): the epoch bump kills their deliveries; KV
        # they reserved is released with the slots below
        self.cancelled_inflight += max(0, job.inflight)
        job.epoch += 1
        job.inbox = {}
        t0 = self._vfirst.pop(req.request_id, None)
        if t0 is not None and len(req.output) > 1:
            self.decode_latencies[req.request_id] = \
                (self._now - t0) / (len(req.output) - 1)
        self._release_all(job)
        self.jobs.pop(req.request_id, None)
        self.completed += 1
        cb = self._listeners.pop(req.request_id, None)
        if cb is not None and cb[1] is not None:
            cb[1](req)

    def _preempt(self, job: _Job) -> None:
        """Pool exhausted: evict pipeline-wide, keep generated tokens, requeue
        at the front (recompute-on-readmit, same pipeline)."""
        TRACER.count("preemptions")
        self._requeue(job, clear_pipe=False)

    # -- failover ------------------------------------------------------------
    def fail_node(self, name: str) -> None:
        """Kill a node's engine; every request whose pipeline crossed it is
        requeued (its KV on survivors released) pending a replanned pipeline."""
        eng = self.engines.pop(name, None)
        close = getattr(eng, "close", None)
        if callable(close):
            close()                  # remote: drop the (possibly dead) channel
        proc = self.workers.pop(name, None)
        if proc is not None and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=10)    # reap: no zombie per failover
        for job in list(self.jobs.values()):
            if name in job.route.nodes:
                self._requeue(job, clear_pipe=True)
        for job in self.queue:
            if job.route is not None and name in job.route.nodes:
                job.pipe = None
                job.route = None

    def _requeue(self, job: _Job, clear_pipe: bool) -> None:
        job.epoch += 1               # cancels every in-flight pass
        job.inbox = {}
        job.kv_pending = set()       # readmission restarts any KV handoff
        self._release_all(job)
        if clear_pipe:
            job.pipe = None
            job.route = None
        self.jobs.pop(job.req.request_id, None)
        job.req.preemptions += 1
        job.t_queued = TRACER.clock()
        self.queue.appendleft(job)

    def apply_plan(self, plan) -> None:
        """Adopt a replanned placement: rebuild engines whose slice changed
        (requeueing their resident requests), swap IWRR weights in place when
        the placement survived, else install a fresh scheduler, and re-sync
        true pool occupancy into the KV estimator."""
        new_assign = plan.placement.assignment
        for node in [n for n in self.engines if n not in new_assign]:
            self.fail_node(node)
        old_assign = self.placement.assignment
        old_roles = (self.placement.meta or {}).get("roles")
        # install the new topology BEFORE building engines: pool sizing
        # reads node VRAM from self.cluster, and an autoscale scale-up plan
        # places layers on nodes that exist only in plan.cluster
        self.cluster = plan.cluster
        self.profile = plan.model
        changed = set()
        for node, rng in sorted(new_assign.items()):
            if node in self.engines and old_assign.get(node) == rng:
                continue
            changed.add(node)
            for job in list(self.jobs.values()):
                if node in job.slots:
                    self._requeue(job, clear_pipe=True)
            self.engines[node] = self._make_engine(node, rng, new_assign)
        # queued jobs (e.g. preempted ones holding their old pipeline) whose
        # cached pipeline crosses a rebuilt node would execute stale layer
        # ranges — force them to reschedule
        for job in self.queue:
            if job.route is not None and \
                    changed.intersection(job.route.nodes):
                job.pipe = None
                job.route = None
        same = (old_assign == new_assign
                and old_roles == (plan.placement.meta or {}).get("roles"))
        self.placement = plan.placement
        if same and not self.disaggregated and \
                self.scheduler.placement.assignment == new_assign:
            self.scheduler.update_weights(plan.flows)
        else:
            kv_old = self.scheduler.kv
            kv_pre = self.sched_prefill.kv
            self._build_role_schedulers(plan)
            if self.scheduler.kv is not None and kv_old is not None:
                self.scheduler.kv.high_water = kv_old.high_water
            if self.sched_prefill is not self.scheduler and \
                    self.sched_prefill.kv is not None and kv_pre is not None:
                self.sched_prefill.kv.high_water = kv_pre.high_water
        self._sync_kv(capacities=True)

    # -- introspection --------------------------------------------------------
    def node_occupancy(self) -> Dict[str, float]:
        """Per-node KV occupancy fraction (used tokens / capacity tokens) —
        the autoscaler's saturation signal.  Nodes whose engine exposes no
        KV accounting report 0.0."""
        out = {}
        for n, e in self.engines.items():
            used = getattr(e, "kv_tokens_used", None)
            cap = getattr(e, "kv_tokens_capacity", None)
            if callable(used) and callable(cap):
                c = cap()
                out[n] = (used() / c) if c else 0.0
            else:
                out[n] = 0.0
        return out

    def pool_pages_used(self) -> Dict[str, int]:
        out = {}
        for n, e in self.engines.items():
            used = e.pool_used()
            if used is not None:
                out[n] = used
        return out

    def mean_decode_latency(self) -> float:
        """Mean per-token decode latency on the virtual clock, over
        completed requests that decoded at least one token past prefill —
        the number the in-flight window is meant to shrink."""
        lats = list(self.decode_latencies.values())
        return sum(lats) / len(lats) if lats else 0.0

    # -- multi-process workers ------------------------------------------------
    @classmethod
    def spawn_workers(cls, cfg: ModelConfig, params, plan,
                      engine_cfg: EngineConfig, *,
                      connect: Optional[str] = None,
                      queue_depth: int = 8,
                      worker_timeout_s: float = 300.0,
                      direct_links: bool = False,
                      **kw) -> "ClusterRuntime":
        """Build a runtime whose stage engines live in separate OS
        processes behind a ``SocketTransport``.

        By default one ``repro.launch.worker`` subprocess is launched per
        placed node and dialled back over loopback TCP.  With ``connect``
        ("host:port") the coordinator instead listens there and waits for
        externally started workers (``python -m repro.launch.worker
        --connect host:port`` on each machine), accepting one per node in
        sorted-node order.  Everything a node needs — config, params, its
        layer slice, pool sizing — ships over the wire at init, so workers
        start from nothing but the address.

        Failover works by killing a worker (``kill_worker``/``fail_node``);
        ``apply_plan`` re-inits surviving workers whose slice moved over
        their existing channels and respawns processes for dead nodes that
        re-enter the placement.  Call ``shutdown()`` when done.

        Local workers are refused when this process runs on an accelerator:
        it already holds the chip, so a child could not open it.  There,
        build the in-process runtime (one stage engine per device) or
        start workers yourself and pass ``connect``.
        """
        if connect is None and jax.default_backend() != "cpu":
            raise RuntimeError(
                f"spawn_workers: this process holds the "
                f"{jax.default_backend()} device, so local worker processes "
                "could not open it.  Use the in-process ClusterRuntime, "
                "which puts each stage engine on its own device, or start "
                "workers yourself and pass connect='host:port'.")
        nodes = sorted(plan.placement.assignment)
        channels: Dict[str, WorkerChannel] = {}
        procs: Dict[str, Any] = {}

        def _spawn(node: str) -> WorkerChannel:
            lsock = _socket.socket()
            lsock.bind(("127.0.0.1", 0))
            lsock.listen(1)
            host, port = lsock.getsockname()
            env = dict(os.environ)
            src_root = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
            env["PYTHONPATH"] = src_root + (
                os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH")
                else "")
            proc = subprocess.Popen(
                [sys.executable, "-m", "repro.launch.worker",
                 "--connect", f"{host}:{port}",
                 "--timeout-s", str(worker_timeout_s)],
                env=env)
            lsock.settimeout(worker_timeout_s)
            try:
                conn, _ = lsock.accept()
            except _socket.timeout:
                proc.kill()
                raise RuntimeError(
                    f"worker for {node} did not dial back within "
                    f"{worker_timeout_s}s") from None
            finally:
                lsock.close()
            procs[node] = proc
            return WorkerChannel(conn, node=node, timeout_s=worker_timeout_s)

        if connect is not None:
            host, _, port = connect.rpartition(":")
            lsock = _socket.socket()
            lsock.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
            lsock.bind((host or "0.0.0.0", int(port)))
            lsock.listen(len(nodes))
            lsock.settimeout(worker_timeout_s)
            print(f"waiting for {len(nodes)} workers on {connect} ...")
            try:
                for node in nodes:
                    conn, addr = lsock.accept()
                    channels[node] = WorkerChannel(conn, node=node,
                                                   timeout_s=worker_timeout_s)
                    print(f"  {node} <- worker at {addr[0]}:{addr[1]}")
            finally:
                lsock.close()
        else:
            for node in nodes:
                channels[node] = _spawn(node)

        transport = SocketTransport(channels, queue_depth=queue_depth,
                                    direct_links=direct_links)
        cfg_wire = dataclasses.asdict(cfg)
        ec_wire = dataclasses.asdict(engine_cfg)

        def _wire_peers() -> None:
            """(Re)build the worker-to-worker mesh: ask every live worker
            for its peer listener port, then broadcast the full address
            book.  Runs after every init/respawn so a replaced worker's
            new port propagates; workers drop channels whose address
            changed and re-dial lazily."""
            addrs: Dict[str, Tuple[str, int]] = {}
            for node, ch in sorted(channels.items()):
                if not ch.alive:
                    continue
                try:
                    port = ch.call("peer_addr")
                    host = ch.sock.getpeername()[0]
                except (WorkerDied, OSError):
                    continue
                addrs[node] = (host, int(port))
            for node, ch in sorted(channels.items()):
                if not ch.alive:
                    continue
                try:
                    ch.call("set_peers", addrs)
                except (WorkerDied, OSError):
                    pass

        def factory(rt: "ClusterRuntime", node: str, rng: LayerRange):
            # converted per init/respawn and then dropped — holding a
            # permanent numpy copy would double the coordinator's weight
            # footprint for the runtime's whole life
            params_np = jax.tree.map(np.asarray, rt.params)
            ch = channels.get(node)
            if ch is None or not ch.alive:
                if connect is not None:
                    raise WorkerDied(
                        f"no live worker for {node} and external workers "
                        "cannot be respawned by the coordinator")
                ch = _spawn(node)
                channels[node] = ch
                rt.workers[node] = procs[node]
                transport.channels[node] = ch
                transport.dead.discard(node)
            spec = rt._engine_spec(node, rng)
            ch.call("init", {
                "node": node, "cfg": cfg_wire, "ec": ec_wire,
                "layers": (rng.start, rng.end), "params": params_np,
                "paged": spec["paged"], "num_pages": spec["num_pages"],
                "page_size": rt.page_size, "kv_dtype": spec["kv_dtype"],
                "rng_seed": rt.rng_seed})
            if direct_links:
                _wire_peers()
            return RemoteStageEngine(ch, node, rng_seed=rt.rng_seed)

        rt = cls(cfg, params, plan, engine_cfg, transport=transport,
                 engine_factory=factory, **kw)
        rt.workers.update(procs)
        return rt

    def kill_worker(self, name: str) -> None:
        """Hard-kill a node's worker process (fault injection: SIGKILL, no
        cleanup) — the caller then drives ``fail_node`` + replan +
        ``apply_plan`` exactly as for any node loss."""
        proc = self.workers.get(name)
        if proc is None:
            raise ValueError(f"{name} has no worker process")
        proc.kill()
        proc.wait(timeout=30)

    def shutdown(self) -> None:
        """Tear down remote workers and transport threads (no-op for pure
        in-process runtimes)."""
        for eng in self.engines.values():
            close = getattr(eng, "close", None)
            if callable(close):
                close()
        close = getattr(self.transport, "close", None)
        if callable(close):
            close()
        for proc in self.workers.values():
            if proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
        self.workers.clear()
