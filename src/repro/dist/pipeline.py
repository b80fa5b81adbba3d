"""Helix-placement-driven pipeline parallelism.

The MILP planner (``repro.core``) assigns each heterogeneous node a
contiguous layer range; this module turns that placement into an *unequal*
GPipe pipeline executed with ``shard_map`` over a ``("stage", "data")``
mesh (HexGen-style asymmetric partitioning: a 4-chip slice gets a bigger
stage than a 1-chip slice).

Layout: the repeated super-block stack (``params["super"]``, leading
"layers" axis of length ``cfg.repeats``) is re-stacked to a
``(num_stages, max_units, ...)`` array sharded along the mesh "stage" axis.
Stages holding fewer than ``max_units`` super-blocks mask the padded scan
steps to identity, so the compiled program is SPMD-uniform while the math
follows the uneven placement exactly.

Schedule: classic GPipe fill/steady/drain — ``num_microbatches +
num_stages - 1`` ticks; each tick every stage applies its blocks to the
activation received from its predecessor (``lax.ppermute`` shift along
"stage"), stage 0 ingests a fresh microbatch, the last stage accumulates
masked token-level NLL sums.  The final loss psums the (nll, count)
accumulators over ("stage", "data") and divides, which reproduces the
single-program ``models.loss_fn`` to float tolerance and is differentiable
end to end (ppermute and the masked scans all have transposes).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..configs.base import ModelConfig
from ..core.placement import Placement
from ..models.common import ParamSpec, apply_norm


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    num_stages: int
    stage_units: Tuple[int, ...]     # super-blocks per stage (may be uneven)
    num_microbatches: int = 1

    def __post_init__(self):
        assert self.num_stages >= 1
        assert len(self.stage_units) == self.num_stages, \
            (self.stage_units, self.num_stages)
        assert all(u >= 0 for u in self.stage_units), self.stage_units
        assert self.num_microbatches >= 1

    @property
    def max_units(self) -> int:
        return max(self.stage_units)

    @property
    def total_units(self) -> int:
        return sum(self.stage_units)


# ---------------------------------------------------------------------------
# Helix placement -> stage sizes
# ---------------------------------------------------------------------------

def stage_units_from_placement(placement: Placement, cfg: ModelConfig,
                               order: Sequence[str]) -> List[int]:
    """Map a Helix placement's per-node layer ranges to per-stage
    super-block counts, in pipeline ``order``.

    The planner's layer axis may be expressed either in super-block units
    (``placement.num_layers == cfg.repeats``) or in raw model layers
    (``== len(cfg.pattern) * cfg.repeats``); both map to stage sizes in
    super-block units — the granularity the ``lax.scan`` pipeline executes.

    Replicated placements are reduced Helix-style (§3.3 partial inference):
    a node only contributes the layers not yet covered by earlier stages;
    nodes fully covered by their predecessors are dropped.  Gaps or splits
    that cut through a super-block raise ``ValueError``.
    """
    pat = max(1, len(cfg.pattern))
    total = placement.num_layers
    if cfg.prologue and total == cfg.num_layers:
        raise ValueError(
            "placements over prologue layers cannot be pipelined; plan over "
            f"the {cfg.repeats}-super-block repeated stack instead")
    if total == cfg.repeats:
        per_unit = 1
    elif total == cfg.repeats * pat:
        per_unit = pat
    else:
        raise ValueError(
            f"placement covers {total} layers; expected {cfg.repeats} "
            f"(super-block units) or {cfg.repeats * pat} (raw layers) "
            f"for {cfg.name}")
    units: List[int] = []
    cursor = 0
    for node in order:
        rng = placement.assignment[node]
        if rng.end <= cursor:
            continue  # fully covered by earlier stages (replicated node)
        start = max(rng.start, cursor)
        if start > cursor:
            raise ValueError(
                f"layer gap before {node}: covered up to {cursor}, "
                f"next range starts at {rng.start}")
        take = rng.end - cursor
        if take % per_unit:
            raise ValueError(
                f"{node}: stage boundary at layer {rng.end} cuts through a "
                f"{per_unit}-layer super-block")
        units.append(take // per_unit)
        cursor = rng.end
    if cursor != total:
        raise ValueError(f"placement covers layers [0, {cursor}) of {total}")
    return units


# ---------------------------------------------------------------------------
# Stage-stacked param specs
# ---------------------------------------------------------------------------

def pipeline_param_specs(cfg: ModelConfig, pipe: PipelineConfig) -> Dict:
    """ParamSpec tree for the pipelined model.

    Identical to ``models.param_specs`` except ``"super"`` leaves gain a
    leading ("stage", max_units) layout replacing the flat ("layers",)
    stack; entries past a stage's real unit count are padding (masked to
    identity at apply time).  Stage s holds super-blocks
    ``[sum(units[:s]), sum(units[:s+1]))`` of the flat stack.
    """
    if cfg.prologue or cfg.is_encoder_decoder:
        raise NotImplementedError(
            "pipeline parallelism covers the repeated super-block stack; "
            f"{cfg.name} has prologue/encoder blocks")
    from ..models import model as M
    base = M.param_specs(cfg)
    S, U = pipe.num_stages, pipe.max_units

    def restack(s: ParamSpec) -> ParamSpec:
        # base "super" leaves are ("layers",)+axes with shape (repeats, ...)
        return ParamSpec((S, U) + s.shape[1:], ("stage",) + s.axes,
                         init=s.init, scale=s.scale)

    out = dict(base)
    out["super"] = jax.tree.map(restack, base["super"],
                                is_leaf=lambda x: isinstance(x, ParamSpec))
    return out


def flatten_pipeline_params(params, pipe: PipelineConfig):
    """Inverse of the stage stacking: (S, U, ...) pipeline "super" leaves ->
    the single-program (repeats, ...) layer stack (drops padding)."""
    def unstack(x):
        parts = [x[s, :u] for s, u in enumerate(pipe.stage_units) if u]
        return jnp.concatenate(parts, axis=0)
    out = dict(params)
    out["super"] = jax.tree.map(unstack, params["super"])
    return out


# ---------------------------------------------------------------------------
# Pipelined loss
# ---------------------------------------------------------------------------

def _ce_sums(logits: jax.Array, labels: jax.Array):
    """(sum of NLL over valid tokens, valid-token count) — the same masked
    cross-entropy as models.loss_fn, pre-normalization."""
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return jnp.where(valid, nll, 0.0).sum(), valid.sum().astype(jnp.float32)


def make_pipeline_loss(cfg: ModelConfig, pipe: PipelineConfig, mesh: Mesh,
                       *, aux_weight: float = 0.0):
    """Jitted ``loss(params, batch) -> scalar`` running the GPipe schedule
    over mesh axes ("stage", "data").

    ``params`` comes from ``pipeline_param_specs``; ``batch`` holds
    ``tokens``/``labels`` of shape (B, S) with B divisible by
    ``data_size * num_microbatches``.  Matches ``models.loss_fn(...,
    aux_weight=0.0)`` exactly up to float reassociation; MoE aux losses are
    averaged over microbatches (a per-microbatch approximation of the
    full-batch load-balance term).
    """
    from ..models import model as M
    assert "stage" in mesh.axis_names and "data" in mesh.axis_names, \
        mesh.axis_names
    S = pipe.num_stages
    n_mb = pipe.num_microbatches
    U = pipe.max_units
    units_arr = jnp.asarray(pipe.stage_units, jnp.int32)
    if pipe.total_units != cfg.repeats:
        raise ValueError(f"stage_units {pipe.stage_units} sum to "
                         f"{pipe.total_units}; {cfg.name} has "
                         f"{cfg.repeats} super-blocks")

    def stage_apply(sup, h, positions, my_units):
        """Apply this stage's super-blocks; padded units are identity."""
        def unit(carry, xs):
            h, aux_acc = carry
            u, layer_params = xs
            hn, aux_u = h, jnp.zeros((), jnp.float32)
            for i, b in enumerate(cfg.pattern):
                hn, _, a = M._apply_block(cfg, b, layer_params[f"pos{i}"],
                                          hn, positions, None)
                aux_u = aux_u + a
            keep = u < my_units
            return (jnp.where(keep, hn, h),
                    aux_acc + jnp.where(keep, aux_u, 0.0)), None
        (h, aux), _ = jax.lax.scan(unit, (h, jnp.zeros((), jnp.float32)),
                                   (jnp.arange(U), sup))
        return h, aux

    def shard_body(params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        sup = jax.tree.map(lambda x: x[0], params["super"])  # drop stage dim
        stage = jax.lax.axis_index("stage")
        my_units = units_arr[stage]
        B_loc, S_seq = tokens.shape
        if B_loc % n_mb:
            raise ValueError(
                f"per-data-shard batch {B_loc} not divisible by "
                f"{n_mb} microbatches")
        mb = B_loc // n_mb
        tok_mb = tokens.reshape(n_mb, mb, S_seq)
        lab_mb = labels.reshape(n_mb, mb, S_seq)
        positions = jnp.broadcast_to(jnp.arange(S_seq), (mb, S_seq))
        is_first = stage == 0
        is_last = stage == S - 1
        state0 = jnp.zeros((mb, S_seq, cfg.d_model), params["embed"].dtype)
        perm = [(i, i + 1) for i in range(S - 1)]

        def tick(carry, t):
            state, nll_sum, valid_sum, aux_sum = carry
            # stage 0 ingests microbatch t; others consume the shifted state
            t_in = jnp.clip(t, 0, n_mb - 1)
            tok = jax.lax.dynamic_index_in_dim(tok_mb, t_in, 0,
                                               keepdims=False)
            emb = jnp.take(params["embed"], tok, axis=0)
            x = jnp.where(is_first, emb, state)
            h, aux = stage_apply(sup, x, positions, my_units)
            # this stage processed microbatch t - stage (if in range)
            live = jnp.logical_and(t - stage >= 0, t - stage < n_mb)
            aux_sum = aux_sum + jnp.where(live, aux, 0.0)
            # last stage: microbatch t - (S-1) just finished.  The vocab
            # projection + CE only run on live last-stage ticks (lax.cond),
            # not in every stage's bubble ticks
            t_out = t - (S - 1)
            done = jnp.logical_and(
                is_last, jnp.logical_and(t_out >= 0, t_out < n_mb))
            lab = jax.lax.dynamic_index_in_dim(
                lab_mb, jnp.clip(t_out, 0, n_mb - 1), 0, keepdims=False)

            def ce(operand):
                h, lab = operand
                hn = apply_norm(cfg, params["final_norm"], h)
                return _ce_sums(M._logits(cfg, params, hn), lab)

            zero = jnp.zeros((), jnp.float32)
            nll, cnt = jax.lax.cond(done, ce, lambda _: (zero, zero),
                                    (h, lab))
            nll_sum = nll_sum + nll
            valid_sum = valid_sum + cnt
            state = jax.lax.ppermute(h, "stage", perm) if perm else h
            return (state, nll_sum, valid_sum, aux_sum), None

        zero = jnp.zeros((), jnp.float32)
        (_, nll_sum, valid_sum, aux_sum), _ = jax.lax.scan(
            tick, (state0, zero, zero, zero),
            jnp.arange(n_mb + S - 1))
        nll = jax.lax.psum(nll_sum, ("stage", "data"))
        valid = jax.lax.psum(valid_sum, ("stage", "data"))
        aux = jax.lax.psum(aux_sum, ("stage", "data")) / n_mb
        return nll / jnp.maximum(valid, 1.0) + aux_weight * aux

    specs = pipeline_param_specs(cfg, pipe)

    def leaf_spec(s: ParamSpec) -> P:
        if s.axes and s.axes[0] == "stage":
            return P(*(("stage",) + (None,) * (len(s.shape) - 1)))
        return P(*((None,) * len(s.shape)))

    pspecs = jax.tree.map(leaf_spec, specs,
                          is_leaf=lambda x: isinstance(x, ParamSpec))
    bspec = P("data", None)
    fn = jax.shard_map(shard_body, mesh=mesh,
                       in_specs=(pspecs, {"tokens": bspec, "labels": bspec}),
                       out_specs=P(), check_vma=False)
    return jax.jit(fn)
