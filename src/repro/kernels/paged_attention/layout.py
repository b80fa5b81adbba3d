"""The stored layout of a paged K/V pool: the one rule the pool, its
writes, the decode kernel and the pool sizing share.

A page of K (or V) holds ``page`` tokens x ``KH`` kv heads x ``D`` lanes,
and a pool stores it as ``rows`` rows of ``lanes = lcm(D, 128)`` lanes:
``(num_pages, rows, lanes)``, each row holding ``lanes / D`` consecutive
(token, kv head) records, tokens major.  On a TPU such an array keeps its
written order under the chip's ``(8, 128)`` tiles when ``rows`` is a
multiple of 8 (or 1, 2 or 4), so one page is whole tiles that the kernel
DMAs as they lie and no program relayouts the pool; ``page_layout``
refuses a page size that gives any other row count.  OLMo-1B (16 kv heads
of 128, page 16) stores ``(P, 256, 128)``, a bitcast of the token view
``(P, 16, 16, 128)``; SmolLM-360M (5 of 64, page 16) stores
``(P, 40, 128)``: two records a row, a token 2.5 rows.  The token view
``(P, page, KH, D)`` would make XLA keep SmolLM's pool pages-minor, since
``(5, 64)`` pads 3.2x under ``(8, 128)`` tiles, and relayout the whole
pool for every kernel call.

Writes go by *runs*: ``run_tokens`` consecutive tokens of a page that are
``run_rows`` rows of whole tiles (one token of 16 rows for OLMo-1B; for
SmolLM-360M the whole page, 16 tokens of 40 rows, since 2.5 rows a token
meet a tile's 8 rows only there).  ``write_tokens`` scatters the runs a
write touches, reading them first where a run holds tokens it does not
write.  A scatter of rows that are not whole tiles makes XLA relayout the
whole pool (measured in the compiled program: a 5-row window copies
SmolLM-360M's 5 GB K pool).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

LANES = 128        # the minor tile dimension of the chip's (8, 128) tiles
TILE_ROWS = 8      # its second-minor one


def tiled(rows: int) -> int:
    """Rows the chip stores for ``rows`` rows of a tiled array: 1, 2, 4 and
    multiples of 8 as they are, other counts rounded up to 8."""
    return rows if rows in (1, 2, 4) else -(-rows // TILE_ROWS) * TILE_ROWS


@dataclasses.dataclass(frozen=True)
class PageLayout:
    """How a pool stores one page of K (or V): ``rows`` rows of ``lanes``
    lanes, ``records`` (token, kv head) records a row, tokens major.  Runs
    of ``run_tokens`` tokens are ``run_rows`` rows of whole tiles, the unit
    a write scatters."""

    page: int
    kv_heads: int
    head_dim: int

    @property
    def lanes(self) -> int:
        return math.lcm(self.head_dim, LANES)

    @property
    def records(self) -> int:
        return self.lanes // self.head_dim

    @property
    def rows(self) -> int:
        return self.page * self.kv_heads // self.records

    @property
    def run_tokens(self) -> int:
        """Fewest tokens that are whole tiles of rows (a page, if it is
        less than a tile)."""
        if self.rows < TILE_ROWS:
            return self.page
        tile = TILE_ROWS * self.lanes
        return tile // math.gcd(self.kv_heads * self.head_dim, tile)

    @property
    def run_rows(self) -> int:
        return self.run_tokens * self.kv_heads // self.records

    def shape(self, num_pages: int):
        """The stored pool's shape."""
        return (num_pages, self.rows, self.lanes)

    def page_bytes(self, itemsize: int) -> int:
        """Bytes the chip stores for one page of K (or V)."""
        return tiled(self.rows) * self.lanes * itemsize

    def logical_bytes(self, itemsize: int) -> int:
        """Bytes one page of K (or V) holds."""
        return self.page * self.kv_heads * self.head_dim * itemsize

    def to_rows(self, x: jax.Array) -> jax.Array:
        """(..., page, KH, D) tokens -> (..., rows, lanes) stored pages."""
        return x.reshape(x.shape[:-3] + (self.rows, self.lanes))

    def to_tokens(self, x: jax.Array) -> jax.Array:
        """(..., rows, lanes) stored pages -> (..., page, KH, D) tokens."""
        return x.reshape(x.shape[:-2]
                         + (self.page, self.kv_heads, self.head_dim))


def page_layout(page: int, kv_heads: int, head_dim: int) -> PageLayout:
    """The stored layout of a pool's pages; refuses a page size whose
    pages are not whole runs of whole tiles."""
    lay = PageLayout(page, kv_heads, head_dim)
    if page * kv_heads % lay.records or page % lay.run_tokens \
            or tiled(lay.rows) != lay.rows:
        raise ValueError(
            f"a page of {page} tokens x {kv_heads} kv heads x {head_dim} is "
            f"{page * kv_heads / lay.records:g} rows of {lay.lanes} lanes, "
            f"not whole (8, 128) tiles: take a page size that makes it 1, "
            f"2, 4 or a multiple of 8 rows")
    return lay


def pool_layout(pool_shape, head_dim: int, *, page: int | None = None,
                kv_heads: int | None = None) -> PageLayout:
    """The layout of a stored ``(P, rows, lanes)`` pool, from its page size
    or its kv heads (the other follows from the rows)."""
    _, R, L = pool_shape
    if L != math.lcm(head_dim, LANES):
        raise ValueError(f"a pool of {L} lanes does not hold records of "
                         f"{head_dim}")
    if page is None:
        page = R * (L // head_dim) // kv_heads
    else:
        kv_heads = R * (L // head_dim) // page
    lay = page_layout(page, kv_heads, head_dim)
    if lay.rows != R:
        raise ValueError(f"pages of {R} rows do not hold {page} tokens x "
                         f"{kv_heads} kv heads x {head_dim}")
    return lay


def write_tokens(pages: jax.Array, block_table: jax.Array, start: jax.Array,
                 new: jax.Array, page: int) -> jax.Array:
    """Write ``new`` (B, C, KH, D) at positions ``start .. start+C-1`` of
    each row's paged sequence into a pool stored as ``page_layout`` gives.

    The pool is seen as runs, ``(P, page / run_tokens, run_rows, lanes)``
    (a bitcast: a run is whole tiles), and each run the write touches is
    scattered whole by its (page, run) index.  Where a run holds more than
    one token, the runs are read first and the tokens the write does not
    cover kept; run slots past a row's table (or of no touched run) go to
    the scratch page 0.  Nothing pool-sized is copied or relaid out."""
    P, R, L = pages.shape
    B, C, KH, D = new.shape
    lay = page_layout(page, KH, D)
    m, rr, k = lay.run_tokens, lay.run_rows, lay.records
    NP = block_table.shape[1]
    runs = pages.reshape(P, page // m, rr, L)
    new = new.astype(pages.dtype)
    if m == 1:                       # a token is whole tiles
        tok = start[:, None] + jnp.arange(C)[None, :]           # (B, C)
        pid = jnp.take_along_axis(block_table, tok // page, axis=1)
        win = new.reshape(B, C, rr, L)
    else:
        NR = (C + m - 2) // m + 1                 # runs C tokens can touch
        tok = (start[:, None] // m + jnp.arange(NR)[None, :]) * m
        live = (tok < start[:, None] + C) & (tok // page < NP)
        pid = jnp.where(live, jnp.take_along_axis(
            block_table, jnp.minimum(tok // page, NP - 1), axis=1), 0)
        W, n = NR * rr, C * KH       # the window's rows, records written
        old = runs[pid, tok % page // m].reshape(B, W, L)
        # the new records as rows that start at each record slot of a row
        # (relayouts of the new records only), shifted by whole rows to
        # each token of a run the write can start at, and selected: no
        # token view of the window, no gather
        recs = new.reshape(B, n, D)
        phased = [jnp.pad(recs, ((0, 0), (p, -(n + p) % k), (0, 0)))
                  .reshape(B, -1, L) for p in range(k)]
        t = (start % m)[:, None, None]
        placed = old
        for s in range(m):
            r0, p = divmod(s * KH, k)
            rows = phased[p]
            placed = jnp.where(t == s, jnp.pad(
                rows, ((0, 0), (r0, W - r0 - rows.shape[1]), (0, 0))),
                placed)
        slot = k * jnp.arange(W)[:, None] + jnp.arange(L)[None, :] // D
        rel = slot[None] - t * KH                # record written at a slot
        win = jnp.where((rel >= 0) & (rel < n), placed,
                        old).reshape(B, NR, rr, L)
    return runs.at[pid, tok % page // m].set(win).reshape(P, R, L)
