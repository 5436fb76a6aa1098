"""Pallas TPU kernel: streaming entity-inference ranking.

The paper's evaluation hot loop scores EVERY entity as a candidate
replacement for each test triplet — an O(B·E·k) sweep that dominates eval
wall-time on Freebase-scale tables.  A naive lowering materializes the
(B, E) distance matrix in HBM; this kernel streams entity-table tiles
through VMEM and keeps only a running (B,) counter of entities strictly
closer than the gold — the rank — FlashAttention-style two-level tiling
adapted from softmax-accumulation to metric ranking (DESIGN.md §3).

TPU adaptation:
  * L2 path: expand ||q - e||² = ||q||² - 2 q·e + ||e||² so the O(B·E·k)
    contraction is a (TB, k) x (k, TE) matmul — it runs on the MXU. Tiles
    are multiples of 128 to match the MXU/lane geometry.
  * L1 path: no contraction form exists; the (TB, TE, k) |diff| reduce runs
    on the VPU with k as the minor (lane) axis.
  * Accumulation across entity tiles exploits Pallas' revisiting-output
    semantics: the count block's index_map ignores the entity-tile index, so
    it stays resident in VMEM while the inner grid dimension sweeps E.

VMEM: Mosaic gives a kernel a scoped VMEM stack (16 MiB on v5e), and the
largest live value of the L1 body is its ``|diff|`` tensor.  The body
reduces ``k`` in slices of ``LANES`` columns, so that tensor is
``(TB, TE, LANES)`` whatever ``k`` is, and :func:`_tiles` sizes ``TB`` so
it stays within ``L1_DIFF_BUDGET`` — the one place the budget is set.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_TB = 256   # query tile (rows)
DEFAULT_TE = 512   # entity-table tile (rows)
LANES = 128        # L1 reduces k in slices this wide (one vreg of lanes)
# bytes of the L1 body's (TB, TE, LANES) fp32 |diff| slice: a quarter of
# v5e's 16 MiB scoped VMEM leaves room for the double-buffered input tiles
# and Mosaic's own temporaries (a 16.6 MiB stack at TB=256, TE=128 was
# refused when the whole of k was reduced at once)
L1_DIFF_BUDGET = 4 * 1024 * 1024


def _tiles(B: int, E: int, norm: str) -> tuple[int, int]:
    """(tb, te) for a ``(B, k)`` query block against an ``(E, k)`` table:
    the default tiles, clamped to the problem, with ``tb`` cut for L1 so
    the ``|diff|`` slice fits ``L1_DIFF_BUDGET``.  Tiles stay multiples of
    8 (the sublane count) unless a tile spans its whole axis."""
    te = min(DEFAULT_TE, max(8, E))
    tb = DEFAULT_TB
    if norm == "l1":
        tb = max(8, L1_DIFF_BUDGET // (te * LANES * 4) // 8 * 8)
    return min(tb, max(8, B)), te


def _kernel(q_ref, tab_ref, gold_ref, cnt_ref, *, norm: str):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    gold = gold_ref[...].astype(jnp.float32)    # (TB, 1)

    if norm == "l1":
        # reduce k one LANES-wide slice at a time (k is padded to a
        # multiple of LANES): the live |diff| tensor is (TB, TE, LANES)
        # however wide the embedding is
        d = None
        for lo in range(0, q_ref.shape[1], LANES):
            q = q_ref[:, lo:lo + LANES].astype(jnp.float32)
            tab = tab_ref[:, lo:lo + LANES].astype(jnp.float32)
            part = jnp.sum(jnp.abs(q[:, None, :] - tab[None, :, :]), axis=-1)
            d = part if d is None else d + part
    else:
        q = q_ref[...].astype(jnp.float32)          # (TB, k)
        tab = tab_ref[...].astype(jnp.float32)      # (TE, k)
        qq = jnp.sum(q * q, axis=-1, keepdims=True)              # (TB, 1)
        tt = jnp.sum(tab * tab, axis=-1)[None, :]                # (1, TE)
        # MXU contraction
        qt = jax.lax.dot_general(
            q, tab, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        d = jnp.sqrt(jnp.maximum(qq - 2.0 * qt + tt, 0.0) + 1e-12)

    closer = (d < gold).astype(jnp.float32)                      # (TB, TE)
    cnt_ref[...] += jnp.sum(closer, axis=1, keepdims=True)


def rank_counts(
    queries: jax.Array,        # (B, k)
    table: jax.Array,          # (E, k)
    gold_d: jax.Array,         # (B,)
    *,
    norm: str = "l1",
    tb: int | None = None,
    te: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Count of entities strictly closer than gold, per query: (B,) int32.
    rank = 1 + count.  Inputs are padded here; pad rows of the table get
    +inf-like distances and never count.  ``tb``/``te`` override the tiles
    :func:`_tiles` picks."""
    B, k = queries.shape
    E = table.shape[0]

    auto_tb, auto_te = _tiles(B, E, norm)
    tb = auto_tb if tb is None else min(tb, max(8, B))
    te = auto_te if te is None else min(te, max(8, E))
    Bp = -(-B // tb) * tb
    Ep = -(-E // te) * te

    # L1 slices k LANES columns at a time: zero columns add exactly 0
    kp = -(-k // LANES) * LANES if norm == "l1" else k
    qp = jnp.zeros((Bp, kp), queries.dtype).at[:B, :k].set(queries)
    # pad entities FAR away: distance to anything is huge -> never "closer"
    tp = (jnp.zeros((Ep, kp), table.dtype).at[:, :k].set(1e9)
          .at[:E, :k].set(table))
    gp = jnp.zeros((Bp, 1), jnp.float32).at[:B, 0].set(gold_d.astype(jnp.float32))

    grid = (Bp // tb, Ep // te)

    cnt = pl.pallas_call(
        functools.partial(_kernel, norm=norm),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((te, kp), lambda i, j: (j, 0)),
            pl.BlockSpec((tb, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tb, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
        interpret=interpret,
        name="rank_counts",
    )(qp, tp, gp)
    return cnt[:B, 0].astype(jnp.int32)
