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

Complex-modulus path (``modulus_rank_counts``, RotatE): rows are ``k``
complex numbers stored ``[re | im]``, and a distance is ``sum_j |q_j -
e_j|`` over complex moduli.  Each half is padded to a multiple of
``LANES`` with zeros (a zero column adds ``sqrt(0) = 0`` exactly), and the
body pairs each ``LANES``-wide slice of the real half with the matching
slice of the imaginary half.  Same tiling, grid, padding and resident
count as the L1 path; its Pallas call has a name of its own.

VMEM: Mosaic gives a kernel a scoped VMEM stack (16 MiB on v5e), and the
largest live value of the L1 body is its ``|diff|`` tensor.  The body
reduces ``k`` in slices of ``LANES`` columns, so that tensor is
``(TB, TE, LANES)`` whatever ``k`` is (two of them live in the modulus
body), and :func:`_tiles` sizes ``TB`` so they stay within
``L1_DIFF_BUDGET`` — the one place the budget is set.  Rows wider than
``TABLE_TILE_BUDGET`` allows at ``DEFAULT_TE`` take a shorter entity tile,
so a double-buffered table tile stays small beside them.
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
# bytes of one (TE, padded width) fp32 table tile: 1 MiB at TE=512 for
# TransE's padded 512 columns, so only rows wider than 1,024 floats (the
# modulus path's 2 x 1,024) shorten the entity tile
TABLE_TILE_BUDGET = 2 * 1024 * 1024


def _tiles(B: int, E: int, norm: str, width: int = 0,
           slices: int = 1) -> tuple[int, int]:
    """(tb, te) for a ``(B, width)`` query block against an ``(E, width)``
    table: the default tiles, clamped to the problem, with ``te`` cut so a
    table tile fits ``TABLE_TILE_BUDGET`` and ``tb`` cut for L1 so the
    ``slices`` live ``|diff|`` slices fit ``L1_DIFF_BUDGET``.  Tiles stay
    multiples of 8 (the sublane count) unless a tile spans its whole
    axis."""
    te = min(DEFAULT_TE, max(8, E))
    if width:
        te = min(te, max(8, TABLE_TILE_BUDGET // (width * 4) // 8 * 8))
    tb = DEFAULT_TB
    if norm == "l1":
        tb = max(8, L1_DIFF_BUDGET // (slices * te * LANES * 4) // 8 * 8)
    return min(tb, max(8, B)), te


def _init_count(cnt_ref, gold_ref) -> jax.Array:
    """Zero the resident count at the first entity tile; the ``(TB, 1)``
    gold distances."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    return gold_ref[...].astype(jnp.float32)


def _count_closer(cnt_ref, gold, d):
    """Add the table rows of this tile strictly closer than the gold."""
    closer = (d < gold).astype(jnp.float32)                      # (TB, TE)
    cnt_ref[...] += jnp.sum(closer, axis=1, keepdims=True)


def _kernel(q_ref, tab_ref, gold_ref, cnt_ref, *, norm: str):
    gold = _init_count(cnt_ref, gold_ref)       # (TB, 1)

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

    _count_closer(cnt_ref, gold, d)


def _modulus_kernel(q_ref, tab_ref, gold_ref, cnt_ref):
    """Sum of complex moduli: rows are ``[re | im]``, each half padded to
    a multiple of LANES; slice ``lo`` of the real half pairs with slice
    ``half + lo`` of the imaginary half, so two ``(TB, TE, LANES)``
    differences are live at once."""
    gold = _init_count(cnt_ref, gold_ref)
    half = q_ref.shape[1] // 2
    d = None
    for lo in range(0, half, LANES):
        re = (q_ref[:, lo:lo + LANES].astype(jnp.float32)[:, None, :]
              - tab_ref[:, lo:lo + LANES].astype(jnp.float32)[None, :, :])
        hi = half + lo
        im = (q_ref[:, hi:hi + LANES].astype(jnp.float32)[:, None, :]
              - tab_ref[:, hi:hi + LANES].astype(jnp.float32)[None, :, :])
        part = jnp.sum(jnp.sqrt(re * re + im * im), axis=-1)
        d = part if d is None else d + part
    _count_closer(cnt_ref, gold, d)


def _grid_tiles(B: int, E: int, norm: str, width: int, slices: int,
                tb: int | None, te: int | None) -> tuple[int, int]:
    """:func:`_tiles`, with ``tb``/``te`` overrides clamped the same way."""
    auto_tb, auto_te = _tiles(B, E, norm, width, slices)
    tb = auto_tb if tb is None else min(tb, max(8, B))
    te = auto_te if te is None else min(te, max(8, E))
    return tb, te


def _padded_call(body, name: str, qp: jax.Array, tp: jax.Array,
                 gold_d: jax.Array, B: int, tb: int, te: int,
                 interpret: bool) -> jax.Array:
    """Run ``body`` over padded ``(Bp, w)`` queries and an ``(Ep, w)``
    table: the count block stays resident while the inner grid axis
    sweeps the entity tiles.  Returns the ``(B,)`` int32 counts."""
    Bp, w = qp.shape
    gp = jnp.zeros((Bp, 1), jnp.float32).at[:B, 0].set(gold_d.astype(jnp.float32))

    grid = (Bp // tb, tp.shape[0] // te)

    cnt = pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, w), lambda i, j: (i, 0)),
            pl.BlockSpec((te, w), lambda i, j: (j, 0)),
            pl.BlockSpec((tb, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tb, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
        interpret=interpret,
        name=name,
    )(qp, tp, gp)
    return cnt[:B, 0].astype(jnp.int32)


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

    # L1 slices k LANES columns at a time: zero columns add exactly 0
    kp = -(-k // LANES) * LANES if norm == "l1" else k
    tb, te = _grid_tiles(B, E, norm, kp, 1, tb, te)
    Bp = -(-B // tb) * tb
    Ep = -(-E // te) * te

    qp = jnp.zeros((Bp, kp), queries.dtype).at[:B, :k].set(queries)
    # pad entities FAR away: distance to anything is huge -> never "closer"
    tp = (jnp.zeros((Ep, kp), table.dtype).at[:, :k].set(1e9)
          .at[:E, :k].set(table))
    return _padded_call(functools.partial(_kernel, norm=norm), "rank_counts",
                        qp, tp, gold_d, B, tb, te, interpret)


def modulus_rank_counts(
    queries: jax.Array,        # (B, 2k): [re | im]
    table: jax.Array,          # (E, 2k): [re | im]
    gold_d: jax.Array,         # (B,)
    *,
    tb: int | None = None,
    te: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """:func:`rank_counts` for the sum of complex moduli ``sum_j |q_j -
    e_j|`` over ``k`` complex columns stored ``[re | im]`` (RotatE's
    distance from a rotated query).  Each half is padded to ``kp``, a
    multiple of LANES, with zeros; pad rows of the table sit far away on
    the real axis and never count."""
    B, w = queries.shape
    k = w // 2
    E = table.shape[0]
    kp = -(-k // LANES) * LANES
    tb, te = _grid_tiles(B, E, "l1", 2 * kp, 2, tb, te)
    Bp = -(-B // tb) * tb
    Ep = -(-E // te) * te

    qp = (jnp.zeros((Bp, 2 * kp), queries.dtype)
          .at[:B, :k].set(queries[:, :k])
          .at[:B, kp:kp + k].set(queries[:, k:]))
    tp = (jnp.zeros((Ep, 2 * kp), table.dtype).at[:, :k].set(1e9)
          .at[:E, :k].set(table[:, :k])
          .at[:E, kp:kp + k].set(table[:, k:]))
    return _padded_call(_modulus_kernel, "modulus_rank_counts", qp, tp,
                        gold_d, B, tb, te, interpret)


def scan_cells(rows: int, n_entities: int, width: int, *, norm: str = "l1",
               modulus: bool = False) -> tuple[int, int]:
    """(scanned, useful) (query, entity, column) cells of one call over
    ``rows`` queries of ``width`` columns (``width`` complex columns, two
    floats each, on the modulus path): padded rows, entities and columns
    included, and the problem's own."""
    halves = 2 if modulus else 1
    kp = -(-width // LANES) * LANES if norm == "l1" else width
    tb, te = _tiles(rows, n_entities, norm, halves * kp, halves)
    scanned = (-(-rows // tb) * tb) * (-(-n_entities // te) * te) * halves * kp
    return scanned, rows * n_entities * halves * width
