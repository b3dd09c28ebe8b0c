"""BC-fused direct streaming Pallas kernels — no padded-array materialization.

The v1 hot path (``parallel.halo.exchange_halo`` + ``apply_taps_pallas_stream``)
pays for a full ghost-padded copy of the field every step: XLA's
``concatenate`` materializes the (nx+2, ny+2, nz+2) buffer (read + write of
the whole volume) before the stencil kernel reads it again — roughly
doubling HBM traffic, the roofline resource (SURVEY.md §6). The padded
buffer's (ny+2, nz+2) planes are also sublane/lane-misaligned (514 rows/
lanes pad to 520x640 VMEM tiles).

These kernels instead read the UNPADDED field — whose (by, nz) plane-chunks
are perfectly (8, 128)-tiled — and synthesize the boundary ghosts
in-register: Dirichlet ghosts are constant fills, periodic ghosts are
wrapped rows/planes fetched via modular BlockSpec index maps. HBM traffic
drops to the streaming minimum (one read + one write per cell per update;
the fused two-step variant halves that again), which is the whole game for
a 7/27-point stencil at ~8 B/cell.

Scope: the in-kernel ghost synthesis is exact where a boundary is a DOMAIN
boundary — the whole shard on a (1, 1, 1) mesh (the judged single-chip
benchmark config) and every axis-size-1 shard_map axis. On multi-chip
meshes these kernels still sweep the bulk (parallel.step's faces-direct
step): the outermost shell of each sharded axis, where the local synthesis
is wrong, is recomputed from the exchanged ghost faces and patched in.

Layout: the local (nx, ny, nz) volume is walked as a 2D Pallas grid
(J, nx + 2k) — y-chunk-column outer (J = ny/by picked to fit VMEM), x-plane
inner — so arbitrarily large fields stream through a 3-slot VMEM plane ring
exactly once per update.

Ring planes come in two layouts, chosen from the shape (_aligned_layout):

- Aligned, whenever nz is a multiple of 128 on the tap-chain route (every
  benchmarked deployment): a plane is exactly nz lanes wide, with no ghost
  lanes, and the (by, nz) chunk sits at a tile-aligned row between one
  storage tile of pad rows above and one below (8 rows for 4-byte storage,
  16 for 2-byte), its ghost rows stored narrowly into the pad next to it.
  Every store and every unshifted read is whole tiles. A tap term is a
  rotation of an aligned plane: dj = ±1 a sublane roll, dk = ±1 a lane
  roll, whose wrap is the periodic boundary and which a one-lane select
  turns into the Dirichlet one (_aligned_plane_taps). The tb=2 stage
  between the two updates computes whole aligned planes too.
- Ghost-framed, for any other nz and for the mehrstellen q-ring route:
  each plane is (by + 2h, nz + 2h) with its ghost ring stored around the
  chunk at offset (h, h), and every tap term is a slice at a lane and
  sublane offset of 0..2h, which Mosaic relayouts.

Both layouts sum the same values in the canonical order, so their results
are bitwise equal.

Note on grid-step count (so it isn't re-derived): per-step fixed overhead
cannot be amortized by fusing bi > 1 x-planes per block. Every scheme holds
~10 block-sized buffers (rings + in/out pipelines), so steps =
(ny/by)(nx/bi) ≈ cells x 10 x itemsize / VMEM_budget independent of the
bi/by split — ~4k steps at 1024^3 fp32 is structural; only raising the
VMEM budget (capped by Mosaic headroom) lowers it. Reference parity (SURVEY.md §2 C1): this is the
CUDA Jacobi kernel's job done the TPU way — the grid pipeline is the
``__global__`` launch, the plane ring is the shared-memory tile, and the
ghost synthesis replaces the separate boundary kernels.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from heat3d_tpu.core.stencils import (
    accumulate_taps,
    decompose_mehrstellen,
    effective_num_taps,
    flat_taps,
    mehrstellen_enabled,
    nonzero_taps,
)

_LANE = 128
_SUBLANE = 8

# Explicit ring/pipeline buffer budget, empirically tuned to leave Mosaic
# headroom for spills and the semaphore pool.
_VMEM_BUDGET = 10 * 1024 * 1024

# The tap-chain scoped-stack budget and estimator are shared with the
# exchange-path kernels (single source: stencil_pallas, where the
# calibration measurement is documented). The chunk chooser bounds the
# chain separately from the explicit ring/pipeline buffers.
from heat3d_tpu.ops.stencil_pallas import (  # noqa: E402
    _TAP_STACK_BUDGET,
    _tap_stack_bytes as _tap_stack_bytes_2d,
)


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _plane_bytes(rows: int, lanes: int, itemsize: int) -> int:
    return _round_up(rows, _SUBLANE) * _round_up(lanes, _LANE) * itemsize


def _pad_rows(itemsize: int) -> int:
    """Rows of one storage tile: the aligned layout's pad above and below
    the chunk (8 for 4-byte storage, 16 for 2-byte)."""
    return _SUBLANE * max(1, 4 // itemsize)


def _aligned_rows(by: int, itemsize: int) -> int:
    """Rows of an aligned ring plane: the chunk, rounded up to whole
    storage tiles, between one pad tile above and one below."""
    p = _pad_rows(itemsize)
    return p + _round_up(by, p) + p


def _tap_stack_bytes(
    by: int, nz: int, halo: int, n_taps: int, compute_itemsize: int = 4,
    aligned_itemsize: int = 0,
) -> int:
    """Scoped-stack estimate of one tap chain: the fused (halo=2) kernel's
    widest chain is the intermediate plane, one ghost ring larger. Under
    the aligned layout (``aligned_itemsize`` = storage itemsize) the chain
    runs on, or rotates, whole aligned planes."""
    if aligned_itemsize:
        return _tap_stack_bytes_2d(
            _aligned_rows(by, aligned_itemsize), nz, n_taps, compute_itemsize
        )
    r = halo - 1
    return _tap_stack_bytes_2d(
        by + 2 * r, nz + 2 * r, n_taps, compute_itemsize
    )


def _vmem_bytes(
    by: int,
    nz: int,
    halo: int,
    in_itemsize: int,
    out_itemsize: int,
    q_itemsize: int = 0,
    aligned: bool = False,
    partial_itemsize: int = 0,
) -> int:
    """VMEM footprint of the direct kernel at chunk height ``by`` and ghost
    width ``halo`` (1 = single step, 2 = fused two-step): the assembled-plane
    ring(s), the double-buffered input chunk + ghost-row pipeline, and the
    double-buffered output pipeline. ``q_itemsize`` > 0 adds the mehrstellen
    per-plane 2D-conv cache ring (3 planes, compute dtype). ``aligned``
    sizes the rings as aligned planes (_aligned_layout): nz lanes, the
    chunk between two pad tiles, in both rings. ``partial_itemsize`` > 0
    adds the double-buffered block of change-residual partial sums that
    apply_taps_direct emits with ``residual_dtype``."""
    if aligned:
        ring = 3 * halo * _plane_bytes(
            _aligned_rows(by, in_itemsize), nz, in_itemsize
        )
    else:
        ring = 3 * _plane_bytes(by + 2 * halo, nz + 2 * halo, in_itemsize)
    if halo == 2 and not aligned:
        # fused two-step: second ring for the intermediate planes
        ring += 3 * _plane_bytes(by + 2, nz + 2, in_itemsize)
    if q_itemsize:
        # one q ring per update stage: (by, nz) for the final stage, plus
        # the (by+2, nz+2) first-stage ring under temporal blocking
        ring += 3 * _plane_bytes(by, nz, q_itemsize)
        if halo == 2:
            ring += 3 * _plane_bytes(by + 2, nz + 2, q_itemsize)
    pipe_in = 2 * (
        _plane_bytes(by, nz, in_itemsize)
        + 2 * halo * _plane_bytes(1, nz, in_itemsize)
    )
    pipe_out = 2 * _plane_bytes(by, nz, out_itemsize)
    if partial_itemsize:
        pipe_out += 2 * _plane_bytes(
            _SUBLANE, _partial_lanes(nz), partial_itemsize
        )
    return ring + pipe_in + pipe_out


def _partial_lanes(nz: int) -> int:
    """Lanes of a change-residual partial block: one lane tile where nz is
    a whole number of them (the lanes fold by adding lane-tile slices),
    else all nz lanes (a full-extent block)."""
    return _LANE if nz % _LANE == 0 else nz


# Scoped-stack planes of the mehrstellen emit/store (vs the tap chain's
# effective_num_taps): store-time z131 + q (2), emit-time s + the psum
# accumulation (<=3 live) + u0 + the result accumulator (~6 peak). Used
# for the chunk chooser's stack budgeting whenever the q-ring route runs.
_MEHRSTELLEN_STACK_PLANES = 8


def choose_chunk(
    local_shape: Tuple[int, int, int],
    halo: int = 1,
    in_itemsize: int = 4,
    out_itemsize: int = 4,
    n_taps: int = 7,
    compute_itemsize: int = 4,
    q_ring: bool = False,
    aligned: bool = False,
    partial_itemsize: int = 0,
) -> Optional[int]:
    """Largest y-chunk height ``by`` (a divisor of ny, multiple of 8 when
    ny >= 8) whose working set fits the VMEM budget — both the explicit
    ring/pipeline buffers (including the mehrstellen q-ring when
    ``q_ring``) and the emit chain's scoped stack — or None. ``q_ring``
    overrides ``n_taps`` with the mehrstellen stack size here, in ONE
    place, so the dispatch gate and the kernel builder can't drift.
    ``aligned`` budgets the aligned ring layout (the direct kernels pass
    _aligned_layout's verdict); ``partial_itemsize`` the tb=1 kernel's
    change-residual partials (_vmem_bytes)."""
    if q_ring:
        n_taps = _MEHRSTELLEN_STACK_PLANES
    ny, nz = local_shape[1], local_shape[2]
    for by in range(ny, 0, -1):
        if ny % by:
            continue
        if by % 8 and by != ny:
            # multi-chunk ghost-row loads need 8-row-aligned blocks
            # (_row_block_specs); only the full-extent single chunk may be
            # unaligned
            continue
        ring = _vmem_bytes(
            by, nz, halo, in_itemsize, out_itemsize,
            q_itemsize=compute_itemsize if q_ring else 0,
            aligned=aligned,
            partial_itemsize=partial_itemsize,
        )
        stack = _tap_stack_bytes(
            by, nz, halo, n_taps, compute_itemsize,
            aligned_itemsize=in_itemsize if aligned else 0,
        )
        if ring > _VMEM_BUDGET or stack > _TAP_STACK_BUDGET:
            continue
        return by
    return None


def _mehrstellen_q_ring(taps) -> bool:
    """Whether apply_taps_direct will take the q-ring mehrstellen route
    for these taps under the current env — the ONE predicate the dispatch
    gate (direct_supported) and the kernel builder must share, so the
    gate can never approve a shape the builder then rejects."""
    return (
        taps is not None
        and mehrstellen_enabled()
        and decompose_mehrstellen(taps) is not None
    )


def _aligned_layout(nz: int, q_ring: bool) -> bool:
    """Whether the direct kernels lay their ring planes out on whole
    tiles — nz lanes with no ghost lanes, the chunk at a tile-aligned row
    — and build tap terms by rotation (_aligned_plane_taps): on the chain
    route whenever nz is a whole number of lane tiles. The ONE layout
    predicate the gate (direct_supported), both builders and the solver's
    route record share."""
    return nz % _LANE == 0 and not q_ring


def direct_layout(local_shape: Tuple[int, int, int], taps=None) -> str:
    """'aligned' or 'framed': the ring layout the direct kernels build for
    this local shape (``taps`` as for direct_supported)."""
    aligned = _aligned_layout(local_shape[2], _mehrstellen_q_ring(taps))
    return "aligned" if aligned else "framed"


def direct_supported(
    local_shape: Tuple[int, int, int],
    halo: int = 1,
    in_itemsize: int = 4,
    out_itemsize: int = 4,
    n_taps: int = 7,
    compute_itemsize: int = 4,
    taps=None,
    partial_itemsize: int = 0,
) -> bool:
    """Pass ``taps`` so the gate budgets the same route (q-ring or chain)
    apply_taps_direct will actually build; without them the chain route
    is assumed (the mehrstellen knob is ignored). ``partial_itemsize``
    budgets the change-residual partials of a tb=1 residual step."""
    nx, ny, nz = local_shape
    if halo == 2 and (nx < 2 or ny < 2 or nz < 2):
        return False  # wrapped/clamped width-2 ghosts would alias interior
    q_ring = _mehrstellen_q_ring(taps)
    return (
        choose_chunk(
            local_shape, halo, in_itemsize, out_itemsize, n_taps,
            compute_itemsize, q_ring=q_ring,
            aligned=_aligned_layout(nz, q_ring),
            partial_itemsize=partial_itemsize,
        )
        is not None
    )


def _store_framed_plane(ring, k, chunk, top, bot, bc, periodic, h):
    """Write the ghost-framed plane (by+2h, nz+2h) for ring slot ``k``
    directly into the scratch via slice stores — one bulk chunk store plus
    narrow row/lane edge stores — instead of materializing it with two
    full-plane concatenates and then copying it into the ring (the VMEM
    passes that made the streaming kernels compute-bound, BASELINE.md
    traffic model). The lane ghosts are read back from the ring after the row
    stores (Pallas refs have sequential semantics), so periodic corners
    wrap exactly as the concatenate construction did."""
    by, nz = chunk.shape
    ring[k, h : h + by, h : h + nz] = chunk
    ring[k, 0:h, h : h + nz] = top
    ring[k, h + by :, h : h + nz] = bot
    if periodic:
        ring[k, :, 0:h] = ring[k, :, nz : nz + h]
        ring[k, :, h + nz :] = ring[k, :, h : 2 * h]
    else:
        edge = jnp.full((by + 2 * h, h), bc, chunk.dtype)
        ring[k, :, 0:h] = edge
        ring[k, :, h + nz :] = edge


def _store_input_plane(ring, k, chunk, top, bot, bc, periodic, h, ghost_x):
    """Ring-slot store for one input plane: the framed plane, or (Dirichlet
    only) a pure-bc plane on the conceptual domain ghost planes — gated with
    pl.when rather than a per-step full-plane select. ``ghost_x`` is the
    scalar predicate marking those planes (ignored when periodic: wrapped
    planes are genuine data)."""
    if periodic:
        _store_framed_plane(ring, k, chunk, top, bot, bc, True, h)
        return

    @pl.when(ghost_x)
    def _bc_plane():
        ring[k] = jnp.full(
            (chunk.shape[0] + 2 * h, chunk.shape[1] + 2 * h), bc, chunk.dtype
        )

    @pl.when(jnp.logical_not(ghost_x))
    def _real_plane():
        _store_framed_plane(ring, k, chunk, top, bot, bc, False, h)


def _store_aligned_plane(ring, k, chunk, top, bot, bc, periodic, h, ghost_x):
    """The aligned layout's _store_input_plane: the chunk goes in at the
    tile-aligned row ``pad`` by one whole-tile store (no lane or sublane
    shift), its h ghost rows by narrow stores into the pad tiles beside
    it. There are no ghost lanes: _aligned_plane_taps supplies the z edge.
    Dirichlet domain ghost planes are pure bc, as in the framed layout."""
    by = chunk.shape[0]
    pad = _pad_rows(chunk.dtype.itemsize)

    def real_plane():
        ring[k, pad : pad + by] = chunk
        ring[k, pad - h : pad] = top
        ring[k, pad + by : pad + by + h] = bot

    if periodic:
        real_plane()
        return

    @pl.when(ghost_x)
    def _bc_plane():
        ring[k] = jnp.full(ring.shape[1:], bc, chunk.dtype)

    pl.when(jnp.logical_not(ghost_x))(real_plane)


def _edge_lane_select(x, lane, fill):
    """``x`` with lane ``lane`` set to ``fill``. The select runs on the one
    lane tile that holds the lane; the other tiles pass through."""
    t0 = lane // _LANE * _LANE
    tile = x[:, t0 : t0 + _LANE]
    ids = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    tile = jnp.where(ids == lane - t0, fill, tile)
    parts = [x[:, :t0], tile, x[:, t0 + _LANE :]]
    return jnp.concatenate([p for p in parts if p.shape[1]], axis=1)


def _roll(x, shift, axis):
    """``pltpu.roll`` of ``x``. Mosaic rotates 32-bit data only, so a
    narrower dtype (a bf16 compute dtype) is rotated as float32, which
    holds each of its values exactly, and cast back."""
    if x.dtype.itemsize < 4:
        return pltpu.roll(x.astype(jnp.float32), shift, axis).astype(x.dtype)
    return pltpu.roll(x, shift, axis)


def _aligned_plane_taps(planes, taps_flat, lo, n, edge, compute_dtype):
    """The tap chain over three aligned (R, nz) planes: the (n, nz) update
    of rows [lo, lo + n) of the middle plane, in the canonical
    core.stencils.accumulate_taps order with _plane_taps' xsum/ysum
    caching, so the values summed, and their order, are the framed
    route's and the results are bitwise equal.

    A shifted term is a rotation of an aligned plane, never a misaligned
    slice: dj by a sublane roll of the whole plane (rows wrap only into pad
    rows no valid row reads), dk by a lane roll. Across the z edge the lane
    roll is the periodic wrap (``edge`` None: z is always one whole block);
    under Dirichlet (``edge`` = the ghost value in the compute dtype) a
    select writes on the edge lane what the framed plane's ghost lane holds
    there: bc, or the sum of bc's that a cached xsum/ysum adds up."""
    rows_total, nz = planes[0].shape
    cache = {}

    def rows(src, dj):
        if dj:
            src = _roll(src, (-dj) % rows_total, 0)
        return src[lo : lo + n]

    def lanes(x, dk, fill):
        if dk == 0:
            return x
        x = _roll(x, (-dk) % nz, 1)
        if fill is None:
            return x
        return _edge_lane_select(x, 0 if dk < 0 else nz - 1, fill)

    def twice(v):
        return None if v is None else v + v

    def term(di, dj, dk):
        if di == "xsum":
            if "p" not in cache:
                cache["p"] = planes[-1] + planes[1]
            src, fill = cache["p"], twice(edge)
        else:
            src, fill = planes[di], edge
        if dj == "ysum":
            key = ("ys", di)
            if key not in cache:
                cache[key] = rows(src, -1) + rows(src, 1)
            return lanes(cache[key], dk, twice(fill))
        return lanes(rows(src, dj), dk, fill)

    return accumulate_taps(taps_flat, term, compute_dtype)


# Tap accumulation shared with the exchange-path kernels: op order must stay
# identical across kernels so fused == unfused results match to the ulp.
from heat3d_tpu.ops.stencil_pallas import _plane_taps  # noqa: E402


def _row_block_specs(x_of, by, ny, nz, periodic):
    """BlockSpecs for the ghost-row loads of a multi-chunk kernel: 8-row
    blocks (sublane-aligned, see _chunk_ghost_rows) addressed in units of
    ny/8. Valid only when by % 8 == 0 (choose_chunk guarantees it whenever
    ny >= 8, and ny < 8 forces the single-chunk mode that skips these)."""
    nyb = ny // 8
    if periodic:
        tb_of = lambda j: jax.lax.rem(by * j // 8 - 1 + nyb, nyb)
        bb_of = lambda j: jax.lax.rem((by * j + by) // 8, nyb)
    else:
        # domain-edge chunk columns load an in-range dummy block; the
        # kernel substitutes the boundary value there
        tb_of = lambda j: jnp.maximum(by * j // 8 - 1, 0)
        bb_of = lambda j: jnp.minimum((by * j + by) // 8, nyb - 1)

    def make(idx_of):
        return pl.BlockSpec(
            (1, 8, nz), lambda j, i, f=idx_of: (x_of(i), f(j), 0)
        )

    return [make(tb_of), make(bb_of)]


def _chunk_ghost_rows(chunk, top_ref, bot_ref, h, periodic, bc):
    """Extract the (h, nz) ghost-row values above/below the current chunk.

    Multi-chunk mode loads 8-row-aligned blocks (TPU lowering requires
    sublane block dims divisible by 8 or full-extent): since by % 8 == 0,
    the top ghost rows are always the LAST h rows of the block above and
    the bottom ghost rows the FIRST h of the block below — static in-block
    offsets. Single-chunk mode (no row refs) derives them from the chunk
    itself: periodic wrap rows, or the boundary value."""
    if top_ref is None:  # single chunk column
        if periodic:
            return chunk[-h:], chunk[:h]
        fill = jnp.full((h, chunk.shape[1]), bc, chunk.dtype)
        return fill, fill
    return top_ref[0, 8 - h :], bot_ref[0, :h]


def _direct_kernel(
    u_ref,
    top_ref,
    bot_ref,
    out_ref,
    ring,
    ring_q=None,
    *,
    taps_flat=None,
    coeffs=None,
    nx,
    by,
    nz,
    n_chunks,
    periodic,
    bc_value,
    compute_dtype,
    out_dtype,
    aligned=False,
    partial_ref=None,
):
    """Grid step (j, i): assemble plane p = i-1 of chunk column j into a
    3-slot ring; once 3 planes are resident emit output plane i-2.
    Conceptual plane p runs -1 .. nx (the two x ghost planes); the index maps
    wrap (periodic) or clamp (Dirichlet, substituted with bc here).

    Two emit routes over one scaffold (the ring-slot arithmetic and ghost
    synthesis are load-bearing invariants kept in exactly one place):
    ``taps_flat`` = the canonical tap chain, on ghost-framed planes or, when
    ``aligned``, on aligned planes (_aligned_layout); ``coeffs`` + ``ring_q``
    = the mehrstellen S+F route on framed planes, where each stored plane
    also caches its 2D conv in ``ring_q`` (computed ONCE per input plane
    instead of once per output plane that reads it — the shifted-read reuse
    the route exists for).

    With ``partial_ref`` (apply_taps_direct's ``residual_dtype``) each emit
    also writes the partial sums of (new - old)^2 over the output chunk:
    the stored new values against the centre plane's data cells, both in
    the partials' dtype (_change_partials)."""
    j = pl.program_id(0)
    i = pl.program_id(1)
    bc = u_ref.dtype.type(bc_value)

    chunk = u_ref[0]  # (by, nz) aligned
    top, bot = _chunk_ghost_rows(chunk, top_ref, bot_ref, 1, periodic, bc)
    if not periodic:
        # domain-edge chunk columns: the clamped row loads fetched dummy
        # rows; substitute the Dirichlet boundary value (narrow blocks only)
        top = jnp.where(j == 0, jnp.full_like(top, bc), top)
        bot = jnp.where(j == n_chunks - 1, jnp.full_like(bot, bc), bot)

    for k in range(3):

        @pl.when(jax.lax.rem(i, 3) == k)
        def _store(k=k):
            # Conceptual planes -1 and nx are domain ghost planes: the
            # clamped load fetched plane 0 / nx-1; store a pure-bc plane.
            store = _store_aligned_plane if aligned else _store_input_plane
            store(
                ring, k, chunk, top, bot, bc, periodic, 1,
                ghost_x=jnp.logical_or(i == 0, i == nx + 1),
            )
            if coeffs is not None:
                # AFTER the framed store (sequential ref semantics: reads
                # back the exact stored frame)
                ring_q[k] = _plane_q(ring[k], by, nz, compute_dtype)

    for k in range(3):

        @pl.when(jnp.logical_and(i >= 2, jax.lax.rem(i, 3) == k))
        def _emit(k=k):
            # planes (i-2, i-1, i) live in slots ((k+1)%3, (k+2)%3, k)
            slots = {-1: (k + 1) % 3, 0: (k + 2) % 3, 1: k}
            planes = {
                d: ring[s].astype(compute_dtype) for d, s in slots.items()
            }
            if coeffs is not None:
                q_planes = {d: ring_q[s] for d, s in slots.items()}
                res = _plane_mehrstellen(
                    planes, q_planes, coeffs, by, nz, compute_dtype
                )
            elif aligned:
                res = _aligned_plane_taps(
                    planes, taps_flat, _pad_rows(ring.dtype.itemsize), by,
                    None if periodic else compute_dtype(bc), compute_dtype,
                )
            else:
                res = _plane_taps(planes, taps_flat, by, nz, compute_dtype)
            stored = res.astype(out_dtype)
            out_ref[0] = stored
            if partial_ref is not None:
                if aligned:
                    pad = _pad_rows(ring.dtype.itemsize)
                    old = ring[slots[0], pad : pad + by]
                else:
                    old = ring[slots[0]][1 : 1 + by, 1 : 1 + nz]
                partial_ref[0] = _change_partials(
                    stored, old, partial_ref.shape[1:], partial_ref.dtype
                )


def _direct_kernel_single(u_ref, out_ref, ring, ring_q=None, **params):
    """Single-chunk-column variant: no ghost-row refs (derived in-kernel)."""
    _direct_kernel(u_ref, None, None, out_ref, ring, ring_q, **params)


def _tree_sum(parts):
    """Sum of equal-shaped arrays by pairwise adds."""
    while len(parts) > 1:
        parts = [a + b for a, b in zip(parts[::2], parts[1::2])] + (
            parts[-1:] if len(parts) % 2 else []
        )
    return parts[0]


def _change_partials(new, old, shape, dtype):
    """Partial sums of ``(new - old)^2`` over one (by, nz) output chunk,
    in ``dtype`` and folded to ``shape`` = (rows, lanes): lanes by adding
    lane-tile slices, rows by adding row-tile slices (rows = 1: one
    sublane sum), each by pairwise adds. residual_sumsq's values, summed
    in another order."""
    rows, lanes = shape
    d = new.astype(dtype) - old.astype(dtype)
    sq = d * d
    by, nz = sq.shape
    sq = _tree_sum([sq[:, c : c + lanes] for c in range(0, nz, lanes)])
    if rows == 1:
        return jnp.sum(sq, axis=0, keepdims=True)
    return _tree_sum([sq[r : r + rows] for r in range(0, by, rows)])


def _plane_q(framed, by, nz, compute_dtype):
    """Per-plane mehrstellen cache: the 2D [1,3,1](x)[1,3,1] convolution of
    one ghost-framed (by+2, nz+2) plane, valid interior (by, nz). Op order
    is the z-then-y prefix of the canonical mehrstellen order
    (ops.stencil_jnp._apply_mehrstellen_padded)."""
    f = framed.astype(compute_dtype)
    three = compute_dtype(3.0)
    z131 = (f[:, 0:nz] + f[:, 2 : nz + 2]) + three * f[:, 1 : nz + 1]
    return (z131[0:by] + z131[2 : by + 2]) + three * z131[1 : by + 1]


def _plane_mehrstellen(planes, q_planes, coeffs, by, nz, compute_dtype):
    """Emit one output plane from the 3 framed x-planes and their cached
    q planes: S via the x-direction [1,3,1] over the q ring, the face sum
    from the framed planes, one 3-term combine — the canonical mehrstellen
    order's x/psum/combine suffix."""
    a, b, d = (compute_dtype(c) for c in coeffs)
    three = compute_dtype(3.0)
    s = (q_planes[-1] + q_planes[1]) + three * q_planes[0]
    f0 = planes[0]
    u0 = f0[1 : 1 + by, 1 : 1 + nz]
    px = (
        planes[-1][1 : 1 + by, 1 : 1 + nz]
        + planes[1][1 : 1 + by, 1 : 1 + nz]
    )
    py = f0[0:by, 1 : 1 + nz] + f0[2 : by + 2, 1 : 1 + nz]
    pz = f0[1 : 1 + by, 0:nz] + f0[1 : 1 + by, 2 : nz + 2]
    psum = (px + py) + pz
    return (a * u0 + b * s) + d * psum


def apply_taps_direct(
    u: jax.Array,
    taps: np.ndarray,
    periodic: bool = False,
    bc_value: float = 0.0,
    compute_dtype=jnp.float32,
    out_dtype=None,
    interpret: bool = False,
    residual_dtype=None,
):
    """One stencil update of the full (1,1,1)-mesh shard: unpadded
    (nx, ny, nz) in, (nx, ny, nz) out, boundary conditions synthesized
    in-kernel. Equivalent to ``exchange_halo`` + ``apply_taps_padded`` at
    half the HBM traffic.

    With ``residual_dtype`` it returns ``(out, partials)``: partial sums
    of ``(out - u)^2`` in that dtype, one (rows, lanes) block per output
    plane and chunk column, whose sum is ``residual_sumsq(out, u,
    residual_dtype)`` up to summation order. rows is 8 (1 for a single
    chunk of another height), lanes 128 where nz is a whole number of lane
    tiles, else nz. Only data cells count."""
    nx, ny, nz = u.shape
    out_dtype = out_dtype or u.dtype
    compute_dtype = jnp.dtype(compute_dtype).type
    flat = flat_taps(taps)
    q_ring = _mehrstellen_q_ring(taps)
    coeffs = decompose_mehrstellen(taps) if q_ring else None
    aligned = _aligned_layout(nz, q_ring)
    if residual_dtype is not None:
        residual_dtype = jnp.dtype(residual_dtype)
    by = choose_chunk(
        u.shape, 1, u.dtype.itemsize, jnp.dtype(out_dtype).itemsize,
        n_taps=effective_num_taps(taps),
        compute_itemsize=jnp.dtype(compute_dtype).itemsize,
        q_ring=q_ring,
        aligned=aligned,
        partial_itemsize=residual_dtype.itemsize if residual_dtype else 0,
    )
    if by is None:
        raise ValueError(f"no VMEM-feasible chunking for {u.shape}")
    n_chunks = ny // by

    if periodic:
        x_of = lambda i: jax.lax.rem(i - 1 + nx, nx)
    else:
        x_of = lambda i: jnp.clip(i - 1, 0, nx - 1)

    single = n_chunks == 1
    if aligned:
        plane = (_aligned_rows(by, u.dtype.itemsize), nz)
    else:
        plane = (by + 2, nz + 2)
    scratch_shapes = [pltpu.VMEM((3, *plane), u.dtype)]
    shared = dict(
        nx=nx,
        by=by,
        nz=nz,
        n_chunks=n_chunks,
        periodic=periodic,
        bc_value=bc_value,
        compute_dtype=compute_dtype,
        out_dtype=jnp.dtype(out_dtype),
        aligned=aligned,
    )
    base = _direct_kernel if not single else _direct_kernel_single
    if coeffs is not None:
        kernel = functools.partial(base, coeffs=coeffs, **shared)
        scratch_shapes.append(
            pltpu.VMEM((3, by, nz), jnp.dtype(compute_dtype))
        )
    else:
        kernel = functools.partial(base, taps_flat=flat, **shared)
    in_specs = [pl.BlockSpec((1, by, nz), lambda j, i: (x_of(i), j, 0))]
    operands = (u,)
    if not single:
        in_specs += _row_block_specs(x_of, by, ny, nz, periodic)
        operands = (u, u, u)
    # the mehrstellen route does ~MEHRSTELLEN_OPS vector ops/cell, not the
    # chain's len(flat) — the estimate feeds XLA's overlap scheduling
    from heat3d_tpu.core.stencils import MEHRSTELLEN_OPS

    flops_per_cell = 2 * (MEHRSTELLEN_OPS if coeffs is not None else len(flat))
    out_index = lambda j, i: (jnp.maximum(i - 2, 0), j, 0)  # noqa: E731
    out_specs = pl.BlockSpec((1, by, nz), out_index)
    out_shape = jax.ShapeDtypeStruct((nx, ny, nz), out_dtype)
    bytes_accessed = nx * ny * nz * (
        u.dtype.itemsize + jnp.dtype(out_dtype).itemsize
    )
    if residual_dtype is not None:
        rows = _SUBLANE if by % _SUBLANE == 0 else 1
        lanes = _partial_lanes(nz)
        partials = jax.ShapeDtypeStruct(
            (nx, rows * n_chunks, lanes), residual_dtype
        )
        out_specs = [out_specs, pl.BlockSpec((1, rows, lanes), out_index)]
        out_shape = [out_shape, partials]
        # the subtract, multiply and add of each cell's change
        flops_per_cell += 3
        bytes_accessed += partials.size * residual_dtype.itemsize
        kernel = _with_partials(kernel, len(operands))
    return pl.pallas_call(
        kernel,
        name="heat3d.stencil.direct",
        grid=(n_chunks, nx + 2),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        cost_estimate=pl.CostEstimate(
            flops=flops_per_cell * nx * ny * nz,
            bytes_accessed=bytes_accessed,
            transcendentals=0,
        ),
        interpret=interpret,
    )(*operands)


def _with_partials(kernel, n_in):
    """The direct kernel with its partials' output: Pallas passes the
    inputs, both outputs, then the scratch refs; ``kernel`` takes the
    partials' ref by keyword."""

    def with_partials(*refs):
        out_ref, partial_ref = refs[n_in : n_in + 2]
        kernel(*refs[:n_in], out_ref, *refs[n_in + 2 :],
               partial_ref=partial_ref)

    return with_partials


def _direct2_kernel(
    u_ref,
    top_ref,
    bot_ref,
    out_ref,
    ring_a,
    ring_b,
    ring_qa=None,
    ring_qb=None,
    *,
    taps_flat=None,
    coeffs=None,
    nx,
    by,
    nz,
    n_chunks,
    periodic,
    bc_value,
    compute_dtype,
    storage_dtype,
    out_dtype,
    aligned=False,
):
    """Fused two-update direct kernel (temporal blocking k=2 in one HBM
    sweep). Grid step (j, i): (a) assemble input plane q = i (conceptual
    global plane i-2, width-2 ghosts) into ring_a; (b) at i>=2 compute
    intermediate plane m = i-2 (global i-4, one ghost ring wide) into
    ring_b, pinning Dirichlet domain ghosts exactly as the unfused sequence
    sees them; (c) at i>=4 emit output plane o = i-4 (global). Same plane
    indexing as ops.stencil_pallas._stream2_kernel; only the input source
    (assembled vs pre-padded) differs. Chunk columns recompute their two
    boundary intermediate rows — ~2/by duplicated VPU work, no extra HBM.

    Routes as in _direct_kernel: ``taps_flat`` = tap chain, framed or
    ``aligned``; ``coeffs`` + ``ring_qa``/``ring_qb`` = mehrstellen, with a
    per-stage q cache (each stored input/intermediate plane's 2D conv
    computed once; stage (b)'s cache is built AFTER the ghost pinning so it
    convolves exactly the plane the unfused sequence would read).

    Aligned, stage (b) computes the whole aligned plane — rows pad-1 ..
    pad+by are the intermediate's, the rest pad rows nothing reads — and
    ring_b has no ghost lanes: stage (c)'s edge select supplies bc there."""
    j = pl.program_id(0)
    i = pl.program_id(1)
    bc_s = u_ref.dtype.type(bc_value)
    pad = _pad_rows(storage_dtype.itemsize)
    edge = None if periodic else compute_dtype(bc_s)

    chunk = u_ref[0]  # (by, nz)
    top, bot = _chunk_ghost_rows(chunk, top_ref, bot_ref, 2, periodic, bc_s)
    if not periodic:
        top = jnp.where(j == 0, jnp.full_like(top, bc_s), top)
        bot = jnp.where(j == n_chunks - 1, jnp.full_like(bot, bc_s), bot)

    for k in range(3):

        @pl.when(jax.lax.rem(i, 3) == k)
        def _load(k=k):
            store = _store_aligned_plane if aligned else _store_input_plane
            store(
                ring_a, k, chunk, top, bot, bc_s, periodic, 2,
                ghost_x=jnp.logical_or(i <= 1, i >= nx + 2),
            )
            if coeffs is not None:
                ring_qa[k] = _plane_q(ring_a[k], by + 2, nz + 2, compute_dtype)

    # (b) intermediate plane m = i-2 from input planes (i-2, i-1, i).
    for k in range(3):  # k == i % 3

        @pl.when(jnp.logical_and(i >= 2, jax.lax.rem(i, 3) == k))
        def _mid(k=k):
            slots = {-1: (k + 1) % 3, 0: (k + 2) % 3, 1: k}
            planes = {
                d: ring_a[s].astype(compute_dtype) for d, s in slots.items()
            }
            if coeffs is not None:
                q_planes = {d: ring_qa[s] for d, s in slots.items()}
                mid = _plane_mehrstellen(
                    planes, q_planes, coeffs, by + 2, nz + 2, compute_dtype
                )
            elif aligned:
                mid = _aligned_plane_taps(
                    planes, taps_flat, 0, ring_a.shape[1], edge,
                    compute_dtype,
                )
            else:
                mid = _plane_taps(
                    planes, taps_flat, by + 2, nz + 2, compute_dtype
                )
            slot = (k + 1) % 3  # slot (i-2)%3
            if periodic:
                # round-trip through storage dtype so fused == unfused bitwise
                ring_b[slot] = mid.astype(storage_dtype)
                if coeffs is not None:
                    ring_qb[slot] = _plane_q(
                        ring_b[slot], by, nz, compute_dtype
                    )
            else:
                m = i - 2  # 0 .. nx+1 in 1-ring coords; 0 / nx+1 = ghosts
                ghost_plane = jnp.logical_or(m == 0, m == nx + 1)

                @pl.when(ghost_plane)
                def _bc_mid():
                    ring_b[slot] = jnp.full(
                        ring_b.shape[1:], bc_s, storage_dtype
                    )

                @pl.when(jnp.logical_not(ghost_plane))
                def _real_mid():
                    # domain ghost ring of the intermediate, pinned by
                    # narrow stores after the bulk store; ghost ROWS exist
                    # only on the edge chunk columns (interior chunk
                    # borders hold genuinely-updated cells), ghost lane
                    # columns 0 / nz+1 always (framed only: the aligned
                    # plane has none)
                    ring_b[slot] = mid.astype(storage_dtype)
                    width = ring_b.shape[2]
                    if aligned:
                        top_row, bot_row = pad - 1, pad + by
                    else:
                        top_row, bot_row = 0, by + 1
                        edge_col = jnp.full((by + 2, 1), bc_s, storage_dtype)
                        ring_b[slot, :, 0:1] = edge_col
                        ring_b[slot, :, nz + 1 : nz + 2] = edge_col
                    edge_row = jnp.full((1, width), bc_s, storage_dtype)

                    @pl.when(j == 0)
                    def _top_row():
                        ring_b[slot, top_row : top_row + 1, :] = edge_row

                    @pl.when(j == n_chunks - 1)
                    def _bot_row():
                        ring_b[slot, bot_row : bot_row + 1, :] = edge_row

                if coeffs is not None:
                    # after BOTH branches' stores: convolve the exact
                    # (pinned or pure-bc) plane stage (c) will read
                    ring_qb[slot] = _plane_q(
                        ring_b[slot], by, nz, compute_dtype
                    )

    # (c) output plane o = i-4 from intermediate planes (i-4, i-3, i-2).
    for k in range(3):  # k == i % 3; (i-4)%3 == (k+2)%3, (i-3)%3 == k

        @pl.when(jnp.logical_and(i >= 4, jax.lax.rem(i, 3) == k))
        def _out(k=k):
            slots = {-1: (k + 2) % 3, 0: k, 1: (k + 1) % 3}
            planes = {
                d: ring_b[s].astype(compute_dtype) for d, s in slots.items()
            }
            if coeffs is not None:
                q_planes = {d: ring_qb[s] for d, s in slots.items()}
                res = _plane_mehrstellen(
                    planes, q_planes, coeffs, by, nz, compute_dtype
                )
            elif aligned:
                res = _aligned_plane_taps(
                    planes, taps_flat, pad, by, edge, compute_dtype
                )
            else:
                res = _plane_taps(planes, taps_flat, by, nz, compute_dtype)
            out_ref[0] = res.astype(out_dtype)


def _direct2_kernel_single(
    u_ref, out_ref, ring_a, ring_b, ring_qa=None, ring_qb=None, **params
):
    """Single-chunk-column variant: no ghost-row refs (derived in-kernel)."""
    _direct2_kernel(
        u_ref, None, None, out_ref, ring_a, ring_b, ring_qa, ring_qb,
        **params,
    )


def apply_taps_direct2(
    u: jax.Array,
    taps: np.ndarray,
    periodic: bool = False,
    bc_value: float = 0.0,
    compute_dtype=jnp.float32,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Two fused stencil updates of the full (1,1,1)-mesh shard in one HBM
    sweep: unpadded (nx, ny, nz) in, (nx, ny, nz) after TWO updates out.
    The single-chip analogue of the width-2-exchange + stream2 superstep,
    minus the padded-copy materialization."""
    nx, ny, nz = u.shape
    out_dtype = out_dtype or u.dtype
    compute_dtype = jnp.dtype(compute_dtype).type
    flat = flat_taps(taps)
    q_ring = _mehrstellen_q_ring(taps)
    coeffs = decompose_mehrstellen(taps) if q_ring else None
    aligned = _aligned_layout(nz, q_ring)
    by = choose_chunk(
        u.shape, 2, u.dtype.itemsize, jnp.dtype(out_dtype).itemsize,
        n_taps=effective_num_taps(taps),
        compute_itemsize=jnp.dtype(compute_dtype).itemsize,
        q_ring=q_ring,
        aligned=aligned,
    )
    if by is None:
        raise ValueError(f"no VMEM-feasible chunking for {u.shape}")
    n_chunks = ny // by

    if periodic:
        x_of = lambda i: jax.lax.rem(i - 2 + 2 * nx, nx)
    else:
        x_of = lambda i: jnp.clip(i - 2, 0, nx - 1)

    single = n_chunks == 1
    base = _direct2_kernel if not single else _direct2_kernel_single
    shared = dict(
        nx=nx,
        by=by,
        nz=nz,
        n_chunks=n_chunks,
        periodic=periodic,
        bc_value=bc_value,
        compute_dtype=compute_dtype,
        storage_dtype=u.dtype,
        out_dtype=jnp.dtype(out_dtype),
        aligned=aligned,
    )
    if aligned:  # both rings on the same aligned planes
        planes = [(_aligned_rows(by, u.dtype.itemsize), nz)] * 2
    else:
        planes = [(by + 4, nz + 4), (by + 2, nz + 2)]
    scratch_shapes = [pltpu.VMEM((3, *p), u.dtype) for p in planes]
    if coeffs is not None:
        kernel = functools.partial(base, coeffs=coeffs, **shared)
        scratch_shapes += [
            pltpu.VMEM((3, by + 2, nz + 2), jnp.dtype(compute_dtype)),
            pltpu.VMEM((3, by, nz), jnp.dtype(compute_dtype)),
        ]
    else:
        kernel = functools.partial(base, taps_flat=flat, **shared)
    in_specs = [pl.BlockSpec((1, by, nz), lambda j, i: (x_of(i), j, 0))]
    operands = (u,)
    if not single:
        in_specs += _row_block_specs(x_of, by, ny, nz, periodic)
        operands = (u, u, u)
    from heat3d_tpu.core.stencils import MEHRSTELLEN_OPS

    ops_per_update = 2 * (
        MEHRSTELLEN_OPS if coeffs is not None else len(flat)
    )
    # RAW flops (the streamk convention): the fused superstep's mid stage
    # sweeps the one-ring-padded volume (synthesized ghosts included), and
    # obs/perf/roofline's effective discount assumes the reported flops
    # count that recompute trapezoid
    raw_cells = (nx + 2) * (ny + 2) * (nz + 2) + nx * ny * nz
    return pl.pallas_call(
        kernel,
        name="heat3d.stencil.direct2",
        grid=(n_chunks, nx + 4),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, by, nz), lambda j, i: (jnp.maximum(i - 4, 0), j, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((nx, ny, nz), out_dtype),
        scratch_shapes=scratch_shapes,
        cost_estimate=pl.CostEstimate(
            flops=ops_per_update * raw_cells,
            bytes_accessed=nx * ny * nz
            * (u.dtype.itemsize + jnp.dtype(out_dtype).itemsize),
            transcendentals=0,
        ),
        interpret=interpret,
    )(*operands)
