"""Pallas TPU kernel: a sorted segment sum as one-hot matmuls on the MXU.

    out[b, s] = sum over j with ids[j] == s of xT[b, j] * values[j]

``ids`` is non-decreasing (the canonical order for the espmm forward, the
row-sorted dual order for dX; DESIGN.md §1b). On the TPU, XLA lowers a
sorted ``segment_sum`` to a scatter-add that updates one accumulator row
per slot, each after the one before; here every 128-slot sub-block is
reduced at once by a matmul with its one-hot (segment x slot) matrix.

Layout: slots run along lanes (``xT`` is (B, S), lane-dense at any batch)
and so do segments: the (B, n_seg_pad) f32 accumulator is the output
block, resident in VMEM across the whole slot grid. Sub-block ``k``'s ids
span ``trips[k]`` windows of 128 segments from window ``base[k]`` (scalar
prefetch, computed by XLA from the sorted ids). Each grid step adds every
sub-block's first window in straight-line code, then loops over the
further windows of the few sub-blocks that span more (empty segments, a
long run of short segments).

Exactness: each f32 contribution ``xT * values`` is split exactly into
three bfloat16 parts (8 + 8 + 8 significant bits), and a one-hot is exact
in bfloat16, so one default-precision dot of the stacked parts accumulates
every contribution unrounded in f32. No contribution is rounded to
bfloat16.

Padding slots carry id ``n_segments`` and value 0: they match no segment
of a window, or the accumulator column ``n_segments`` that the caller
slices off; a sub-block of padding alone adds its zeros to a clamped
window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # slots per sub-block, and segments per window
SUBBLOCKS = 8  # sub-blocks per grid step
STEP_SLOTS = SUBBLOCKS * LANES  # a kernel's slots are a multiple of this
# the resident accumulator tile (double-buffered by the pipeline); a batch
# tile is as many rows as fit, up to MAX_BATCH_TILE, and a width whose
# 8-row tile does not fit keeps XLA's segment_sum
ACC_VMEM_BYTES = 4 * 1024 * 1024
MAX_BATCH_TILE = 512
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def batch_tile(n_segments: int, batch: int) -> int:
    """Rows of the accumulator per grid row, a multiple of 8: all of the
    batch if they fit, else as many as do; 0 if not even 8 (rows past the
    batch are edge padding, dropped when written back)."""
    per_row = _round_up(n_segments, LANES) * 4
    rows = min(ACC_VMEM_BYTES // per_row // 8 * 8, MAX_BATCH_TILE)
    return min(rows, _round_up(batch, 8))


def fits(n_segments: int, batch: int, chunk: int, dtype) -> bool:
    """Whether the kernel takes a chunked reduction: f32, an accumulator
    tile that fits, and chunks of at least one grid step and of at least
    as many slots as the accumulator has (padded) segments. The kernel
    writes its whole accumulator per chunk, where XLA's scatter-add touches
    only the rows a chunk updates: with short chunks and wide layers
    (batch-512 evaluation of a 27,500-wide layer, 4,096-slot chunks) that
    write costs more than the serialized scatter."""
    return (
        jnp.dtype(dtype) == jnp.float32
        and chunk >= max(STEP_SLOTS, _round_up(n_segments, LANES))
        and batch_tile(n_segments, batch) > 0
    )


def _split3(c):
    """f32 (rows, LANES) -> its three bfloat16 parts stacked (3 * rows,
    LANES); the f32 sum of a row's three parts is exactly the row."""
    hi = c.astype(jnp.bfloat16).astype(jnp.float32)
    mid = (c - hi).astype(jnp.bfloat16).astype(jnp.float32)
    lo = c - hi - mid
    return jnp.concatenate([hi, mid, lo]).astype(jnp.bfloat16)


def _kernel(base_ref, trips_ref, ids_ref, x_ref, v_ref, o_ref):
    step = pl.program_id(1)
    rows = o_ref.shape[0]

    @pl.when(step == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    seg = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)

    def window(r, t):
        """Sub-block ``r``'s slots whose segment lies in window ``t``."""
        lanes = pl.ds(pl.multiple_of(r * LANES, LANES), LANES)
        parts = _split3(x_ref[:, lanes] * v_ref[:, lanes])
        onehot = (seg == ids_ref[pl.ds(r, 1), :] - t * LANES).astype(jnp.bfloat16)
        sums = jax.lax.dot_general(
            parts, onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        cols = pl.ds(pl.multiple_of(t * LANES, LANES), LANES)
        o_ref[:, cols] += (sums[:rows] + sums[rows:2 * rows]) + sums[2 * rows:]

    # every sub-block's first window, unrolled from one traced body (eight
    # traced copies lowered slowly enough to lengthen set-up); padding alone
    # adds zeros to its clamped window. Then sub-blocks spanning more windows.
    def first(r, carry):
        window(r, base_ref[step * SUBBLOCKS + r])
        return carry

    def extras(r, carry):
        k = step * SUBBLOCKS + r

        def extra(w, carry):
            window(r, base_ref[k] + w)
            return carry

        return jax.lax.fori_loop(1, trips_ref[k], extra, carry)

    jax.lax.fori_loop(0, SUBBLOCKS, first, 0, unroll=True)
    jax.lax.fori_loop(0, SUBBLOCKS, extras, 0)


@functools.partial(jax.jit, static_argnames=("n_segments", "interpret"))
def sorted_segment_sum_T(
    xT: jax.Array,
    values: jax.Array,
    ids: jax.Array,
    *,
    n_segments: int,
    interpret: bool = False,
) -> jax.Array:
    """Sorted segment sum of the slots' contributions ``xT * values``.

    ``xT`` is (B, S) f32 and ``values`` (S,): slot j contributes
    ``xT[:, j] * values[j]`` (rounded to f32, as XLA's product is) to
    segment ``ids[j]``. ``ids`` (S,) int32 is non-decreasing, with padding
    slots at the end carrying id ``n_segments`` and value 0; S is a
    multiple of ``STEP_SLOTS``. Returns the (B, round_up(n_segments,
    128)) accumulator: columns past ``n_segments`` hold padding and are the
    caller's to slice off.
    """
    B, S = xT.shape
    bb = batch_tile(n_segments, B)
    n_seg_pad = _round_up(n_segments, LANES)
    ids2 = ids.reshape(S // LANES, LANES)
    first = ids2[:, 0]
    last = jnp.minimum(ids2[:, -1], n_segments - 1)
    base = jnp.minimum(first // LANES, n_seg_pad // LANES - 1)
    trips = jnp.where(first < n_segments, last // LANES - first // LANES + 1, 0)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(B, bb), S // STEP_SLOTS),
            in_specs=[
                pl.BlockSpec((SUBBLOCKS, LANES), lambda b, i, base, trips: (i, 0)),
                pl.BlockSpec((bb, STEP_SLOTS), lambda b, i, base, trips: (b, i)),
                pl.BlockSpec((1, STEP_SLOTS), lambda b, i, base, trips: (0, i)),
            ],
            out_specs=pl.BlockSpec(
                (bb, n_seg_pad), lambda b, i, base, trips: (b, 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((B, n_seg_pad), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
    )(base, trips, ids2, xT, values.reshape(1, S))


def analysis_programs():
    """Registry hook: the kernel as the espmm forward and dX passes run it,
    inside ``core.sparsity.coo_matmul_T``'s chunk scan, at CIFAR-10's first
    layer (3072 -> 4000, 141,440 connections, batch 128). A TPU compile
    lowers the kernel, any other backend XLA's ``segment_sum``; the
    contract holds for both."""
    from repro.analysis.registry import AuditProgram, Contract, ProgramSpec
    from repro.core import sparsity

    src_dim, n_segments, nnz, batch = 3072, 4000, 141_440, 128

    def build() -> AuditProgram:
        slot = jnp.arange(nnz, dtype=jnp.int32)
        args = (
            jnp.zeros((src_dim, batch), jnp.float32),   # srcT
            jnp.zeros((nnz,), jnp.float32),             # values
            slot % src_dim,                             # gather_idx
            slot * n_segments // nnz,                   # segment_idx (sorted)
        )
        program = functools.partial(sparsity.coo_matmul_T, n_segments=n_segments)
        return AuditProgram(
            make=lambda donate: jax.jit(program, donate_argnums=donate),
            args=args,
            meta={"batch": batch, "nnz": nnz, "n_segments": n_segments},
        )

    # a chunk's (B, chunk) slab is SPMM_TEMP_BUDGET_ELEMS f32 elements: the
    # slab and the chunked index arrays stay within two of them
    slab_bytes = 4 * sparsity.SPMM_TEMP_BUDGET_ELEMS
    return [
        ProgramSpec(
            name="kernels.sorted_segment_sum",
            subsystem=__name__,
            contract=Contract(
                max_unsorted_scatter=0,
                max_intermediate_elems=sparsity.SPMM_TEMP_BUDGET_ELEMS,
                max_temp_bytes=2 * slab_bytes,
                expected_compiles=1,
            ),
            build=build,
            notes="coo_matmul_T: one-hot MXU kernel on TPU, segment_sum elsewhere",
        ),
    ]
