"""Jit'd public ops for sparse linear layers.

Block granularity — three interchangeable implementations (same math, same
topology arrays):

* ``bsmm_pallas``   — the Pallas TPU kernel (custom_vjp wiring fwd/dX/dW
                      kernels). ``interpret=True`` validates on CPU.
* ``bsmm_xla``      — XLA-native gather/einsum/scatter-add. FLOPs scale with
                      live blocks; natively differentiable; shards cleanly
                      under GSPMD (used by the multi-pod dry-run).
* ``ref.bsmm_ref``  — densify-then-matmul oracle (tests only).

Element granularity (the paper-faithful COO path) — dispatched by ``espmm``:

* ``custom``  — hand-derived ``custom_vjp`` over the transpose-free chunked
                segment-sum passes (DESIGN.md §1 "Backward"): forward in
                transposed (out_dim, batch) layout over the canonical
                (col, row) order; dX over the row-sorted dual order (sorted
                segment ids — no unsorted scatter in the train step; on the
                TPU both segment sums are the one-hot MXU kernel);
                dW as a chunked per-slot batch contraction. All three passes
                peak at O(batch * chunk) intermediate memory.
* ``segment`` — the same chunked forward with XLA-autodiff backward; never
                selected by ``auto`` (its scan autodiff re-materializes
                O(batch * nnz) residuals) — reachable only when pinned, as
                the benchmarks' autodiff baseline.
* ``scatter`` — the original gather/scatter-add formulation (materializes
                (batch, nnz); reference/fallback).
* ``auto``    — ``scatter`` for small problems, ``custom`` at scale;
                thresholds calibrated on value_and_grad wall clock
                (``core.sparsity.SPMM_AUTO_*``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparsity import (
    SPMM_AUTO_ELEMS,
    SPMM_AUTO_NNZ,
    SPMM_INFER_ELEMS,
    SPMM_INFER_NNZ,
    BlockMeta,
    BlockTopoArrays,
    ElemTopoArrays,
    coo_dw,
    coo_matmul_T,
    element_spmm,
    element_spmm_segment,
    spmm_chunk_for,
)
from repro.kernels import block_sparse_matmul as _k
from repro.runtime import donation


def _float0_zeros(x):
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


# ---------------------------------------------------------------------------
# Pallas path with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _bsmm_core(meta: BlockMeta, block_b: int, interpret: bool, x, values, topo):
    return _k.bsmm_fwd(
        x,
        values,
        topo.rows,
        topo.cols,
        topo.first_col,
        grid_n=meta.grid_n,
        block_b=block_b,
        interpret=interpret,
    )


def _bsmm_core_fwd(meta, block_b, interpret, x, values, topo):
    y = _bsmm_core(meta, block_b, interpret, x, values, topo)
    return y, (x, values, topo)


def _bsmm_core_bwd(meta, block_b, interpret, res, dy):
    x, values, topo = res
    dx = _k.bsmm_dx(
        dy,
        values,
        topo.rows_r,
        topo.cols_r,
        topo.first_row,
        topo.perm_r,
        grid_m=meta.grid_m,
        block_b=block_b,
        interpret=interpret,
    )
    dw = _k.bsmm_dw(
        x,
        dy,
        topo.rows,
        topo.cols,
        n_blocks=values.shape[0],
        block_m=meta.block_m,
        block_n=meta.block_n,
        block_b=block_b,
        interpret=interpret,
    )
    dtopo = BlockTopoArrays(*(_float0_zeros(t) for t in topo))
    return dx.astype(x.dtype), dw.astype(values.dtype), dtopo


_bsmm_core.defvjp(_bsmm_core_fwd, _bsmm_core_bwd)


def bsmm_pallas(
    x: jax.Array,
    values: jax.Array,
    topo: BlockTopoArrays,
    meta: BlockMeta,
    *,
    block_b: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Block-sparse ``y = x @ W`` for x of shape (..., in_dim)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    B = x2.shape[0]
    bb = min(block_b, _round_up(B, 8))
    pad_b = _round_up(B, bb) - B
    pad_m = meta.padded_in - meta.in_dim
    if pad_b or pad_m:
        x2 = jnp.pad(x2, ((0, pad_b), (0, pad_m)))
    y = _bsmm_core(meta, bb, interpret, x2, values, topo)
    y = y[:B, : meta.out_dim]
    return y.reshape(*lead, meta.out_dim)


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


# ---------------------------------------------------------------------------
# XLA-native truly sparse path (gather -> block einsum -> scatter-add)
# ---------------------------------------------------------------------------


def bsmm_xla(
    x: jax.Array,
    values: jax.Array,
    topo: BlockTopoArrays,
    meta: BlockMeta,
) -> jax.Array:
    lead = x.shape[:-1]
    pad_m = meta.padded_in - meta.in_dim
    if pad_m:
        x = jnp.pad(x, [(0, 0)] * len(lead) + [(0, pad_m)])
    xr = x.reshape(*lead, meta.grid_m, meta.block_m)
    xg = jnp.take(xr, topo.rows, axis=-2)  # (..., nb, bm)
    yb = jnp.einsum(
        "...nm,nmo->...no", xg, values, preferred_element_type=jnp.float32
    ).astype(x.dtype)
    y = jnp.zeros((*lead, meta.grid_n, meta.block_n), x.dtype)
    y = y.at[..., topo.cols, :].add(yb)
    y = y.reshape(*lead, meta.padded_out)
    return y[..., : meta.out_dim]


def bsmm(
    x: jax.Array,
    values: jax.Array,
    topo: BlockTopoArrays,
    meta: BlockMeta,
    *,
    impl: str = "xla",
    interpret: bool = False,
    block_b: int = 128,
) -> jax.Array:
    if impl == "xla":
        return bsmm_xla(x, values, topo, meta)
    if impl == "pallas":
        return bsmm_pallas(
            x, values, topo, meta, block_b=block_b, interpret=interpret
        )
    raise ValueError(f"unknown impl {impl!r}")


# ---------------------------------------------------------------------------
# Element-sparse (COO) path
# ---------------------------------------------------------------------------
#
# Hand-derived VJP (DESIGN.md §1 "Backward"). For y = x @ W with W in COO:
#
#   fwd  yT[cols[j], :]  += xT[rows[j], :]  * v[j]     canonical (col,row)
#   dX   dxT[rows_r[j],:] += dyT[cols_r[j],:] * v[perm_r[j]]   row-sorted
#   dW   dv[j]            = sum_b x[b, rows[j]] * dy[b, cols[j]]
#
# Every pass is a chunked sorted-segment reduction (or contraction) in
# transposed (features, batch) layout — no per-chunk transposes, no unsorted
# scatter, peak intermediate O(batch * chunk) for all three. fwd and dX share
# ``coo_matmul_T``, whose segment sum is the one-hot MXU kernel on the TPU.


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _espmm_core(out_dim: int, chunk, x2, values, topo: ElemTopoArrays):
    with jax.named_scope("espmm_fwd"):
        yT = coo_matmul_T(
            x2.T, values, topo.rows, topo.cols, out_dim, chunk=chunk
        )
    return yT.T


def _espmm_core_fwd(out_dim, chunk, x2, values, topo):
    y = _espmm_core(out_dim, chunk, x2, values, topo)
    return y, (x2, values, topo)


def _espmm_core_bwd(out_dim, chunk, res, dy):
    x2, values, topo = res
    in_dim = x2.shape[-1]
    dyT = dy.T
    # dX over the row-sorted dual order: segment ids (rows_r) sorted, the
    # values gathered through perm_r from their canonical slots
    with jax.named_scope("espmm_dx"):
        dxT = coo_matmul_T(
            dyT, values[topo.perm_r], topo.cols_r, topo.rows_r, in_dim,
            chunk=chunk,
        )
    # dW in canonical slot order
    with jax.named_scope("espmm_dw"):
        dv = coo_dw(x2.T, dyT, topo.rows, topo.cols, chunk=chunk)
    dtopo = ElemTopoArrays(*(_float0_zeros(t) for t in topo))
    return dxT.T.astype(x2.dtype), dv.astype(values.dtype), dtopo


_espmm_core.defvjp(_espmm_core_fwd, _espmm_core_bwd)


def espmm_custom(
    x: jax.Array,
    values: jax.Array,
    topo: ElemTopoArrays,
    out_dim: int,
    *,
    chunk: int | None = None,
) -> jax.Array:
    """Element-sparse ``y = x @ W`` with the hand-derived custom VJP."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    chunk = spmm_chunk_for(x2.shape[0], int(values.shape[0]), chunk)
    y = _espmm_core(out_dim, chunk, x2, values, topo)
    return y.reshape(*lead, out_dim)


def espmm(
    x: jax.Array,
    values: jax.Array,
    topo: ElemTopoArrays,
    out_dim: int,
    *,
    impl: str = "auto",
    chunk: int | None = None,
) -> jax.Array:
    """Element-sparse ``y = x @ W`` for COO topology arrays.

    ``auto`` (default) picks per call site: scatter-add for small problems
    (faster on CPU XLA, intermediate still tiny, and its autodiff backward
    is still cheap), the hand-derived custom-VJP path once nnz or the
    (batch, nnz) intermediate crosses the thresholds in ``core.sparsity`` —
    keeping peak memory flat in nnz and the backward scatter-free at scale.
    The thresholds are calibrated on ``value_and_grad`` timings (a train
    step is ~2/3 backward), not forward-only ones.
    """
    if impl == "auto":
        nnz = int(values.shape[0])
        batch = int(np.prod(x.shape[:-1])) if x.ndim > 1 else 1
        big = nnz >= SPMM_AUTO_NNZ or batch * nnz >= SPMM_AUTO_ELEMS
        impl = "custom" if big else "scatter"
    if impl == "custom":
        return espmm_custom(x, values, topo, out_dim, chunk=chunk)
    if impl == "segment":
        return element_spmm_segment(
            x, values, topo.rows, topo.cols, out_dim, chunk=chunk
        )
    if impl == "scatter":
        return element_spmm(x, values, topo.rows, topo.cols, out_dim)
    raise ValueError(f"unknown element impl {impl!r}")


# ---------------------------------------------------------------------------
# Forward-only (serving) entries
# ---------------------------------------------------------------------------
#
# The serving engine never differentiates, so these entries (a) skip the
# custom_vjp wrappers entirely — no residual tuple is even traced — and
# (b) dispatch on *forward-only* calibration (``SPMM_INFER_*``), not the
# value_and_grad thresholds ``espmm``'s "auto" uses for training.


def espmm_infer(
    x: jax.Array,
    values: jax.Array,
    topo: ElemTopoArrays,
    out_dim: int,
    *,
    chunk: int | None = None,
) -> jax.Array:
    """Element-sparse ``y = x @ W``, inference dispatch (no VJP machinery).

    Scatter-add while its (batch, nnz) intermediate is affordable and nnz is
    below the forward-only cliff (~65k on XLA:CPU); the chunked segment-sum
    path beyond — same O(batch * chunk) temp bound as training, but reached
    at ~30x larger problems because no backward pass has to be paid for.
    """
    nnz = int(values.shape[0])
    batch = int(np.prod(x.shape[:-1])) if x.ndim > 1 else 1
    big = nnz >= SPMM_INFER_NNZ or batch * nnz >= SPMM_INFER_ELEMS
    if big:
        return element_spmm_segment(
            x, values, topo.rows, topo.cols, out_dim, chunk=chunk
        )
    return element_spmm(x, values, topo.rows, topo.cols, out_dim)


def bsmm_infer(
    x: jax.Array,
    values: jax.Array,
    topo: BlockTopoArrays,
    meta: BlockMeta,
) -> jax.Array:
    """Block-sparse ``y = x @ W`` for serving: the XLA-native gather/einsum
    path (natively forward-only — no custom_vjp residuals to trace), named
    separately so engine call sites read as inference and can re-dispatch
    (e.g. to a Pallas decode kernel) without touching the training path."""
    return bsmm_xla(x, values, topo, meta)


# ---------------------------------------------------------------------------
# Out-of-core per-shard entries (repro.xl, DESIGN.md §7)
# ---------------------------------------------------------------------------
#
# The XL substrate streams a layer's COO topology through the device as
# fixed-capacity connection shards; these are the only two device programs
# its forward/backward ever dispatches. Everything about their shapes is
# static across the whole model — the shard capacity, the chunk width and
# the d_max-padded (features, batch) activation layout come from the plan
# (xl/planner.py) — so a full training run compiles each of them exactly
# once, no matter how many shards, layers or epochs stream through.

def _xl_shard_acc_impl(
    acc: jax.Array,
    srcT: jax.Array,
    values: jax.Array,
    gather_idx: jax.Array,
    segment_idx: jax.Array,
    *,
    n_segments: int,
    chunk: int,
) -> jax.Array:
    """One connection shard's chunked sorted-segment reduction, accumulated
    into the running ``(n_segments, B)`` buffer:

        acc[segment_idx[j], :] += srcT[gather_idx[j], :] * values[j]

    The ONE streamed matmul program for both directions: forward shards pass
    the canonical order (gather ``rows``, segment ``cols``); dX shards pass
    the row-sorted dual order (gather ``cols_r``, segment ``rows_r``) with
    values host-gathered through ``perm_r``. Shards are canonical-order
    slices, so ``segment_idx`` is non-decreasing within every shard; padded
    tail slots carry segment id ``n_segments`` (dropped by ``segment_sum``)
    and value 0. Because shard capacity is a multiple of ``chunk``, the
    chunk partition — hence the f32 addition order — matches one in-core
    ``coo_matmul_T`` over the concatenated shards (DESIGN.md §7).
    """
    return coo_matmul_T(
        srcT, values, gather_idx, segment_idx, n_segments, chunk=chunk, acc=acc
    )


def make_xl_shard_acc(donate=None):
    """Fresh jitted shard-acc. The accumulator (arg 0) is donated per the
    central policy (``repro.runtime.donation``) so XLA reuses its buffer in
    place; ``donate`` overrides the policy (contract-auditor force builds)."""
    return jax.jit(
        _xl_shard_acc_impl,
        static_argnames=("n_segments", "chunk"),
        donate_argnums=donation.donate_argnums(0, override=donate),
    )


@functools.cache
def xl_shard_acc():
    """The shared production instance every stream executor dispatches
    through — ONE compile per (shapes, n_segments, chunk), however many
    layers/shards. Built on first use: the donation policy asks for the
    backend, and importing this module must not claim a device."""
    return make_xl_shard_acc()


def _xl_shard_dw_impl(
    xT: jax.Array,
    dyT: jax.Array,
    rows: jax.Array,
    cols: jax.Array,
    *,
    chunk: int,
) -> jax.Array:
    """Per-shard dW: ``dv[j] = sum_b x[b, rows[j]] * dy[b, cols[j]]`` for one
    canonical-order shard — each slot's batch contraction is independent, so
    sharding cannot change its f32 reduction order (bit-equal to the in-core
    ``coo_dw`` regardless of shard boundaries). Padded tail slots gather
    clamped garbage; the host writes back only the shard's real extent.
    """
    return coo_dw(xT, dyT, rows, cols, chunk=chunk)


def make_xl_shard_dw(donate=None):
    """Fresh jitted shard-dW (no donated args: every input is reused by the
    caller; ``donate`` exists for auditor symmetry with shard-acc)."""
    return jax.jit(
        _xl_shard_dw_impl,
        static_argnames=("chunk",),
        donate_argnums=donation.donate_argnums(override=donate),
    )


@functools.cache
def xl_shard_dw():
    """Shared production shard-dW, built on first use (see ``xl_shard_acc``)."""
    return make_xl_shard_dw()
