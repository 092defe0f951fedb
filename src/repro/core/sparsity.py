"""Truly sparse weight representations.

Two granularities, both storing ONLY the live parameters (no dense mask):

* ``ElementSparse`` — COO element-level sparsity. This is the paper-faithful
  representation (SciPy-CSR equivalent) used for the SET-MLP experiments.
  Compute is a chunked segment-sum SpMM whose FLOP count is O(B * nnz);
  topology arrays carry a dual (col,row)/(row,col) order so the hand-derived
  backward passes are segment reductions too (DESIGN.md §1b).

* ``BlockSparse`` — MXU-aligned block sparsity (TPU adaptation, see DESIGN.md
  §2). Active (block_m, block_n) tiles are stored as a compact
  ``(n_blocks, bm, bn)`` array plus int32 block coordinates. Compute goes
  through either a Pallas kernel (``repro.kernels``) or an XLA-native
  gather/segment-sum einsum whose FLOPs also scale with the live block count.

Topology (coordinates) is intentionally kept in host numpy and treated as
non-trainable data: SET evolution / Importance Pruning happen *between* jitted
train segments (the paper evolves once per epoch on the master), so the jitted
step functions only ever see fixed-capacity arrays and never recompile when
connections move.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import sorted_segment_sum as _sss

__all__ = [
    "BlockMeta",
    "BlockTopoArrays",
    "BlockTopology",
    "ElemTopoArrays",
    "ElementTopology",
    "coo_dw",
    "coo_matmul_T",
    "density_from_epsilon",
    "element_spmm",
    "element_spmm_segment",
    "erdos_renyi_nnz",
    "spmm_chunk_for",
]


def density_from_epsilon(epsilon: float, n_in: int, n_out: int) -> float:
    """SET's Erdős–Rényi density: p = eps * (n_in + n_out) / (n_in * n_out)."""
    return min(1.0, float(epsilon) * (n_in + n_out) / (n_in * n_out))


def erdos_renyi_nnz(epsilon: float, n_in: int, n_out: int) -> int:
    return max(1, int(round(density_from_epsilon(epsilon, n_in, n_out) * n_in * n_out)))


# ---------------------------------------------------------------------------
# Block sparsity
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BlockMeta:
    """Static metadata of a block-sparse matrix (hashable; safe for jit)."""

    in_dim: int
    out_dim: int
    block_m: int = 128
    block_n: int = 128

    @property
    def grid_m(self) -> int:
        return -(-self.in_dim // self.block_m)

    @property
    def grid_n(self) -> int:
        return -(-self.out_dim // self.block_n)

    @property
    def padded_in(self) -> int:
        return self.grid_m * self.block_m

    @property
    def padded_out(self) -> int:
        return self.grid_n * self.block_n

    @property
    def total_blocks(self) -> int:
        return self.grid_m * self.grid_n


class BlockTopoArrays(NamedTuple):
    """Device-side topology for the kernels. All int32, shape (n_blocks,).

    Canonical order is sorted by (col, row) — required by the forward kernel's
    output-revisit accumulation. ``*_r`` fields are the same topology sorted by
    (row, col) for the dX kernel; ``perm_r[i]`` maps row-ordered slot i back to
    the canonical slot owning its values.
    """

    rows: jax.Array
    cols: jax.Array
    first_col: jax.Array  # 1 where cols[i] != cols[i-1]
    rows_r: jax.Array
    cols_r: jax.Array
    first_row: jax.Array  # 1 where rows_r[i] != rows_r[i-1]
    perm_r: jax.Array


def _first_flags(keys: np.ndarray) -> np.ndarray:
    first = np.ones_like(keys, dtype=np.int32)
    if keys.size > 1:
        first[1:] = (keys[1:] != keys[:-1]).astype(np.int32)
    return first


class BlockTopology:
    """Host-side (numpy) block topology with SET bookkeeping.

    Invariants:
      * slots sorted by (col, row); positions unique
      * every block-column in [0, grid_n) is covered by >= 1 slot
        ("no output neuron without incoming connections"); coverage slots may
        be zero-valued but keep the Pallas output-tile zeroing correct.
    """

    def __init__(self, meta: BlockMeta, rows: np.ndarray, cols: np.ndarray):
        self.meta = meta
        order = np.lexsort((rows, cols))
        self.rows = np.asarray(rows, np.int32)[order]
        self.cols = np.asarray(cols, np.int32)[order]
        self._check()

    # -- construction -----------------------------------------------------

    @classmethod
    def erdos_renyi(
        cls,
        meta: BlockMeta,
        density: float,
        rng: np.random.Generator,
    ) -> "BlockTopology":
        """Sample an ER block topology with ~density fraction of live blocks."""
        total = meta.total_blocks
        n_blocks = int(np.clip(round(density * total), meta.grid_n, total))
        flat = rng.choice(total, size=n_blocks, replace=False).astype(np.int64)
        rows = (flat // meta.grid_n).astype(np.int32)
        cols = (flat % meta.grid_n).astype(np.int32)
        rows, cols = _ensure_coverage(meta, rows, cols, rng)
        return cls(meta, rows, cols)

    @classmethod
    def from_epsilon(
        cls, meta: BlockMeta, epsilon: float, rng: np.random.Generator
    ) -> "BlockTopology":
        return cls.erdos_renyi(
            meta, density_from_epsilon(epsilon, meta.in_dim, meta.out_dim), rng
        )

    # -- properties ---------------------------------------------------------

    @property
    def n_blocks(self) -> int:
        return int(self.rows.shape[0])

    @property
    def density(self) -> float:
        return self.n_blocks / self.meta.total_blocks

    @property
    def n_params(self) -> int:
        return self.n_blocks * self.meta.block_m * self.meta.block_n

    def _check(self) -> None:
        m = self.meta
        assert self.rows.shape == self.cols.shape
        assert (0 <= self.rows).all() and (self.rows < m.grid_m).all()
        assert (0 <= self.cols).all() and (self.cols < m.grid_n).all()
        flat = self.rows.astype(np.int64) * m.grid_n + self.cols
        assert np.unique(flat).size == flat.size, "duplicate block positions"
        assert np.unique(self.cols).size == m.grid_n, (
            "coverage invariant violated: some output block-column has no slot"
        )

    # -- device views ---------------------------------------------------------

    def device_arrays(self) -> BlockTopoArrays:
        rows, cols = self.rows, self.cols
        perm_r = np.lexsort((cols, rows)).astype(np.int32)
        rows_r = rows[perm_r]
        cols_r = cols[perm_r]
        return BlockTopoArrays(
            rows=jnp.asarray(rows),
            cols=jnp.asarray(cols),
            first_col=jnp.asarray(_first_flags(cols)),
            rows_r=jnp.asarray(rows_r),
            cols_r=jnp.asarray(cols_r),
            first_row=jnp.asarray(_first_flags(rows_r)),
            perm_r=jnp.asarray(perm_r),
        )

    # -- values -----------------------------------------------------------

    def init_values(
        self,
        rng: np.random.Generator,
        dtype=jnp.float32,
        scheme: str = "he_uniform",
    ) -> jax.Array:
        m = self.meta
        shape = (self.n_blocks, m.block_m, m.block_n)
        vals = _init_numpy(rng, shape, fan_in_dense=m.in_dim, scheme=scheme)
        # connections that fall into the zero-padding region of a padded grid
        # must stay zero so padded inputs contribute nothing.
        return jnp.asarray(vals, dtype=dtype)

    def to_dense(self, values: jax.Array) -> jax.Array:
        """Scatter block values into the dense (in_dim, out_dim) matrix."""
        m = self.meta
        dense = jnp.zeros((m.grid_m, m.block_m, m.grid_n, m.block_n), values.dtype)
        dense = dense.at[self.rows, :, self.cols, :].set(values)
        dense = dense.reshape(m.padded_in, m.padded_out)
        return dense[: m.in_dim, : m.out_dim]


def _ensure_coverage(
    meta: BlockMeta, rows: np.ndarray, cols: np.ndarray, rng: np.random.Generator
) -> Tuple[np.ndarray, np.ndarray]:
    """Swap surplus slots into uncovered block-columns (keeps slot count)."""
    covered = np.zeros(meta.grid_n, bool)
    covered[cols] = True
    missing = np.flatnonzero(~covered)
    if missing.size == 0:
        return rows, cols
    # donate slots from columns having > 1 block
    order = np.argsort(cols, kind="stable")
    counts = np.bincount(cols, minlength=meta.grid_n)
    donors = [i for i in order if counts[cols[i]] > 1]
    if len(donors) < missing.size:
        raise ValueError(
            f"cannot cover {missing.size} empty block-columns with "
            f"{len(donors)} donor slots; raise density"
        )
    taken = set()
    di = 0
    rows = rows.copy()
    cols = cols.copy()
    for c in missing:
        while True:
            slot = donors[di]
            di += 1
            if counts[cols[slot]] > 1:
                counts[cols[slot]] -= 1
                break
        cols[slot] = c
        rows[slot] = rng.integers(meta.grid_m)
        taken.add(slot)
    # dedupe (rare): if the random row collides within the column, nudge
    flat = rows.astype(np.int64) * meta.grid_n + cols
    while np.unique(flat).size != flat.size:
        _, idx, cnt = np.unique(flat, return_index=True, return_counts=True)
        for f, i0, c0 in zip(_, idx, cnt):
            if c0 > 1:
                dup = np.flatnonzero(flat == f)[1:]
                for d in dup:
                    rows[d] = rng.integers(meta.grid_m)
        flat = rows.astype(np.int64) * meta.grid_n + cols
    return rows, cols


# ---------------------------------------------------------------------------
# Element sparsity (paper-faithful COO)
# ---------------------------------------------------------------------------


class ElemTopoArrays(NamedTuple):
    """Device-side dual-order COO topology. All int32, shape (nnz,).

    Canonical order is sorted by (col, row) — ``cols`` is non-decreasing, so
    the forward/dW passes are sorted segment reductions. The ``*_r`` fields
    are the same connections re-sorted by (row, col) for the hand-derived dX
    backward pass (``rows_r`` non-decreasing — sorted segment ids, so no
    unsorted scatter); ``perm_r[j]`` maps row-ordered slot j back to the
    canonical slot owning its value. Sorted is not scatter-free: on the TPU,
    XLA's sorted ``segment_sum`` is a serialized scatter-add, which is why
    the forward and dX segment sums run the one-hot MXU kernel there
    (``kernels.sorted_segment_sum``, DESIGN.md §1b). ``first_col``/
    ``first_row`` flag segment boundaries (1 where the sort key changes),
    mirroring ``BlockTopoArrays``: the element passes read the sorted ids
    themselves and not these flags, but the layouts are kept identical so
    the two granularities stay drop-in interchangeable.
    """

    rows: jax.Array
    cols: jax.Array
    first_col: jax.Array  # 1 where cols[i] != cols[i-1]
    rows_r: jax.Array
    cols_r: jax.Array
    first_row: jax.Array  # 1 where rows_r[i] != rows_r[i-1]
    perm_r: jax.Array


class ElementTopology:
    """Host-side COO topology for the paper's SET-MLP path.

    rows/cols are int32 (nnz,) with unique positions, sorted by (col, row).
    """

    def __init__(self, in_dim: int, out_dim: int, rows: np.ndarray, cols: np.ndarray):
        self.in_dim = int(in_dim)
        self.out_dim = int(out_dim)
        order = np.lexsort((rows, cols))
        self.rows = np.asarray(rows, np.int32)[order]
        self.cols = np.asarray(cols, np.int32)[order]
        flat = self.rows.astype(np.int64) * out_dim + self.cols
        assert np.unique(flat).size == flat.size, "duplicate connections"

    @classmethod
    def erdos_renyi(
        cls, in_dim: int, out_dim: int, epsilon: float, rng: np.random.Generator
    ) -> "ElementTopology":
        nnz = erdos_renyi_nnz(epsilon, in_dim, out_dim)
        nnz = min(nnz, in_dim * out_dim)
        flat = rng.choice(in_dim * out_dim, size=nnz, replace=False).astype(np.int64)
        return cls(in_dim, out_dim, (flat // out_dim), (flat % out_dim))

    @property
    def nnz(self) -> int:
        return int(self.rows.shape[0])

    @property
    def density(self) -> float:
        return self.nnz / (self.in_dim * self.out_dim)

    def device_arrays(self) -> "ElemTopoArrays":
        rows, cols = self.rows, self.cols
        perm_r = np.lexsort((cols, rows)).astype(np.int32)
        rows_r = rows[perm_r]
        cols_r = cols[perm_r]
        return ElemTopoArrays(
            rows=jnp.asarray(rows),
            cols=jnp.asarray(cols),
            first_col=jnp.asarray(_first_flags(cols)),
            rows_r=jnp.asarray(rows_r),
            cols_r=jnp.asarray(cols_r),
            first_row=jnp.asarray(_first_flags(rows_r)),
            perm_r=jnp.asarray(perm_r),
        )

    def init_values(
        self, rng: np.random.Generator, dtype=jnp.float32, scheme: str = "he_uniform"
    ) -> jax.Array:
        vals = _init_numpy(rng, (self.nnz,), fan_in_dense=self.in_dim, scheme=scheme)
        return jnp.asarray(vals, dtype=dtype)

    def to_dense(self, values: jax.Array) -> jax.Array:
        dense = jnp.zeros((self.in_dim, self.out_dim), values.dtype)
        return dense.at[self.rows, self.cols].set(values)


def element_spmm(
    x: jax.Array, values: jax.Array, rows: jax.Array, cols: jax.Array, out_dim: int
) -> jax.Array:
    """Truly sparse y = x @ W for COO W. FLOPs = 2 * B * nnz.

    Reference/fallback formulation only. It materializes the full
    (batch, nnz) contribution array, and — worse — its autodiff VJP is the
    transposed scatter/gather pair XLA emits: the dX path scatters with
    *unsorted* row indices (the scatter cliff ``BENCH_kernels.json`` measures
    at 3–14x beyond ~65k nnz) and re-materializes the (batch, nnz)
    contribution array again on the way back. The memory-bounded default for
    training is the hand-derived custom-VJP path (``kernels.ops.espmm`` with
    ``impl="custom"``; DESIGN.md §1 "Backward"), whose three passes all peak
    at O(batch * chunk).
    """
    contrib = x[..., rows] * values  # (..., nnz)
    out_shape = x.shape[:-1] + (out_dim,)
    y = jnp.zeros(out_shape, contrib.dtype)
    return y.at[..., cols].add(contrib)


# Batch-aware chunk policy: instead of a fixed width, target a fixed
# (batch * chunk) temp-element budget so the peak intermediate of every
# chunked pass (fwd / dX / dW) is the same number of bytes whatever the
# batch. 2M f32 elements = 8 MiB per temp; at the benchmark's B=256 this
# reproduces the previous fixed chunk of 8192.
SPMM_TEMP_BUDGET_ELEMS = 2 * 1024 * 1024
# Floor so tiny batches don't degenerate into thousands of scan steps.
SPMM_CHUNK_MIN = 512

# "auto" impl policy for ``kernels.ops.espmm`` — calibrated on
# jax.value_and_grad wall clock (fwd + dX + dW), not forward-only: the
# scatter formulation's autodiff backward hits the unsorted-scatter cliff
# far earlier and harder than its forward (measured on XLA:CPU at B=256:
# custom/scatter value_and_grad speedup 1.2x by nnz=1k, 2.1x by 4k, 5x by
# 65k, 15x by 262k), so the crossover sits two orders of magnitude below
# the old forward-only fit of 65536. Below this nnz the scatter-add
# formulation still wins the *forward* (eval shares this dispatch), its
# (batch, nnz) intermediate is still tiny, and its backward deficit is
# ~20% — above it the custom-VJP path wins both directions outright
# (benchmarks/kernels_micro.py tracks fwd and value_and_grad rows).
SPMM_AUTO_NNZ = 2048
# ...and independently of nnz, switch to the memory-bounded custom-VJP path
# once the (batch, nnz) scatter intermediate (which autodiff re-materializes
# on the backward pass too) would exceed this many elements.
SPMM_AUTO_ELEMS = 512 * 1024

# Forward-only "auto" policy for serving (``kernels.ops.espmm_infer``).
# Inference never runs a backward pass, so the value_and_grad-calibrated
# thresholds above are wrong for it: the scatter formulation's *forward*
# stays ahead of the chunked segment path until far larger problems (the
# PR-1 forward-only fit measured the crossover near 65k nnz on XLA:CPU —
# the scatter cliff the training thresholds dodge is a backward artifact).
# Serving still bounds peak temp memory: beyond SPMM_INFER_ELEMS elements
# the (batch, nnz) scatter intermediate would exceed the budget, so the
# chunked segment path takes over regardless of nnz.
SPMM_INFER_NNZ = 65536
SPMM_INFER_ELEMS = 4 * 1024 * 1024


def spmm_chunk_for(batch: int, nnz: int, chunk: Optional[int] = None) -> int:
    """Chunk width for the chunked element passes.

    ``chunk=None`` picks the batch-aware width targeting
    ``SPMM_TEMP_BUDGET_ELEMS`` temp elements; an explicit ``chunk`` is only
    clamped to [1, nnz].
    """
    if chunk is None:
        chunk = max(SPMM_CHUNK_MIN, SPMM_TEMP_BUDGET_ELEMS // max(1, int(batch)))
    return max(1, min(int(chunk), max(1, int(nnz))))


def element_spmm_segment(
    x: jax.Array,
    values: jax.Array,
    rows: jax.Array,
    cols: jax.Array,
    out_dim: int,
    *,
    chunk: Optional[int] = None,
) -> jax.Array:
    """Col-sorted segment-sum SpMM (DESIGN.md §1). Same math as
    ``element_spmm`` but the (batch, nnz) contribution array is never
    materialized at once: a thin wrapper over :func:`coo_matmul_T` (the
    shared chunked sorted-segment reduction), so peak intermediate memory is
    O(batch * chunk) instead of O(batch * nnz).

    Differentiable by XLA autodiff — but autodiff through the scan saves a
    residual slab per chunk (O(batch * nnz) again); training goes through
    the hand-derived custom VJP in ``kernels.ops`` instead, which reuses the
    same primitive for its dX pass over the row-sorted dual order.

    Requires the canonical topology ordering (sorted by (col, row) —
    ``ElementTopology`` guarantees it), which makes every chunk's segment ids
    sorted and the segment reduction a single linear pass.

    ``chunk=None`` picks the batch-aware width (``spmm_chunk_for``).
    """
    nnz = int(values.shape[0])
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    dtype = jnp.result_type(x2.dtype, values.dtype)
    if nnz == 0:  # explicit: no connections -> zero output, no scan
        return jnp.zeros((*lead, out_dim), dtype)
    yT = coo_matmul_T(x2.T, values, rows, cols, out_dim, chunk=chunk)
    return yT.T.reshape(*lead, out_dim)


# ---------------------------------------------------------------------------
# transpose-free chunked passes (DESIGN.md §1 "Backward")
#
# The three passes of the hand-derived espmm VJP are the same primitive:
# a chunked sorted-segment reduction computed in transposed (features, batch)
# layout, so the only layout changes are one transpose of the operand on the
# way in and one of the result on the way out — never per chunk.
# ---------------------------------------------------------------------------


def coo_matmul_T(
    srcT: jax.Array,
    values: jax.Array,
    gather_idx: jax.Array,
    segment_idx: jax.Array,
    n_segments: int,
    *,
    chunk: Optional[int] = None,
    acc: Optional[jax.Array] = None,
) -> jax.Array:
    """``accT[segment_idx[j], :] += srcT[gather_idx[j], :] * values[j]``.

    ``srcT`` is (src_dim, B); returns (n_segments, B). ``segment_idx`` must be
    non-decreasing — the canonical (col, row) order for the forward
    (gather rows, segment cols) and the row-sorted dual order for dX
    (gather cols_r, segment rows_r) both guarantee it — so every chunk's
    reduction is a sorted segment sum. Peak intermediate is the (chunk, B)
    contribution slab; nnz is walked by a ``lax.scan``.

    The segment sum is chosen from what the call can observe (DESIGN.md
    §1b): on the TPU, where XLA's sorted ``segment_sum`` is a scatter-add
    that updates one accumulator row per slot in turn, an f32 reduction of
    a shape the kernel takes (``kernels.sorted_segment_sum.fits``) runs the
    one-hot MXU kernel; every other backend and shape keeps
    ``segment_sum``. The ``espmm.segment_reduce`` counter counts each
    traced call by the path it takes on the default backend.

    ``acc`` (optional, (n_segments, B)) is a carry-in accumulator: the result
    is ``acc`` plus this call's reduction, added chunk-by-chunk in the same
    left-to-right order the single-call path uses. The out-of-core substrate
    (``kernels.ops.xl_shard_acc``, DESIGN.md §7) threads one accumulator
    through a connection-shard stream; when shard boundaries are multiples of
    ``chunk``, the chunk partition — and therefore the f32 addition order —
    is identical to one in-core call over the concatenated shards, on
    either path.
    """
    nnz = int(values.shape[0])
    B = srcT.shape[-1]
    dtype = jnp.result_type(srcT.dtype, values.dtype)
    if nnz == 0:
        return acc if acc is not None else jnp.zeros((n_segments, B), dtype)
    chunk = spmm_chunk_for(B, nnz, chunk)
    args = (srcT, values, gather_idx, segment_idx, acc)
    xla = functools.partial(_coo_matmul_T_xla, n_segments=n_segments, chunk=chunk)
    if not _sss.fits(n_segments, B, chunk, dtype):
        _count_segment_reduce("xla")
        return xla(*args)
    _count_segment_reduce("pallas" if jax.default_backend() == "tpu" else "xla")
    pallas = functools.partial(
        _coo_matmul_T_pallas, n_segments=n_segments, chunk=chunk
    )
    return jax.lax.platform_dependent(*args, tpu=pallas, default=xla)


def _count_segment_reduce(path: str) -> None:
    obs.default_registry().counter("espmm.segment_reduce", path=path).inc()


def _chunked(gather_idx, segment_idx, values, *, n_segments, chunk):
    """The slots as (n_chunks, chunk) scan slices, the last chunk padded
    with slots of segment id ``n_segments`` (dropped by the reduction, and
    >= every real id so per-chunk sortedness holds) and value 0."""
    nnz = int(values.shape[0])
    n_chunks = -(-nnz // chunk)
    pad = n_chunks * chunk - nnz
    g_p = jnp.concatenate([gather_idx, jnp.zeros((pad,), gather_idx.dtype)])
    s_p = jnp.concatenate(
        [segment_idx, jnp.full((pad,), n_segments, segment_idx.dtype)]
    )
    v_p = jnp.concatenate([values, jnp.zeros((pad,), values.dtype)])
    return (
        g_p.reshape(n_chunks, chunk),
        s_p.reshape(n_chunks, chunk),
        v_p.reshape(n_chunks, chunk),
    )


def _coo_matmul_T_xla(srcT, values, gather_idx, segment_idx, acc, *, n_segments, chunk):
    """:func:`coo_matmul_T` through XLA's sorted ``segment_sum``."""
    nnz = int(values.shape[0])
    B = srcT.shape[-1]
    dtype = jnp.result_type(srcT.dtype, values.dtype)

    def one_chunk(g, s, v):
        contrib = srcT[g, :] * v[:, None]  # (chunk, B) — already transposed
        return jax.ops.segment_sum(
            contrib.astype(dtype), s, num_segments=n_segments,
            indices_are_sorted=True,
        )

    if nnz <= chunk:
        one = one_chunk(gather_idx, segment_idx, values)
        return one if acc is None else acc + one

    def body(a, sl):
        return a + one_chunk(*sl), None

    acc0 = jnp.zeros((n_segments, B), dtype) if acc is None else acc
    slices = _chunked(
        gather_idx, segment_idx, values, n_segments=n_segments, chunk=chunk
    )
    out, _ = jax.lax.scan(body, acc0, slices)
    return out


def _coo_matmul_T_pallas(
    srcT, values, gather_idx, segment_idx, acc, *, n_segments, chunk,
    interpret=False,
):
    """:func:`coo_matmul_T` through the one-hot MXU kernel: the XLA path's
    chunks and gather, each chunk's gathered slab made lane-dense (B,
    chunk) and padded to the kernel's slot multiple, weighted by the values
    and reduced in the kernel into a (B, n_seg_pad) partial sum, and added
    to the carry as the XLA path adds its chunks."""
    nnz = int(values.shape[0])
    B = srcT.shape[-1]
    step = _sss.STEP_SLOTS

    def reduce(xT, v, s):
        pad = -(-s.shape[0] // step) * step - s.shape[0]
        return _sss.sorted_segment_sum_T(
            jnp.pad(xT, ((0, 0), (0, pad))),
            jnp.pad(v, (0, pad)),
            jnp.pad(s, (0, pad), constant_values=n_segments),
            n_segments=n_segments, interpret=interpret,
        )

    if nnz <= chunk:
        # the XLA path's row gather, which XLA shares with the dW pass's
        # gather of the same rows
        one = reduce(srcT[gather_idx, :].T, values, segment_idx)
        one = one[:, :n_segments].T
        return one if acc is None else acc + one

    if B >= _sss.LANES:
        # a gathered row fills the lanes: XLA's row gather, then a transpose
        def gather(g):
            return srcT[g, :].T
    else:
        # short rows: a column gather of the (B, src_dim) operand, which on
        # a v5e takes leukemia's batch-5 dX 8.5 ms per step against 10.1,
        # and its step 493 MB of temporaries against 519. At batch 512 it
        # compiles to a gather that writes its slab one element at a time
        # (leukemia's evaluation: 132 ms per epoch against 75).
        src = srcT.T

        def gather(g):
            return src[:, g]

    def body(a, sl):
        g, s, v = sl
        return a + reduce(gather(g), v, s), None

    n_seg_pad = -(-n_segments // _sss.LANES) * _sss.LANES
    if acc is None:
        acc0 = jnp.zeros((B, n_seg_pad), jnp.float32)
    else:
        acc0 = jnp.pad(acc.T, ((0, 0), (0, n_seg_pad - n_segments)))
    slices = _chunked(
        gather_idx, segment_idx, values, n_segments=n_segments, chunk=chunk
    )
    out, _ = jax.lax.scan(body, acc0, slices)
    return out[:, :n_segments].T


def coo_dw(
    xT: jax.Array,
    dyT: jax.Array,
    rows: jax.Array,
    cols: jax.Array,
    *,
    chunk: Optional[int] = None,
) -> jax.Array:
    """Per-slot batch contraction ``dv[j] = sum_b x[b, rows[j]] * dy[b, cols[j]]``.

    ``xT`` is (in_dim, B), ``dyT`` is (out_dim, B); returns (nnz,) aligned to
    the canonical slot order. Chunked like :func:`coo_matmul_T`: the two
    gathered (chunk, B) slabs are the peak intermediate, reduced over batch
    immediately — the (batch, nnz) contribution array is never materialized.
    """
    nnz = int(rows.shape[0])
    dtype = jnp.result_type(xT.dtype, dyT.dtype)
    if nnz == 0:
        return jnp.zeros((0,), dtype)
    chunk = spmm_chunk_for(xT.shape[-1], nnz, chunk)

    def one_chunk(r, c):
        return (xT[r, :].astype(dtype) * dyT[c, :].astype(dtype)).sum(axis=-1)

    n_chunks = -(-nnz // chunk)
    if n_chunks == 1:
        return one_chunk(rows, cols)
    pad = n_chunks * chunk - nnz
    # padded slots gather slot 0 — their outputs are sliced off below
    r_p = jnp.concatenate([rows, jnp.zeros((pad,), rows.dtype)])
    c_p = jnp.concatenate([cols, jnp.zeros((pad,), cols.dtype)])

    def body(carry, sl):
        return carry, one_chunk(*sl)

    _, dv = jax.lax.scan(
        body, 0, (r_p.reshape(n_chunks, chunk), c_p.reshape(n_chunks, chunk))
    )
    return dv.reshape(-1)[:nnz]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def _init_numpy(
    rng: np.random.Generator, shape, *, fan_in_dense: int, scheme: str
) -> np.ndarray:
    """Weight init. fan_in follows the paper (dense fan-in based scaling)."""
    if scheme == "normal":
        return rng.standard_normal(shape).astype(np.float32) * 0.05
    if scheme == "he_uniform":
        limit = np.sqrt(6.0 / max(1, fan_in_dense))
        return rng.uniform(-limit, limit, size=shape).astype(np.float32)
    if scheme == "xavier":
        limit = np.sqrt(3.0 / max(1, fan_in_dense))
        return rng.uniform(-limit, limit, size=shape).astype(np.float32)
    if scheme == "zeros":
        return np.zeros(shape, np.float32)
    raise ValueError(f"unknown init scheme {scheme!r}")
