"""The one-hot MXU sorted segment sum (``kernels/sorted_segment_sum.py``),
run through Pallas' interpreter on the CPU against ``jax.ops.segment_sum``,
and the dispatch in ``core.sparsity.coo_matmul_T`` that chooses it.

A sum is held to f32 rounding: a few f32 ulps of the sum of its
contributions' magnitudes. A contribution rounded to bfloat16 on its way
into the MXU would miss that by about 2**-9 of its own size.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import sparsity
from repro.kernels import sorted_segment_sum as sss

jax.config.update("jax_platform_name", "cpu")

STEP = sss.STEP_SLOTS
F32_ULPS = 8 * np.finfo(np.float32).eps


def _slots(ids, n_slots, n_segments):
    """Sorted ids padded with ``n_segments`` up to ``n_slots``."""
    ids = np.sort(np.asarray(ids, np.int32))
    return np.concatenate(
        [ids, np.full(n_slots - ids.size, n_segments, np.int32)]
    )


def _case(name, rng):
    """(batch, n_segments, ids over a multiple of STEP slots)."""
    if name.startswith("batch"):
        batch = int(name[5:])
        # 20,000 segments: batch 187 takes four 48-row batch tiles
        n_seg = 20_000 if batch == 187 else 300
        return batch, n_seg, _slots(rng.integers(0, n_seg, 2 * STEP), 2 * STEP, n_seg)
    if name == "empty_segments":
        # every third segment holds slots, and none past 200 of 1000
        ids = 3 * rng.integers(0, 67, STEP)
        return 5, 1000, _slots(ids, STEP, 1000)
    if name == "long_segment":
        # segment 7 spans several grid steps
        ids = np.concatenate([rng.integers(0, 7, 100), np.full(3 * STEP, 7),
                              rng.integers(8, 50, 200)])
        return 5, 50, _slots(ids, 4 * STEP, 50)
    if name == "wide_block":
        # each 128-slot sub-block spans about 400 segments: the window loop
        ids = np.arange(STEP) * 3 + rng.integers(0, 3, STEP)
        return 8, 3 * STEP + 2, _slots(ids, STEP, 3 * STEP + 2)
    if name == "padding":
        # 700 real slots, then padding through the rest of two grid steps
        return 5, 256, _slots(rng.integers(0, 256, 700), 2 * STEP, 256)
    raise ValueError(name)


def _within_f32_rounding(got, contrib, ids, n_segments):
    """``got`` (n_segments, B) against the float64 sums of ``contrib`` (S, B)."""
    contrib = np.asarray(contrib, np.float64)
    real = ids < n_segments
    ref = np.zeros((n_segments, contrib.shape[1]))
    mag = np.zeros_like(ref)
    np.add.at(ref, ids[real], contrib[real])
    np.add.at(mag, ids[real], np.abs(contrib[real]))
    xla = jax.ops.segment_sum(
        jnp.asarray(contrib, jnp.float32), jnp.asarray(ids),
        num_segments=n_segments, indices_are_sorted=True,
    )
    tol = F32_ULPS * mag + 1e-30
    assert np.all(np.abs(np.asarray(xla, np.float64) - ref) <= tol)
    assert np.all(np.abs(np.asarray(got, np.float64) - ref) <= tol)


def _carry_in(rng, batch):
    """Through ``coo_matmul_T``'s kernel path: several chunks, a ragged last
    chunk and an accumulator carried in, against its XLA path (batch 5
    gathers columns, batch 128 rows)."""
    src_dim, n_seg, nnz, chunk = 40, 300, 4_000, 1_500
    srcT = jnp.asarray(rng.standard_normal((src_dim, batch)), jnp.float32)
    values = jnp.asarray(rng.standard_normal(nnz), jnp.float32)
    g = jnp.asarray(rng.integers(0, src_dim, nnz), jnp.int32)
    s = jnp.asarray(np.sort(rng.integers(0, n_seg, nnz)), jnp.int32)
    acc = jnp.asarray(rng.standard_normal((n_seg, batch)), jnp.float32)
    got = sparsity._coo_matmul_T_pallas(
        srcT, values, g, s, acc, n_segments=n_seg, chunk=chunk, interpret=True
    )
    want = sparsity._coo_matmul_T_xla(
        srcT, values, g, s, acc, n_segments=n_seg, chunk=chunk
    )
    contrib = np.concatenate(
        [np.asarray(srcT)[np.asarray(g)] * np.asarray(values)[:, None],
         np.asarray(acc)]
    )
    ids = np.concatenate([np.asarray(s), np.arange(n_seg, dtype=np.int32)])
    order = np.argsort(ids, kind="stable")
    _within_f32_rounding(got, contrib[order], ids[order], n_seg)
    _within_f32_rounding(want, contrib[order], ids[order], n_seg)


CASES = ["batch1", "batch5", "batch128", "batch187", "empty_segments",
         "long_segment", "wide_block", "padding", "carry_in", "carry_in128"]


@pytest.mark.parametrize("case", CASES)
def test_matches_segment_sum_to_f32_rounding(case):
    rng = np.random.default_rng(CASES.index(case))
    if case.startswith("carry_in"):
        return _carry_in(rng, int(case[8:] or 5))
    batch, n_seg, ids = _case(case, rng)
    x = rng.standard_normal((ids.size, batch)).astype(np.float32)
    values = rng.standard_normal(ids.size).astype(np.float32)
    values[ids == n_seg] = 0.0  # padding slots carry value 0
    contrib = x * values[:, None]
    out = sss.sorted_segment_sum_T(
        jnp.asarray(x.T), jnp.asarray(values), jnp.asarray(ids),
        n_segments=n_seg, interpret=True,
    )
    assert out.shape == (batch, -(-n_seg // sss.LANES) * sss.LANES)
    _within_f32_rounding(np.asarray(out)[:, :n_seg].T, contrib, ids, n_seg)


def test_batch_tile_follows_the_vmem_budget():
    assert sss.batch_tile(27_500, 5) == 8
    assert sss.batch_tile(4_000, 512) == 256
    assert sss.batch_tile(27_500, 512) == 32
    assert sss.batch_tile(500_000, 5) == 0  # XL widths keep segment_sum
    assert sss.fits(27_500, 5, 419_430, jnp.float32)
    assert not sss.fits(27_500, 5, 419_430, jnp.bfloat16)
    assert not sss.fits(500_000, 5, 419_430, jnp.float32)
    assert not sss.fits(27_500, 5, STEP - 1, jnp.float32)
    assert sss.fits(4_000, 512, 4_096, jnp.float32)  # cifar10 evaluation
    assert not sss.fits(27_500, 512, 4_096, jnp.float32)  # leukemia's


@pytest.mark.parametrize(
    "backend,n_segments,nnz,path",
    [("tpu", 300, 2 * STEP, "pallas"), ("cpu", 300, 2 * STEP, "xla"),
     ("tpu", 500_000, 2 * STEP, "xla"), ("tpu", 300, STEP // 2, "xla")],
)
def test_segment_reduce_counter_names_the_path(
    monkeypatch, backend, n_segments, nnz, path
):
    """One count per traced call, labelled by the path the default backend
    runs: the kernel on the TPU where the shape fits it, else XLA."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    reg = obs.default_registry()
    before = {p: reg.counter("espmm.segment_reduce", path=p).value
              for p in ("pallas", "xla")}
    batch = 5
    jax.make_jaxpr(
        lambda srcT, v, g, s: sparsity.coo_matmul_T(srcT, v, g, s, n_segments)
    )(
        jnp.zeros((40, batch), jnp.float32), jnp.zeros((nnz,), jnp.float32),
        jnp.zeros((nnz,), jnp.int32), jnp.zeros((nnz,), jnp.int32),
    )
    after = {p: reg.counter("espmm.segment_reduce", path=p).value
             for p in ("pallas", "xla")}
    other = "xla" if path == "pallas" else "pallas"
    assert after[path] == before[path] + 1
    assert after[other] == before[other]
