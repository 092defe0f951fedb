"""Gradient + edge-case coverage for the element (COO) SpMM path.

The custom-VJP espmm (DESIGN.md §1 "Backward") is compared against the
``to_dense`` dense-matmul oracle across an impl x shape grid: dX, dW, and —
through a two-layer MLP — upstream gradients. Edge cases: nnz == 0,
nnz < chunk, chunk == 1, batch == 1, and non-2D leading dims under vmap.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparsity import (
    ElementTopology,
    spmm_chunk_for,
    SPMM_CHUNK_MIN,
    SPMM_TEMP_BUDGET_ELEMS,
)
from repro.core.topology import element_device_arrays
from repro.kernels import ops

jax.config.update("jax_platform_name", "cpu")

IMPLS = ("custom", "segment", "scatter")

# (in_dim, out_dim, epsilon, batch, chunk)
SHAPES = [
    (96, 72, 9, 11, None),     # generic rectangular
    (50, 40, 5, 1, 7),         # batch == 1, several chunks
    (33, 77, 3, 4, 1),         # chunk == 1 (one connection per scan step)
    (64, 64, 6, 8, 10_000),    # nnz < chunk (single-chunk fast path)
    (128, 16, 2, 3, 13),       # wide-in / narrow-out, ragged last chunk
]


def element_case(in_dim, out_dim, epsilon, batch, seed=0):
    rng = np.random.default_rng(seed)
    topo = ElementTopology.erdos_renyi(in_dim, out_dim, epsilon, rng)
    vals = topo.init_values(rng)
    x = jnp.asarray(rng.standard_normal((batch, in_dim)), jnp.float32)
    co = jnp.asarray(rng.standard_normal((batch, out_dim)), jnp.float32)
    return topo, vals, x, co


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("shape", SHAPES)
def test_value_and_grad_matches_dense_oracle(impl, shape):
    in_dim, out_dim, epsilon, batch, chunk = shape
    topo, vals, x, co = element_case(in_dim, out_dim, epsilon, batch)
    t = topo.device_arrays()

    def f(x, v):
        y = ops.espmm(x, v, t, out_dim, impl=impl, chunk=chunk)
        return (y * co).sum()

    def f_ref(x, v):
        return ((x @ topo.to_dense(v)) * co).sum()

    loss, (gx, gv) = jax.value_and_grad(f, argnums=(0, 1))(x, vals)
    loss_ref, (gx_ref, gv_ref) = jax.value_and_grad(f_ref, argnums=(0, 1))(
        x, vals
    )
    np.testing.assert_allclose(float(loss), float(loss_ref), rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(gx), np.asarray(gx_ref), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(gv), np.asarray(gv_ref), rtol=1e-4, atol=1e-5
    )


@pytest.mark.parametrize("impl", IMPLS)
def test_two_layer_mlp_upstream_grads(impl):
    """Gradients flowing *through* an espmm layer (dX feeding the previous
    layer's dW) must match the dense oracle — the upstream-correctness check
    for the hand-derived dX pass."""
    rng = np.random.default_rng(3)
    t1 = ElementTopology.erdos_renyi(48, 32, 6, rng)
    t2 = ElementTopology.erdos_renyi(32, 10, 4, rng)
    v1, v2 = t1.init_values(rng), t2.init_values(rng)
    a1, a2 = t1.device_arrays(), t2.device_arrays()
    x = jnp.asarray(rng.standard_normal((9, 48)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, size=9), jnp.int32)

    def loss(v1, v2, spmm):
        h = jax.nn.relu(spmm(x, v1, 0))
        logits = spmm(h, v2, 1)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    def spmm_impl(h, v, layer):
        t, out_dim = ((a1, 32), (a2, 10))[layer]
        return ops.espmm(h, v, t, out_dim, impl=impl, chunk=11)

    def spmm_ref(h, v, layer):
        t = (t1, t2)[layer]
        return h @ t.to_dense(v)

    g1, g2 = jax.grad(loss, argnums=(0, 1))(v1, v2, spmm_impl)
    g1_ref, g2_ref = jax.grad(loss, argnums=(0, 1))(v1, v2, spmm_ref)
    np.testing.assert_allclose(
        np.asarray(g1), np.asarray(g1_ref), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(g2), np.asarray(g2_ref), rtol=1e-4, atol=1e-5
    )


# ---------------------------------------------------------------------------
# edge cases
# ---------------------------------------------------------------------------


def empty_topology(in_dim=8, out_dim=6):
    z = np.zeros(0, np.int32)
    return ElementTopology(in_dim, out_dim, z, z)


@pytest.mark.parametrize("impl", IMPLS)
def test_nnz_zero_forward_and_grad(impl):
    topo = empty_topology()
    t = topo.device_arrays()
    vals = jnp.zeros((0,), jnp.float32)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((3, 8)), jnp.float32)
    y = ops.espmm(x, vals, t, 6, impl=impl)
    assert y.shape == (3, 6)
    np.testing.assert_array_equal(np.asarray(y), 0.0)
    gx, gv = jax.grad(
        lambda x, v: ops.espmm(x, v, t, 6, impl=impl).sum(), argnums=(0, 1)
    )(x, vals)
    assert gv.shape == (0,)
    np.testing.assert_array_equal(np.asarray(gx), 0.0)


@pytest.mark.parametrize("impl", ("custom", "segment"))
def test_vmap_leading_dims_match_flat(impl):
    rng = np.random.default_rng(5)
    topo = ElementTopology.erdos_renyi(40, 30, 4, rng)
    t = topo.device_arrays()
    vals = topo.init_values(rng)
    xb = jnp.asarray(rng.standard_normal((5, 7, 40)), jnp.float32)
    y_vmap = jax.vmap(lambda xx: ops.espmm(xx, vals, t, 30, impl=impl))(xb)
    y_lead = ops.espmm(xb, vals, t, 30, impl=impl)  # 3-D leading dims direct
    y_flat = ops.espmm(xb.reshape(35, 40), vals, t, 30, impl=impl)
    assert y_vmap.shape == y_lead.shape == (5, 7, 30)
    np.testing.assert_allclose(
        np.asarray(y_vmap.reshape(35, 30)), np.asarray(y_flat), rtol=1e-5,
        atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(y_lead), np.asarray(y_vmap), rtol=1e-5, atol=1e-6
    )
    # grads under vmap
    gv = jax.grad(
        lambda v: jax.vmap(lambda xx: ops.espmm(xx, v, t, 30, impl=impl))(
            xb
        ).sum()
    )(vals)
    gv_ref = jax.grad(
        lambda v: (xb.reshape(35, 40) @ topo.to_dense(v)).sum()
    )(vals)
    np.testing.assert_allclose(
        np.asarray(gv), np.asarray(gv_ref), rtol=1e-4, atol=1e-5
    )


def test_spmm_chunk_for_policy():
    # batch-aware: fixed temp budget, floor applied, clamped to nnz
    assert spmm_chunk_for(256, 10**9) == SPMM_TEMP_BUDGET_ELEMS // 256
    assert spmm_chunk_for(10**8, 10**9) == SPMM_CHUNK_MIN
    assert spmm_chunk_for(1, 100) == 100  # clamped to nnz
    assert spmm_chunk_for(256, 100, 7) == 7  # explicit chunk honored
    assert spmm_chunk_for(256, 3, 7) == 3
    assert spmm_chunk_for(256, 0) == 1


# ---------------------------------------------------------------------------
# dual-order topology invariants
# ---------------------------------------------------------------------------


def test_dual_order_arrays_host():
    rng = np.random.default_rng(6)
    topo = ElementTopology.erdos_renyi(60, 45, 5, rng)
    t = topo.device_arrays()
    rows, cols = np.asarray(t.rows), np.asarray(t.cols)
    rows_r, cols_r = np.asarray(t.rows_r), np.asarray(t.cols_r)
    perm_r = np.asarray(t.perm_r)
    # canonical: cols non-decreasing; dual: rows_r non-decreasing
    assert (np.diff(cols) >= 0).all()
    assert (np.diff(rows_r) >= 0).all()
    # perm_r maps row-ordered slots back to canonical slots
    np.testing.assert_array_equal(rows[perm_r], rows_r)
    np.testing.assert_array_equal(cols[perm_r], cols_r)
    # boundary flags
    first_col, first_row = np.asarray(t.first_col), np.asarray(t.first_row)
    assert first_col[0] == 1 and first_row[0] == 1
    np.testing.assert_array_equal(
        first_col[1:], (cols[1:] != cols[:-1]).astype(np.int32)
    )
    np.testing.assert_array_equal(
        first_row[1:], (rows_r[1:] != rows_r[:-1]).astype(np.int32)
    )


@pytest.mark.parametrize("nnz_empty", [False, True])
def test_element_device_arrays_matches_host(nnz_empty):
    rng = np.random.default_rng(7)
    if nnz_empty:
        topo = empty_topology(20, 15)
    else:
        topo = ElementTopology.erdos_renyi(20, 15, 4, rng)
    host = topo.device_arrays()
    dev = element_device_arrays(
        jnp.asarray(topo.rows), jnp.asarray(topo.cols),
        in_dim=topo.in_dim, out_dim=topo.out_dim,
    )
    for name, h, d in zip(host._fields, host, dev):
        np.testing.assert_array_equal(
            np.asarray(h), np.asarray(d), err_msg=name
        )


def test_element_device_arrays_int32_guard():
    with pytest.raises(ValueError):
        element_device_arrays(
            jnp.zeros(1, jnp.int32), jnp.zeros(1, jnp.int32),
            in_dim=2**16, out_dim=2**16,
        )


def test_espmm_auto_dispatch_and_unknown_impl():
    rng = np.random.default_rng(8)
    topo = ElementTopology.erdos_renyi(32, 24, 3, rng)
    t = topo.device_arrays()
    vals = topo.init_values(rng)
    x = jnp.asarray(rng.standard_normal((4, 32)), jnp.float32)
    y_auto = ops.espmm(x, vals, t, 24)
    y_cus = ops.espmm(x, vals, t, 24, impl="custom")
    np.testing.assert_allclose(
        np.asarray(y_auto), np.asarray(y_cus), rtol=1e-5, atol=1e-6
    )
    with pytest.raises(ValueError):
        ops.espmm(x, vals, t, 24, impl="nope")


# ---------------------------------------------------------------------------
# pass scopes in the compiled train step (what a profiler trace reads)
# ---------------------------------------------------------------------------

PASS_SCOPES = ("espmm_fwd", "espmm_dx", "espmm_dw")


def _segment_hlo(make):
    """Compiled HLO text of a tiny custom-VJP epoch segment built by
    ``make(config, opt)``."""
    from repro.models.mlp import SparseMLP, SparseMLPConfig
    from repro.optim.sgd import MomentumSGD

    cfg = SparseMLPConfig(
        layer_dims=(48, 64, 32, 4), epsilon=6, activation="all_relu",
        alpha=0.6, dropout=0.3, impl="element", element_impl="custom",
        spmm_chunk=64,
    )
    model, opt = SparseMLP(cfg, seed=0), MomentumSGD(momentum=0.9)
    params = model.params()
    args = (params, opt.init(params), model.topo_arrays(),
            jnp.zeros((64, 48)), jnp.zeros((64,), jnp.int32),
            jnp.zeros((2, 16), jnp.int32), jnp.zeros((2,), jnp.float32),
            jax.random.PRNGKey(0))
    return make(cfg, opt).lower(*args).compile().as_text()


def test_segment_ops_carry_the_pass_scopes():
    """Each espmm pass names at least one op of the compiled segment in its
    ``op_name`` metadata, as the last pass name on the op's path."""
    import re

    from repro.train.trainer import make_segment_fn

    scopes = {}
    for path in re.findall(r'op_name="([^"]*)"', _segment_hlo(make_segment_fn)):
        found = re.findall(r"\b(espmm_(?:fwd|dx|dw))\b", path)
        if found:
            scopes[found[-1]] = scopes.get(found[-1], 0) + 1
    assert set(scopes) == set(PASS_SCOPES) and min(scopes.values()) >= 1


def test_pass_scopes_leave_the_program_unchanged(monkeypatch):
    """The scopes change op metadata only: without them the compiled
    segment is the same op for op."""
    import contextlib
    import re

    from repro.train.trainer import make_segment_program

    def ops_only(hlo):
        lines = [l for l in hlo.splitlines()
                 if l.startswith(("%", "ENTRY", " ", "}"))]
        return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines))

    def fresh(cfg, opt):
        return jax.jit(make_segment_program(cfg, opt))

    scoped = _segment_hlo(fresh)
    assert "espmm_dw" in scoped
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = _segment_hlo(fresh)
    assert "espmm_dw" not in plain
    assert ops_only(scoped) == ops_only(plain)
