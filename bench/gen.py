"""Seeded inputs of a cell: the synthetic dataset and the initial sparse
model, both made from ``--seed`` and nothing else.

The generators are the benchmark's own (written in ``jax.numpy`` so the
data is made on the device in one jitted call); nothing here imports the
program, so a change to the program's data code cannot move the yardstick.

* ``image``: class templates in a smooth low-rank basis plus pixel noise
  (the shape of CIFAR-10: 3,072 features, 10 classes).
* ``classification``: the Guyon (2003) generator the paper uses for its
  artificial data: gaussian clusters on hypercube vertices in an
  informative subspace, redundant features as random linear mixtures of
  them, the rest noise, features shuffled, a share of labels flipped (the
  shape of Leukemia: 54,675 features, 18 classes).

Rows are drawn independently, each from its own key, so the data is made
block by block on the device. Both kinds standardise every feature by the
training split's mean and standard deviation (paper section 5.4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed31(seed: int) -> int:
    """A 31-bit seed for the program's trainers and JAX keys, mixed from the
    benchmark's ``--seed`` (any whole number, also beyond 32 bits)."""
    word = np.random.SeedSequence(abs(int(seed))).generate_state(1)[0]
    return int(word) >> 1


def _block_rows(n: int, cap: int = 256) -> int:
    """The largest divisor of ``n`` not above ``cap``: rows per block."""
    return max(d for d in range(1, min(n, cap) + 1) if n % d == 0)


def _image_rows(key, ds):
    """Row generator of the ``image`` kind: a row's label and pixels depend
    only on the key and the row's index."""
    k_basis, k_coef, k_rows = jax.random.split(key, 3)
    rank, n_feat, n_cls = ds["template_rank"], ds["features"], ds["classes"]
    basis = jnp.cumsum(jax.random.normal(k_basis, (rank, n_feat)), axis=1)
    basis = basis / (jnp.linalg.norm(basis, axis=1, keepdims=True) + 1e-8)
    coef = jax.random.normal(k_coef, (n_cls, rank)) * 3.0

    def row(i):
        k1, k2, k3 = jax.random.split(jax.random.fold_in(k_rows, i), 3)
        y = jax.random.randint(k1, (), 0, n_cls, jnp.int32)
        mix = coef[y] + 0.4 * jax.random.normal(k2, (rank,))
        x = jnp.matmul(mix, basis, precision="highest")
        return x + ds["noise"] * jax.random.normal(k3, (n_feat,)), y

    return row


def _classification_rows(key, ds):
    """Row generator of the ``classification`` kind (Guyon): a row's cluster,
    features and label depend only on the key and the row's index."""
    k = jax.random.split(key, 6)
    n_cls, n_inf, n_red = ds["classes"], ds["informative"], ds["redundant"]
    n_feat = ds["features"]
    n_clusters = n_cls * ds["clusters_per_class"]
    sign = jax.random.bernoulli(k[0], 0.5, (n_clusters, n_inf))
    centroids = jnp.where(sign, 1.0, -1.0) * ds["class_sep"] * (
        1.0 + 0.2 * jax.random.uniform(k[1], (n_clusters, 1)))
    a = jax.random.normal(k[2], (n_clusters, n_inf, n_inf))
    mix = jax.random.normal(k[3], (n_inf, n_red))
    fperm = jax.random.permutation(k[4], n_feat)

    def row(i):
        r = jax.random.split(jax.random.fold_in(k[5], i), 5)
        c = jax.random.randint(r[0], (), 0, n_clusters, jnp.int32)
        x_inf = jnp.matmul(jax.random.normal(r[1], (n_inf,)), a[c],
                           precision="highest") * 0.5 + centroids[c]
        x_red = jnp.matmul(x_inf, mix, precision="highest")
        noise = jax.random.normal(r[2], (n_feat - n_inf - n_red,))
        x = jnp.concatenate([x_inf, x_red, noise])[fperm]
        flip = jax.random.uniform(r[3]) < ds["flip_y"]
        y = jnp.where(flip, jax.random.randint(r[4], (), 0, n_cls, jnp.int32),
                      c % n_cls)
        return x, y

    return row


ROWS = {"image": _image_rows, "classification": _classification_rows}


@functools.partial(jax.jit, static_argnames=("dskey",))
def _dataset(key, *, dskey):
    ds = dict(dskey)
    row = ROWS[ds["kind"]](key, ds)
    n_train, n_test, n_feat = ds["train"], ds["test"], ds["features"]

    def blocks(first, n, fn):
        b = _block_rows(n)
        idx = first + jnp.arange(n, dtype=jnp.int32).reshape(n // b, b)
        return jax.lax.map(lambda i: fn(*jax.vmap(row)(i)), idx)

    # generated block by block, so no temporary is larger than a block:
    # the device peak of set-up stays below the training's own footprint
    mean = blocks(0, n_train, lambda x, y: x.sum(0)).sum(0) / n_train
    var = blocks(0, n_train, lambda x, y: ((x - mean) ** 2).sum(0)).sum(0) / n_train
    sd = jnp.sqrt(var) + 1e-8

    def split(first, n):
        # written block by block into the output: no stacked copy to reshape
        b = _block_rows(n)

        def body(i, acc):
            x, y = acc
            xb, yb = jax.vmap(row)(first + i * b + jnp.arange(b, dtype=jnp.int32))
            x = jax.lax.dynamic_update_slice(x, (xb - mean) / sd, (i * b, 0))
            return x, jax.lax.dynamic_update_slice(y, yb, (i * b,))

        return jax.lax.fori_loop(0, n // b, body, (
            jnp.zeros((n, n_feat), jnp.float32), jnp.zeros((n,), jnp.int32)))

    return (*split(0, n_train), *split(n_train, n_test))


def make_dataset(ds: dict, seed: int):
    """``(x_train, y_train, x_test, y_test)`` on the default device, every
    feature standardised by the training split's mean and deviation."""
    if ds["kind"] not in ROWS:
        raise ValueError(f"unknown dataset kind {ds['kind']!r}")
    return _dataset(jax.random.PRNGKey(seed31(seed)),
                    dskey=tuple(sorted(ds.items())))


def erdos_renyi_nnz(epsilon: float, n_in: int, n_out: int) -> int:
    """SET's Erdos-Renyi connection count eps * (n_in + n_out), at most
    every position of the layer."""
    return min(n_in * n_out, int(round(epsilon * (n_in + n_out))))


def make_topology(cfg: dict, seed: int):
    """Per layer, ``nnz`` distinct positions drawn uniformly, as int32
    ``(rows, cols)`` sorted by (col, row)."""
    rng = np.random.default_rng([abs(int(seed)), 1])
    dims = cfg["layer_dims"]
    out = []
    for l in range(len(dims) - 1):
        n_in, n_out = dims[l], dims[l + 1]
        nnz = erdos_renyi_nnz(cfg["epsilon"], n_in, n_out)
        flat = rng.choice(n_in * n_out, size=nnz, replace=False).astype(np.int64)
        rows, cols = flat // n_out, flat % n_out
        order = np.lexsort((rows, cols))
        out.append((rows[order].astype(np.int32), cols[order].astype(np.int32)))
    return out


@functools.partial(jax.jit, static_argnames=("sizes", "fan_ins", "outs", "init"))
def _values(key, *, sizes, fan_ins, outs, init):
    keys = jax.random.split(key, len(sizes))
    vals = []
    for k, n, fan_in in zip(keys, sizes, fan_ins):
        if init == "he_uniform":
            lim = float(np.sqrt(6.0 / fan_in))
            vals.append(jax.random.uniform(k, (n,), jnp.float32, -lim, lim))
        elif init == "normal":
            vals.append(jax.random.normal(k, (n,), jnp.float32) * 0.05)
        else:
            raise ValueError(f"unknown init {init!r}")
    return tuple(vals), tuple(jnp.zeros((o,), jnp.float32) for o in outs)


def make_values(cfg: dict, topology, seed: int):
    """Initial connection values and biases of every layer in one jitted
    call: ``he_uniform`` is U(+-sqrt(6 / fan_in)) with the dense fan-in,
    ``normal`` is N(0, 0.05^2); biases start at zero."""
    dims = cfg["layer_dims"]
    return _values(
        jax.random.PRNGKey(seed31(seed) ^ 0x5EED),
        sizes=tuple(int(r.shape[0]) for r, _ in topology),
        fan_ins=tuple(dims[:-1]), outs=tuple(dims[1:]), init=cfg["init"],
    )
