"""Plain reference of SET-MLP training (arXiv:2102.01732), written from the
paper and independent of the program under test.

* Layer l: ``z = x @ W_l + b_l`` with ``W_l`` held only as its connections
  ``(rows, cols, values)``: every connection adds ``x[:, row] * value`` to
  output ``col`` (a gather, a product and a segment sum; no kernel, no
  assumption on the connections' order). Autodiff gives the gradients.
* Connection blocks: where a layer's gathered (connections x rows) slab
  would exceed ``block_elems`` elements, its connections are cut into equal
  blocks of ``block_elems // rows``, the last padded with value 0 and an
  output index past the layer (dropped); the blocks' segment sums are added
  into the (out, rows) result in block order. Each block's body is
  rematerialised in the backward pass, so autodiff keeps only the indices,
  values and the result, never every block's slab. A layer whose slab fits
  is one segment sum, as if unblocked.
* All-ReLU (paper Eq. 3) on hidden layer l (1-based): ``z`` where ``z > 0``,
  else ``-alpha * z`` for even l and ``alpha * z`` for odd l. The output
  layer is linear.
* Dropout after each hidden activation: keep with probability ``1 - p``,
  scale by ``1 / (1 - p)``. The masks follow the seed's key stream: one key
  per step split off the run's key, and per hidden layer one key split off
  the step's key, drawn over the (batch, features) activation.
* Cross-entropy on the logits, mean over the batch.
* Momentum SGD (paper Eq. 1) with L2 weight decay on every parameter:
  ``v <- mu v - lr (g + wd p); p <- p + v``.
* SET pruning (paper Algorithm 2): per layer, the fraction zeta of the
  smallest positive values and of the largest negative values, by count
  ``floor(zeta * n)`` within each sign, and every exact zero.

``dtype`` is the precision of the forward and backward arithmetic; the
parameters and the optimizer stay float32. Called with ``bfloat16`` this is
the control that a float32 configuration must fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _all_relu(z, layer, alpha):
    slope = alpha if layer % 2 == 1 else -alpha
    return jnp.where(z > 0, z, slope * z)


def block_plan(nnz, rows, block_elems):
    """``(block, blocks)``: connections per block and the number of blocks
    of a layer with ``nnz`` connections over ``rows`` rows, so that a block's
    slab holds at most ``block_elems`` elements (one block where it fits)."""
    block = max(1, block_elems // max(1, rows))
    if block >= nnz:
        return nnz, 1
    return block, -(-nnz // block)


def _layer(hT, rows, cols, vals, n_out, block_elems):
    """``zT`` (n_out, rows): every connection adds ``hT[row] * value`` to
    output ``col``, summed over the layer's connection blocks in order."""
    block, blocks = block_plan(rows.shape[0], hT.shape[1], block_elems)
    if blocks == 1:
        return jax.ops.segment_sum(hT[rows] * vals[:, None], cols,
                                   num_segments=n_out)
    pad = blocks * block - rows.shape[0]
    rows = jnp.pad(rows, (0, pad)).reshape(blocks, block)
    cols = jnp.pad(cols, (0, pad), constant_values=n_out).reshape(blocks, block)
    vals = jnp.pad(vals, (0, pad)).reshape(blocks, block)

    @jax.checkpoint
    def body(zT, blk):
        r, c, v = blk
        return zT + jax.ops.segment_sum(hT[r] * v[:, None], c,
                                        num_segments=n_out), None

    zT0 = jnp.zeros((n_out, hT.shape[1]), hT.dtype)
    return jax.lax.scan(body, zT0, (rows, cols, vals))[0]


def forward(params, topo, x, cfg, key, dtype, block_elems):
    """Logits (batch, classes) in float32. ``key=None`` turns dropout off;
    ``block_elems`` bounds a connection block's gathered slab."""
    dims = cfg["layer_dims"]
    n_layers = len(dims) - 1
    keep = 1.0 - cfg["dropout"]
    hT = x.T.astype(dtype)
    for l in range(n_layers):
        rows, cols = topo[l]
        vals = params["values"][l].astype(dtype)
        zT = _layer(hT, rows, cols, vals, dims[l + 1], block_elems)
        zT = zT + params["biases"][l].astype(dtype)[:, None]
        if l == n_layers - 1:
            return zT.T.astype(jnp.float32)
        hT = _all_relu(zT, l + 1, cfg["alpha"])
        if key is not None and cfg["dropout"] > 0:
            key, sub = jax.random.split(key)
            mask = jax.random.bernoulli(sub, keep, hT.shape[::-1]).T
            hT = jnp.where(mask, hT / keep, 0.0).astype(dtype)


def loss(params, topo, x, y, cfg, key, dtype, block_elems):
    logp = jax.nn.log_softmax(
        forward(params, topo, x, cfg, key, dtype, block_elems), axis=-1)
    return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()


def _step(params, vel, topo, x, y, key, lr, cfg, dtype, block_elems):
    value, grads = jax.value_and_grad(loss)(params, topo, x, y, cfg, key, dtype,
                                            block_elems)
    mu, wd = cfg["momentum"], cfg["weight_decay"]
    vel = jax.tree.map(lambda v, g, p: mu * v - lr * (g + wd * p), vel, grads, params)
    params = jax.tree.map(lambda p, v: p + v, params, vel)
    norms = jnp.stack([jnp.linalg.norm(g) for g in jax.tree.leaves(grads)])
    return params, vel, value, norms


def _half(x, y):
    b = x.shape[0] // 2
    return x[:b], y[:b]


@functools.partial(jax.jit, static_argnames=("cfgkey", "dtype", "half",
                                             "block_elems"))
def _follow_sequential(params, topo, x_all, y_all, perm, key, lr, *, cfgkey,
                       dtype, half, block_elems):
    cfg = dict(cfgkey)
    vel = jax.tree.map(jnp.zeros_like, params)

    def body(carry, idx):
        p, v, k = carry
        k, sub = jax.random.split(k)
        x, y = x_all[idx], y_all[idx]
        if half:
            x, y = _half(x, y)
        p, v, value, norms = _step(p, v, topo, x, y, sub, lr, cfg, dtype,
                                   block_elems)
        return (p, v, k), (value, norms)

    (params, vel, _), (losses, norms) = jax.lax.scan(body, (params, vel, key), perm)
    return params, vel, losses, norms[0]


@functools.partial(jax.jit, static_argnames=("cfgkey", "dtype", "half", "exchange",
                                             "block_elems"))
def _follow_local_sgd(params, topo, x_all, y_all, idx, lrs, valid, keys, *,
                      cfgkey, dtype, half, exchange, block_elems):
    cfg = dict(cfgkey)
    vel = jax.tree.map(jnp.zeros_like, params)

    def worker(p, v, idx_h, key, lrs_h, valid_h):
        def body(carry, inp):
            p, v, k, total = carry
            i, lr, ok = inp
            k, sub = jax.random.split(k)
            x, y = x_all[i], y_all[i]
            if half:
                x, y = _half(x, y)
            p2, v2, value, norms = _step(p, v, topo, x, y, sub, lr, cfg, dtype,
                                         block_elems)
            keep = ok > 0
            p = jax.tree.map(lambda a, b: jnp.where(keep, a, b), p2, p)
            v = jax.tree.map(lambda a, b: jnp.where(keep, a, b), v2, v)
            return (p, v, k, total + value * ok), norms

        (p, v, _, total), norms = jax.lax.scan(
            body, (p, v, key, jnp.float32(0)), (idx_h, lrs_h, valid_h))
        return p, v, total, norms[0]

    def round_(carry, inp):
        p, v = carry
        idx_r, lrs_r, valid_r, keys_r = inp
        ps, vs, totals, norms = jax.vmap(
            worker, in_axes=(None, None, 0, 0, None, None))(
                p, v, idx_r, keys_r, lrs_r, valid_r)
        # the average over workers after every round; without the exchange
        # each worker would go on alone and worker 0's copy is the result
        pick = (lambda a: a.mean(axis=0)) if exchange else (lambda a: a[0])
        return (jax.tree.map(pick, ps), jax.tree.map(pick, vs)), (
            totals.sum(), norms[0])

    (params, vel), (loss_sums, norms) = jax.lax.scan(
        round_, (params, vel), (idx, lrs, valid, keys))
    return params, vel, loss_sums, norms[0]


def follow_sequential(cfg, params, topo, x_all, y_all, perm, key, lr, *,
                      block_elems, dtype=jnp.float32, half=False):
    """One epoch of the sequential trainer: ``perm`` is (steps, batch) row
    indices, ``key`` the run's key. Returns ``(params, velocity, losses,
    first_grad_norms)`` with the first step's gradient norm per leaf."""
    return _follow_sequential(
        params, topo, x_all, y_all, jnp.asarray(perm), key, jnp.float32(lr),
        cfgkey=_cfgkey(cfg), dtype=jnp.dtype(dtype), half=half,
        block_elems=block_elems)


def follow_local_sgd(cfg, params, topo, x_all, y_all, idx, lrs, valid, keys, *,
                     block_elems, dtype=jnp.float32, half=False, exchange=True):
    """One phase-1 epoch of local SGD with model averaging: ``idx`` is
    (rounds, workers, local steps, batch) row indices, ``lrs``/``valid``
    (rounds, local steps) step sizes and 0/1 weights of padded steps,
    ``keys`` (rounds, workers) the workers' keys. Every round each worker
    starts from the average, takes its local steps, and parameters and
    velocity are averaged again. Returns ``(params, velocity, loss_sums,
    first_grad_norms)`` with ``loss_sums`` per round over all workers."""
    return _follow_local_sgd(
        params, topo, x_all, y_all, jnp.asarray(idx), jnp.asarray(lrs),
        jnp.asarray(valid), keys, cfgkey=_cfgkey(cfg), dtype=jnp.dtype(dtype),
        half=half, exchange=exchange, block_elems=block_elems)


def _cfgkey(cfg):
    return tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                 for k in ("layer_dims", "dropout", "alpha", "momentum",
                           "weight_decay"))


def set_keep(values, zeta):
    """Mask of the connections SET keeps: within each sign, all but the
    ``floor(zeta * n)`` values nearest zero; exact zeros are removed."""
    v = np.asarray(values)
    keep = v != 0
    for side in (v > 0, v < 0):
        idx = np.flatnonzero(side)
        k = int(np.floor(zeta * idx.size))
        drop = idx[np.argsort(np.abs(v[idx]), kind="stable")[:k]]
        keep[drop] = False
    return keep
