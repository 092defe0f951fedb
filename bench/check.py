"""The comparison that decides ``correct``.

The window's own program is the trainer's epoch: one device call that runs
every minibatch step of the epoch, then SET evolution, then evaluation. The
state inside an epoch never leaves the device, so the reference follows the
whole of the job's first epoch (the one set-up ran through the same
trainer object the window then drives) and the numbers compared are those
the epoch leaves at its boundary:

* ``loss``: the epoch's mean training loss, as a gap relative to the
  reference's;
* ``change``: per leaf, the norm of the parameters' change over the epoch,
  on the connections that both the program's and the reference's SET
  evolution keep; the worst leaf's gap of norms (not the norm of the
  difference) over that leaf's reference norm;
* ``velocity``: the same for the optimizer's velocity, the decayed sum of
  the gradients as the optimizer got them;
* ``evolution``: per layer, the connections on which the program's kept set
  and the reference's differ, over the number the reference prunes; the
  worst layer. The program's kept set is read from its state: a connection
  whose position survives and whose velocity is not reset to zero;
* ``nnz``: connections gained or lost by evolution over all layers (exact);
* ``positions``: positions of the evolved topology that repeat within their
  layer or lie outside it, so a regrown connection lands on a free,
  existing position (exact);
* ``logits``: the program's evaluation program, run on the epoch's end
  state over the whole test set in the trainer's own slices, against the
  reference's forward (dropout off) on that state: the widest gap of a
  logit over the largest reference logit. Regrowth draws positions and
  values from the program's key stream, so the state evaluated is the
  program's, and this number holds its evaluation alone;
* ``eval_acc``: test rows by which the accuracy that the trainer recorded
  for the epoch differs from the accuracy of those logits (exact): the
  window's evaluation is that program over that test set.

Leaves whose first-step gradient in the reference is under a thousandth of
the median leaf's move by rounding alone and are left out of ``change`` and
``velocity``; every other leaf is read against its own norm, so a fault
confined to a small leaf, such as a bias, is not diluted by the larger
ones.

The reference is the module that the configuration's ``reference`` names,
a path from the checkout's root (``harness.resolve`` makes it absolute),
loaded by path.
"""
from __future__ import annotations

import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness

ROOT = Path(__file__).resolve().parents[1]
GRAD_FLOOR = 1e-3
# elements of the reference's gathered (connections x rows) slab per
# connection block
BLOCK_ELEMS = 1 << 26
# ``reference_logits`` takes as many rows per call as keep every layer in
# one connection block; where that is under ``WHOLE_ROWS_MIN`` rows, it takes
# ``LOGIT_ROWS`` rows and cuts the layers into blocks. Over 3,000 rows at the
# Table-4 widths on a TPU v5e, 512 rows a call took 7.0 s (64: 20.1 s, 256:
# 7.3 s, 1,024: 9.2 s); all 3,000 in one call took 6.3 s but needs 13.1 GB
# of temporaries beside the data
WHOLE_ROWS_MIN = 64
LOGIT_ROWS = 512
NAMES = ("loss", "change", "velocity", "evolution", "nnz", "positions",
         "logits", "eval_acc")


def load_reference(config):
    """The reference module that ``config["reference"]`` names."""
    return harness.load_module(ROOT / config["reference"])


def reference_state(config, follow, dtype=jnp.float32, half=False, exchange=True):
    """Run the reference over the first epoch; host arrays out."""
    ref = load_reference(config)
    params = jax.tree.map(jnp.asarray, follow["init"])
    topo = tuple((jnp.asarray(r), jnp.asarray(c)) for r, c in follow["topology"])
    if follow["job"] == "sequential":
        p, v, losses, g0 = ref.follow_sequential(
            config, params, topo, follow["x"], follow["y"], follow["perm"],
            follow["key"], follow["lr"], dtype=dtype, half=half,
            block_elems=BLOCK_ELEMS)
        loss = float(np.mean(np.asarray(losses, np.float64)))
    else:
        p, v, loss_sums, g0 = ref.follow_local_sgd(
            config, params, topo, follow["x"], follow["y"], follow["idx"],
            follow["lrs"], follow["valid"], follow["keys"], dtype=dtype,
            half=half, exchange=exchange, block_elems=BLOCK_ELEMS)
        k = follow["idx"].shape[1]
        loss = float(np.sum(np.asarray(loss_sums, np.float64))) / (
            k * float(follow["valid"].sum()))
    p, v = jax.tree.map(np.asarray, (p, v))
    # leaves of a {"biases", "values"} dict flatten biases first
    n = len(p["values"])
    g0 = np.asarray(g0)
    return {
        "values": list(p["values"]), "biases": list(p["biases"]),
        "vel_values": list(v["values"]), "vel_biases": list(v["biases"]),
        "loss": loss, "grad_values": g0[n:], "grad_biases": g0[:n],
        "keep": [ref.set_keep(x, config["zeta"]) for x in p["values"]],
        "nnz_after": [x.size for x in p["values"]],
    }


def bad_positions(rows, cols, n_in, n_out) -> int:
    """Connections outside the ``n_in x n_out`` layer, plus repeats of a
    position already held within it."""
    rows, cols = np.asarray(rows, np.int64), np.asarray(cols, np.int64)
    inside = (rows >= 0) & (rows < n_in) & (cols >= 0) & (cols < n_out)
    keys = cols[inside] * n_in + rows[inside]
    return int((~inside).sum() + keys.size - np.unique(keys).size)


def program_state(captured, follow, dims):
    """The program's epoch-0 end state laid over the initial connection
    slots: values and velocity of every slot whose position survived, and
    which slots it kept. ``dims`` are the layer widths."""
    out = {"values": [], "vel_values": [], "keep": [], "nnz_after": [],
           "biases": list(captured["params"]["biases"]),
           "vel_biases": list(captured["velocity"]["biases"]),
           "loss": captured["loss"], "positions": 0}
    for l, ((r0, c0), (r1, c1)) in enumerate(zip(follow["topology"],
                                                 captured["topology"])):
        out["positions"] += bad_positions(r1, c1, dims[l], dims[l + 1])
        k0 = c0.astype(np.int64) * dims[l] + r0
        k1 = c1.astype(np.int64) * dims[l] + r1
        _, i0, i1 = np.intersect1d(k0, k1, return_indices=True)
        vals = np.full(k0.size, np.nan, np.float32)
        vel = np.full(k0.size, np.nan, np.float32)
        vals[i0] = captured["params"]["values"][l][i1]
        vel[i0] = captured["velocity"]["values"][l][i1]
        keep = np.zeros(k0.size, bool)
        keep[i0] = vel[i0] != 0
        out["values"].append(vals)
        out["vel_values"].append(vel)
        out["keep"].append(keep)
        out["nnz_after"].append(int(k1.size))
    return out


def leaf_gaps(a_leaves, r_leaves, include):
    """Per leaf: the gap of norms over the leaf's reference norm, and that
    norm over the median included leaf's (``None`` for a leaf left out)."""
    norms_a = [float(np.linalg.norm(a.astype(np.float64))) for a in a_leaves]
    norms_r = [float(np.linalg.norm(r.astype(np.float64))) for r in r_leaves]
    med = float(np.median([x for x, ok in zip(norms_r, include) if ok]))
    return [(abs(na - nr) / max(nr, 1e-30), nr / max(med, 1e-30)) if ok else None
            for na, nr, ok in zip(norms_a, norms_r, include)]


def _worst(gaps):
    return max(g[0] for g in gaps if g is not None)


def leaves(state, refst, init):
    """``(include, change, velocity)``: which leaves count, and the leaves
    of the parameters' change and of the velocity of the program (or
    control) ``state`` and of the reference ``refst``, each as a pair of
    lists, on the connections both keep."""
    n = len(refst["values"])
    both = [state["keep"][l] & refst["keep"][l] for l in range(n)]
    grads = list(refst["grad_values"]) + list(refst["grad_biases"])
    g_med = float(np.median(grads))
    include = [g >= GRAD_FLOOR * g_med for g in grads]
    d_prog = [state["values"][l][both[l]] - init["values"][l][both[l]]
              for l in range(n)]
    d_ref = [refst["values"][l][both[l]] - init["values"][l][both[l]]
             for l in range(n)]
    d_prog += [state["biases"][l] - init["biases"][l] for l in range(n)]
    d_ref += [refst["biases"][l] - init["biases"][l] for l in range(n)]
    v_prog = [state["vel_values"][l][both[l]] for l in range(n)]
    v_prog += list(state["vel_biases"])
    v_ref = [refst["vel_values"][l][both[l]] for l in range(n)]
    v_ref += list(refst["vel_biases"])
    return include, (d_prog, d_ref), (v_prog, v_ref)


def readings(state, refst, init):
    """The training numbers compared, for a program (or control) ``state``
    against the reference state ``refst``; ``init`` is the epoch's start."""
    n = len(refst["values"])
    include, change, vel = leaves(state, refst, init)
    evo = max(
        np.count_nonzero(state["keep"][l] ^ refst["keep"][l])
        / max(1, np.count_nonzero(~refst["keep"][l]))
        for l in range(n)
    )
    out = {
        "loss": abs(state["loss"] - refst["loss"]) / abs(refst["loss"]),
        "change": _worst(leaf_gaps(*change, include)),
        "velocity": _worst(leaf_gaps(*vel, include)),
        "evolution": float(evo),
        "nnz": float(sum(abs(a - b.size) for a, b in zip(state["nnz_after"],
                                                         init["values"]))),
        "positions": float(state.get("positions", 0)),
    }
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}


def logit_rows(max_nnz, n):
    """Rows per call of the reference's forward over ``n`` rows whose widest
    layer has ``max_nnz`` connections: as many as keep that layer's slab in
    one block of ``BLOCK_ELEMS`` elements, or ``LOGIT_ROWS`` where that is
    under ``WHOLE_ROWS_MIN``."""
    rows = BLOCK_ELEMS // max_nnz
    return min(n, rows if rows >= WHOLE_ROWS_MIN else LOGIT_ROWS)


def reference_logits(config, params, topology, x, dtype=jnp.float32):
    """The reference's forward with dropout off over the rows of ``x``, in
    equal calls of :func:`logit_rows` rows; host array out."""
    ref = load_reference(config)
    params = jax.tree.map(jnp.asarray, params)
    topo = tuple((jnp.asarray(r), jnp.asarray(c)) for r, c in topology)
    n = x.shape[0]
    rows = logit_rows(max(r.size for r, _ in topology), n)
    fwd = jax.jit(functools.partial(ref.forward, cfg=config, key=None,
                                    dtype=jnp.dtype(dtype),
                                    block_elems=BLOCK_ELEMS))
    out = []
    for s in range(0, n, rows):
        xb = x[s:s + rows]
        pad = rows - xb.shape[0]
        if pad:
            xb = jnp.concatenate([xb, jnp.zeros((pad,) + xb.shape[1:], xb.dtype)])
        out.append(np.asarray(fwd(params, topo, xb))[: rows - pad])
    return np.concatenate(out)


def eval_readings(logits, test_acc, ref_logits, y):
    """``logits`` and ``eval_acc`` for an evaluation's ``logits`` and the
    accuracy recorded for it, against the reference's logits on the same
    state and the labels ``y``."""
    logits = np.asarray(logits, np.float64)
    gap = np.abs(logits - ref_logits).max() / np.abs(ref_logits).max()
    hits = int((logits.argmax(-1) == np.asarray(y)).sum())
    rows = abs(round(test_acc * y.shape[0]) - hits)
    out = {"logits": float(gap), "eval_acc": float(rows)}
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}


def judge(values: dict, limits: dict):
    """``(correct, rows)``: every number at or under its limit; ``rows`` is
    ``{name: {"value", "limit"}}`` in a fixed order."""
    rows = {k: {"value": values[k], "limit": limits[k]} for k in NAMES}
    ok = all(r["value"] <= r["limit"] for r in rows.values())
    return ok, rows
