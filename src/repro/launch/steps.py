"""Pure step functions (train / prefill / decode) for every architecture,
shared by the dry-run, the pod training driver, and the serving driver.

train_step: momentum-SGD (paper Eq. 1) on CE loss (+ MoE aux), gradients
reduced over the data axes by GSPMD from the in/out shardings. Sparse
topology arrays ride along as non-trainable inputs — for the element (COO)
path that now includes the dual-order views (``ElemTopoArrays``), so the
whole step (forward AND the hand-derived custom-VJP backward) runs on the
chunked sorted segment-sum passes with no unsorted scatter (DESIGN.md §1b).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.mlp import SparseMLPConfig, cross_entropy_loss, mlp_forward
from repro.models.transformer import PatternLM, chunked_softmax_xent
from repro.models.whisper import WhisperModel
from repro.optim.sgd import MomentumSGD

PyTree = Any


def make_mlp_step_core(config: SparseMLPConfig, opt: MomentumSGD, topo_arrays,
                       x_all=None, y_all=None):
    """The one SET-MLP minibatch step body (loss → value_and_grad →
    momentum-SGD update), shaped for ``scan_segment``/``scan_masked_segment``.

    With ``x_all``/``y_all`` (device-resident dataset) the step input is
    ``(idx, lr)`` and the batch is gathered on device (clip mode: loader
    permutations are always in bounds, so skip fill-mode bounds masking —
    measurably cheaper on CPU XLA); without them the input is ``(x, y, lr)``.
    Shared by the sequential trainer's fused segment and both WASAP phase-1
    round programs so the step semantics live in exactly one place.
    """

    def step_core(p, s, inp, rng):
        if x_all is None:
            xb, yb, lr = inp
        else:
            idx, lr = inp
            xb = jnp.take(x_all, idx, axis=0, mode="clip")
            yb = jnp.take(y_all, idx, axis=0, mode="clip")

        def loss_fn(pp):
            logits = mlp_forward(pp, topo_arrays, xb, config, train=True, rng=rng)
            return cross_entropy_loss(logits, yb)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        p, s = opt.update(grads, s, p, lr)
        return p, s, loss

    return step_core


def make_mlp_train_step(config: SparseMLPConfig, opt: MomentumSGD):
    """Jitted single-minibatch SET-MLP train step (value_and_grad + update).

    The shared building block for the sequential trainer's per-batch mode
    and the kernels micro-benchmark's train-step row: one espmm per layer in
    the forward, the custom-VJP dX/dW passes in the backward (for
    ``element_impl`` in {"auto", "custom"}), then the momentum-SGD update.
    Topology arrays are non-trainable inputs, so SET evolution between calls
    never recompiles it.
    """

    @jax.jit
    def step(params, opt_state, topo_arrays, x, y, lr, rng):
        core = make_mlp_step_core(config, opt, topo_arrays)
        return core(params, opt_state, (x, y, lr), rng)

    return step


def scan_segment(step_core, params, opt_state, key, step_inputs):
    """Run a multi-step train segment as one ``lax.scan`` (no per-step
    dispatch): threads (params, opt_state, key) through ``step_core`` and
    stacks the per-step metrics. ``step_core(params, opt_state, inp, rng)``
    must return ``(params, opt_state, metrics)``. Jit the caller and donate
    params/opt_state for a fully device-resident epoch segment."""

    def body(carry, inp):
        p, s, k = carry
        k, sub = jax.random.split(k)
        p, s, metrics = step_core(p, s, inp, sub)
        return (p, s, k), metrics

    (params, opt_state, key), metrics = jax.lax.scan(
        body, (params, opt_state, key), step_inputs
    )
    return params, opt_state, key, metrics


def scan_masked_segment(step_core, params, opt_state, key, step_inputs, valid):
    """``scan_segment`` with per-step validity weights.

    ``valid`` is a float (steps,) vector: steps where ``valid == 0`` still
    trace (so padded tails keep every shape static and one compile serves
    the whole run) but leave the (params, opt_state) carry untouched and
    contribute zero to the stacked metrics. ``step_core`` must return a
    scalar metric (it is scaled by ``valid``). Used by the WASAP phase-1
    round function, whose tail rounds pad the local-step axis to a static H.
    """

    def body(carry, inp):
        p, s, k = carry
        x, v = inp
        k, sub = jax.random.split(k)
        new_p, new_s, metric = step_core(p, s, x, sub)
        keep = v > 0
        p = jax.tree.map(lambda n, o: jnp.where(keep, n, o), new_p, p)
        s = jax.tree.map(lambda n, o: jnp.where(keep, n, o), new_s, s)
        return (p, s, k), metric * v

    (params, opt_state, key), metrics = jax.lax.scan(
        body, (params, opt_state, key), (step_inputs, valid)
    )
    return params, opt_state, key, metrics


def _microbatched_grad(loss_fn, params, batch, microbatches: int):
    """Gradient accumulation over leading-batch microbatches (lax.scan).
    Activation memory scales 1/microbatches; grads accumulate in f32."""
    if microbatches <= 1:
        (total, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch
        )
        return total, loss, grads
    mb = jax.tree.map(
        lambda a: a.reshape(microbatches, a.shape[0] // microbatches, *a.shape[1:]),
        batch,
    )

    def body(carry, one):
        g_acc, t_acc, l_acc = carry
        (total, loss), g = jax.value_and_grad(loss_fn, has_aux=True)(params, one)
        g_acc = jax.tree.map(lambda a, b: a + b.astype(a.dtype), g_acc, g)
        return (g_acc, t_acc + total, l_acc + loss), None

    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (g, total, loss), _ = jax.lax.scan(
        body, (g0, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), mb
    )
    inv = 1.0 / microbatches
    grads = jax.tree.map(lambda a: (a * inv), g)
    return total * inv, loss * inv, grads


def make_train_step(
    model, *, lr: float = 1e-2, momentum: float = 0.9, microbatches: int = 1
):
    opt = MomentumSGD(momentum=momentum, weight_decay=1e-4)

    if isinstance(model, WhisperModel):

        def loss_fn_w(p, batch):
            mem = model.encode(p, batch["frames"])
            h = model.decode_train(p, batch["tokens"], mem)
            logits = model.logits(p, h).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits)
            nll = -jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)
            loss = nll.mean()
            return loss, loss

        def train_step(params, opt_state, batch):
            total, loss, grads = _microbatched_grad(
                loss_fn_w, params, batch, microbatches
            )
            params, opt_state = opt.update(grads, opt_state, params, lr)
            return params, opt_state, {"loss": loss}

        return train_step, opt

    def train_step(params, opt_state, batch, topo):
        def loss_fn(p, b):
            h, _, aux = model.forward(
                p,
                b["tokens"],
                topo=topo,
                prefix_embeds=b.get("patch_embeds"),
                return_hidden=True,
            )
            labels = b["labels"]
            if "patch_embeds" in b:
                h = h[:, b["patch_embeds"].shape[1] :]
                labels = labels[:, : h.shape[1]]
            loss = chunked_softmax_xent(model, p, h, labels)
            return loss + aux, loss

        total, loss, grads = _microbatched_grad(loss_fn, params, batch, microbatches)
        params, opt_state = opt.update(grads, opt_state, params, lr)
        return params, opt_state, {"loss": loss, "total": total}

    return train_step, opt


def make_prefill_step(model):
    if isinstance(model, WhisperModel):

        def prefill(params, batch):
            mem = model.encode(params, batch["frames"])
            h = model.decode_train(params, batch["tokens"], mem)
            return model.logits(params, h[:, -1:, :])

        return prefill

    def prefill(params, batch, topo):
        logits, _, _ = model.forward(
            params,
            batch["tokens"],
            topo=topo,
            prefix_embeds=batch.get("patch_embeds"),
        )
        return logits[:, -1:, :]

    return prefill


def make_decode_step(model):
    if isinstance(model, WhisperModel):

        def decode(params, batch):
            return model.decode_step(
                params, batch["tokens"], batch["position"], batch["caches"],
                batch["memory"],
            )

        return decode

    def decode(params, batch, topo):
        logits, new_caches, _ = model.forward(
            params,
            batch["tokens"],
            topo=topo,
            positions=jnp.reshape(batch["position"], (1,)),
            mode="decode",
            caches=batch["caches"],
        )
        return logits, new_caches

    return decode


# ---------------------------------------------------------------------------
# contract auditor registration (repro.analysis, DESIGN.md §10)
# ---------------------------------------------------------------------------


def analysis_programs():
    """Registry hook: the per-batch SET-MLP train step (the legacy/benchmark
    path and the building block of every fused segment). Deliberately NOT
    donated: ``runtime.supervisor.retry_step`` re-enters it with the same
    buffers after a transient fault, which donation would invalidate."""
    from repro.analysis.registry import AuditProgram, Contract, ProgramSpec
    from repro.core import sparsity

    dims = (256, 128, 64)
    batch = 32

    def build() -> AuditProgram:
        from repro.models.mlp import SparseMLP

        config = SparseMLPConfig(layer_dims=dims, epsilon=16, dropout=0.0)
        model = SparseMLP(config, seed=0)
        opt = MomentumSGD(momentum=0.9, weight_decay=2e-4)

        def program(params, opt_state, topo_arrays, x, y, lr, rng):
            core = make_mlp_step_core(config, opt, topo_arrays)
            return core(params, opt_state, (x, y, lr), rng)

        args = (
            model.params(),
            opt.init(model.params()),
            model.topo_arrays(),
            jnp.zeros((batch, dims[0]), jnp.float32),
            jnp.zeros((batch,), jnp.int32),
            jnp.asarray(0.01, jnp.float32),
            jax.random.PRNGKey(0),
        )
        nnz = [int(t.rows.shape[0]) for t in model.topos]
        return AuditProgram(
            make=lambda donate: jax.jit(program, donate_argnums=donate),
            args=args,
            meta={"dims": dims, "batch": batch, "nnz": nnz},
        )

    return [
        ProgramSpec(
            name="launch.mlp_train_step",
            subsystem=__name__,
            contract=Contract(
                max_unsorted_scatter=1,
                max_unsorted_scatter_elems=batch * dims[-1],
                max_intermediate_elems=sparsity.SPMM_TEMP_BUDGET_ELEMS,
                max_temp_bytes=8 * 1024 * 1024,
                expected_compiles=1,
            ),
            build=build,
            notes="per-batch step; undonated by design (retry_step re-entry)",
        )
    ]
