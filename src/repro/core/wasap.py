"""WASAP-SGD (paper Algorithm 1) — device-resident SPMD/TPU adaptation.

Phase 1 (paper: async parameter server) → **local SGD with periodic sparse
model averaging**: K workers take H local momentum-SGD steps on their data
shards, then weights (and momentum) are averaged. H>1 reproduces asynchrony's
communication-avoidance and staleness; H=1 with the Goyal warmup/linear-
scaling schedule is exactly the paper's synchronous control, WASSP-SGD.

Phase 1 runs on the device-resident substrate (DESIGN.md §4): ONE jitted,
buffer-donated call per epoch ``lax.scan``s over the sync rounds — an inner
scan over the H local steps per worker, then an on-device pytree average
between rounds. The training set lives on the device; the host ships only
each worker-shard's epoch index permutation (``ShardedLoader.epoch_order``),
per-step learning rates, and validity weights (tail rounds are padded to a
static H so one compile serves the whole run). The worker axis is expressed
two interchangeable ways, selected by ``WASAPConfig.worker_axis``:

* ``"vmap"``   — stacked (K, ...) worker axis on one device (CPU tests).
* ``"shard_map"`` — the same program shard_map'd over the 'data' axis of a
  ``launch.mesh.make_worker_mesh`` mesh, each shard vmapping its local
  workers and averaging after an ``all_gather`` over the axis (the
  deterministic-order equivalent of a pmean) — bit-identical to the vmap
  path, and the per-shard program a pod runs.

The master's topology evolution between epochs runs jitted on fixed-capacity
arrays (``core.topology.evolve_element_layers_device``) — zero recompiles,
zero host<->device parameter traffic for the whole phase. Every worker
update is implicitly `RetainValidUpdates`-filtered because values are
re-aligned to the evolved topology before workers resume (the paper's
Algorithm 1 line 14).

Phase 2: workers train **locally** on the fused epoch segments
(``train.trainer.make_segment_fn``) and evolve their own topologies
independently on device (per-worker PRNG streams); at the end the K sparse
models are averaged over the union of their topologies and re-sparsified to
the target connection count by the paper's sign-aware magnitude rule
(Algorithm 1, line 37).

``WASAPConfig.fused=False`` keeps the seed-era round loop — per-round Python
dispatch, host-side replication, numpy batch stacking, host evolution — as
the measured baseline for ``benchmarks/table3_parallel.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.sparsity import ElementTopology
from repro.core.topology import (
    evolve_element,
    evolve_element_layers_device,
    prune_indices_by_magnitude,
)
from repro.data.loader import ShardedLoader
from repro.data.synthetic import Dataset
from repro.launch.mesh import make_worker_mesh
from repro.launch.steps import make_mlp_step_core, scan_masked_segment
from repro.models.mlp import (
    SparseMLP,
    SparseMLPConfig,
    cross_entropy_loss,
    mlp_forward,
)
from repro.optim.sgd import MomentumSGD, SGDState, replace_values_velocity
from repro.runtime import donation
from repro.runtime.supervisor import retry_step
from repro.train.trainer import evaluate, make_segment_fn, make_step_fn
from repro import obs
from repro.obs import probes

__all__ = [
    "WASAPConfig",
    "WASAPTrainer",
    "make_phase1_epoch_fn",
    "sparse_average_and_resparsify",
]


@dataclasses.dataclass
class WASAPConfig:
    n_workers: int = 4
    phase1_epochs: int = 6
    phase2_epochs: int = 2
    sync_every: int = 4          # H — local steps between averages (1 => WASSP)
    lr: float = 0.01
    lr_boost: float = 2.0        # paper §2.3: larger LR early in async phase
    lr_boost_epochs: int = 2
    warmup_steps: int = 50       # WASSP: Goyal et al. gradual warmup
    momentum: float = 0.9
    weight_decay: float = 2e-4
    zeta: float = 0.3
    mode: str = "wasap"          # wasap | wassp
    seed: int = 0
    batch_size: int = 32
    average_momentum: bool = True
    fused: bool = True           # one jitted call per epoch (False: seed loop)
    worker_axis: str = "vmap"    # vmap | shard_map
    probe: bool = False          # training-dynamics probes (obs.probes, §12)


# ---------------------------------------------------------------------------
# device-side worker programs
# ---------------------------------------------------------------------------


def _average_pytree(stacked, weights=None):
    if weights is None:
        return jax.tree.map(lambda a: a.mean(axis=0), stacked)
    w = weights / weights.sum()

    def wavg(a):
        wb = w.reshape((-1,) + (1,) * (a.ndim - 1))
        return (a * wb).sum(axis=0)

    return jax.tree.map(wavg, stacked)


def _cast_like(tree, ref):
    """Restore the reference dtypes after an averaging reduction (mean
    promotes the int32 step counter to float; scan carries and repeated jit
    calls both need dtype-stable state)."""
    return jax.tree.map(lambda a, r: a.astype(r.dtype), tree, ref)


_average_workers = jax.jit(_average_pytree)


def _replicate(tree, k: int):
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (k,) + a.shape), tree)


def _take_worker0(tree):
    return jax.tree.map(lambda a: a[0], tree)


def make_phase1_epoch_fn(
    config: SparseMLPConfig,
    opt: MomentumSGD,
    *,
    n_workers: int,
    average_momentum: bool = True,
    worker_axis: str = "vmap",
    mesh=None,
    weighted: bool = False,
    donate=None,
    probe: bool = False,
):
    """Build the jitted phase-1 epoch: one device call scanning sync rounds.

    ``epoch_fn(params, opt_state, topo, x_all, y_all, idx, lrs, valid, keys)``

    * ``idx``   — (R, K, H, B) int32 sample indices into the device-resident
      ``x_all``/``y_all`` (each worker-shard's ``ShardedLoader.epoch_order``,
      padded to R*H steps);
    * ``lrs``/``valid`` — (R, H) per-step learning rates and validity
      weights (0 on padded tail steps: those steps trace but leave the
      carry untouched, so the tail round never changes a shape);
    * ``keys``  — (R, K, 2) per-round per-worker PRNG keys (dropout).

    Returns ``(params, opt_state, loss_sums)`` with ``loss_sums`` the (R,)
    per-round sums of valid per-step losses.

    ``weighted=True`` appends a tenth argument ``worker_w`` — (K,) validity
    weights over the worker axis, renormalized inside the average — so an
    evicted/dead worker contributes zero while the round completes with the
    survivors (the elastic WASAP round, DESIGN.md §8). With uniform weights
    the result is bit-identical to the unweighted build only up to float
    reassociation, so the unweighted path stays the default.

    ``worker_axis="vmap"`` stacks the K workers on one device;
    ``"shard_map"`` maps the same program over the 'data' axis of ``mesh``
    (each shard vmaps its K/D local workers, all_gathers the worker axis,
    and averages in the same order as the vmap path — bit-identical).

    ``donate`` overrides the central donation policy
    (``repro.runtime.donation``) — the contract auditor passes explicit
    argnums to force-build donated/undonated variants.

    ``probe=True`` (static — the default build's traced program is exactly
    the pre-probe program) appends a fourth output: the per-layer
    training-dynamics stats of ``obs.probes.segment_probe``, computed on
    the epoch's first batch (round 0, worker 0 — always a valid, unpadded
    step) AFTER the sync-round scan. Stats stay on device; the trainer
    records them host-side after its ``block_on`` (DESIGN.md §12).
    """
    if worker_axis not in ("vmap", "shard_map"):
        raise ValueError(f"worker_axis must be vmap|shard_map, got {worker_axis!r}")
    if worker_axis == "shard_map":
        if mesh is None:
            raise ValueError("worker_axis='shard_map' needs a mesh")
        data_size = dict(zip(mesh.axis_names, mesh.devices.shape))["data"]
        if n_workers % data_size != 0:
            raise ValueError(
                f"n_workers={n_workers} must be divisible by the mesh's "
                f"data axis ({data_size})"
            )
        k_local = n_workers // data_size
    else:
        k_local = n_workers

    def local_steps(params, opt_state, topo, x_all, y_all, idx_h, lrs_h, valid_h, key):
        step_core = make_mlp_step_core(config, opt, topo, x_all, y_all)
        params, opt_state, _, losses = scan_masked_segment(
            step_core, params, opt_state, key, (idx_h, lrs_h), valid_h
        )
        return params, opt_state, losses.sum()

    def epoch_program(
        params, opt_state, topo, x_all, y_all, idx, lrs, valid, keys,
        worker_w=None,
    ):
        def round_body(carry, inp):
            params, opt_state = carry
            idx_r, lrs_r, valid_r, keys_r = inp
            sp = _replicate(params, k_local)
            so = _replicate(opt_state, k_local)
            sp, so, lsum = jax.vmap(
                lambda p, s, i, kk: local_steps(
                    p, s, topo, x_all, y_all, i, lrs_r, valid_r, kk
                )
            )(sp, so, idx_r, keys_r)
            if worker_axis == "shard_map":
                # gather the full worker axis so every shard averages the K
                # results in the same order as the vmap path — the
                # deterministic-order equivalent of a pmean
                sp, so, lsum = jax.tree.map(
                    lambda a: jax.lax.all_gather(a, "data", axis=0, tiled=True),
                    (sp, so, lsum),
                )
            new_params = _cast_like(_average_pytree(sp, worker_w), params)
            new_opt = (
                _cast_like(_average_pytree(so, worker_w), opt_state)
                if average_momentum
                else _take_worker0(so)
            )
            return (new_params, new_opt), lsum.sum()

        (params, opt_state), loss_sums = jax.lax.scan(
            round_body, (params, opt_state), (idx, lrs, valid, keys)
        )
        if not probe:
            return params, opt_state, loss_sums
        # post-scan probe on the epoch's first batch (round 0, worker 0 —
        # always valid; padding only reaches tail rounds)
        xb = jnp.take(x_all, idx[0, 0, 0], axis=0, mode="clip")
        yb = jnp.take(y_all, idx[0, 0, 0], axis=0, mode="clip")

        def probe_loss(p):
            logits, preacts = mlp_forward(
                p, topo, xb, config, train=False, return_preacts=True
            )
            return cross_entropy_loss(logits, yb), preacts

        (_, preacts), grads = jax.value_and_grad(probe_loss, has_aux=True)(
            params
        )
        stats = probes.segment_probe(
            params, grads, topo, preacts, config.layer_dims
        )
        return params, opt_state, loss_sums, stats

    if not weighted:
        # keep the historical 9-arg signature (and its exact averaging
        # program) when no elastic weights are in play
        program = functools.partial(epoch_program, worker_w=None)
    else:
        program = epoch_program

    fn = program
    if worker_axis == "shard_map":
        in_specs = [
            P(), P(), P(), P(), P(),          # params/opt/topo/x/y replicated
            P(None, "data"),                  # idx   (R, K, H, B) on axis 1
            P(), P(),                         # lrs/valid replicated
            P(None, "data"),                  # keys  (R, K, 2)   on axis 1
        ]
        if weighted:
            in_specs.append(P())              # worker_w (K,) replicated
        out_specs = (P(), P(), P(), P()) if probe else (P(), P(), P())
        fn = jax.shard_map(
            program,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=out_specs,  # P() prefixes the probe-stats dict leaves
            check_vma=False,  # all_gather + mean makes every output replicated
        )
    return jax.jit(fn, donate_argnums=donation.donate_argnums(0, 1, override=donate))


def _make_worker_round(config: SparseMLPConfig, opt: MomentumSGD):
    """Seed-era round: each worker runs H local steps over stacked batches.

    Kept as the measured baseline for the fused epoch (per-round Python
    dispatch, host-side replication, numpy batch stacking). Tail rounds are
    padded to a static H with ``valid`` weights so one compile serves the
    whole run, and the dropout key plumbing is explicit: each step splits a
    fresh ``sub`` that the loss closes over (the seed closed over the
    rebound parent key by late binding and never used its split).
    """

    @jax.jit
    def worker_round(stacked_params, stacked_opt, topo, xs, ys, lrs, valid, rngs):
        # xs: (K, H, B, F); ys: (K, H, B); lrs/valid: (H,)
        step_core = make_mlp_step_core(config, opt, topo)

        def per_worker(params, opt_state, x_h, y_h, rng):
            params, opt_state, _, losses = scan_masked_segment(
                step_core, params, opt_state, rng, (x_h, y_h, lrs), valid
            )
            return params, opt_state, losses.sum()

        return jax.vmap(per_worker)(stacked_params, stacked_opt, xs, ys, rngs)

    return worker_round


# ---------------------------------------------------------------------------
# final merge (Algorithm 1, line 37)
# ---------------------------------------------------------------------------


def _sign_aware_drop(avg: np.ndarray, surplus: int) -> np.ndarray:
    """Indices of ``surplus`` connections to drop by the paper's sign-aware
    magnitude rule: exact zeros first, then each sign's proportional
    low-magnitude tail (the smallest positives and the largest negatives,
    via :func:`prune_indices_by_magnitude`), with any integer remainder
    topped up from the smallest remaining ``|avg|``."""
    zeros = np.flatnonzero(avg == 0)
    if zeros.size >= surplus:
        return zeros[:surplus]
    n_signed = int((avg > 0).sum() + (avg < 0).sum())
    zeta = (surplus - zeros.size) / n_signed
    drop = prune_indices_by_magnitude(avg, zeta)  # zeros + per-sign tails
    short = surplus - drop.size  # >= 0: per-sign tail sizes are floored
    if short > 0:
        rest = np.setdiff1d(np.arange(avg.size), drop)
        rest = rest[np.argsort(np.abs(avg[rest]), kind="stable")]
        drop = np.concatenate([drop, rest[:short]])
    return drop


def sparse_average_and_resparsify(
    topos: List[ElementTopology],
    values: List[np.ndarray],
    target_nnz: int,
) -> Tuple[ElementTopology, np.ndarray]:
    """Average K sparse models over the union of their topologies, then keep
    ``target_nnz`` connections by the paper's sign-aware magnitude rule
    (Algorithm 1 line 37): the surplus is pruned as exact zeros, the
    smallest-positive tail and the largest-negative tail — each sign
    contributing its proportional share — not a plain |value| ranking."""
    k = len(topos)
    assert k >= 1
    in_dim, out_dim = topos[0].in_dim, topos[0].out_dim
    flat_all = np.concatenate(
        [t.rows.astype(np.int64) * out_dim + t.cols for t in topos]
    )
    val_all = np.concatenate([np.asarray(v, np.float64) for v in values])
    uniq, inv = np.unique(flat_all, return_inverse=True)
    summed = np.zeros(uniq.size, np.float64)
    np.add.at(summed, inv, val_all)
    avg = (summed / k).astype(np.float32)  # absent connections count as zero

    surplus = uniq.size - int(target_nnz)
    if surplus > 0:
        # surplus = S' - S unimportant connections pruned (Algorithm 1 l.37)
        drop = _sign_aware_drop(avg, surplus)
        keep = np.setdiff1d(np.arange(uniq.size), drop)
    else:
        keep = np.arange(uniq.size)
    rows = (uniq[keep] // out_dim).astype(np.int32)
    cols = (uniq[keep] % out_dim).astype(np.int32)
    topo = ElementTopology(in_dim, out_dim, rows, cols)
    order = np.lexsort((rows, cols))
    return topo, avg[keep][order]


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------


class WASAPTrainer:
    """Two-phase WASAP/WASSP-SGD for SET-MLPs (element sparsity)."""

    def __init__(self, model: SparseMLP, data: Dataset, wc: WASAPConfig):
        assert model.config.impl == "element", "WASAP path uses element sparsity"
        self.model = model
        self.data = data
        self.wc = wc
        self.opt = MomentumSGD(momentum=wc.momentum, weight_decay=wc.weight_decay)
        self.rng = np.random.default_rng(wc.seed)
        self.key = jax.random.PRNGKey(wc.seed)
        cfg = model.config
        # the device paths encode flat positions in int32
        self._device_ok = all(
            cfg.layer_dims[l] * cfg.layer_dims[l + 1] < 2**31
            for l in range(cfg.n_layers)
        )
        if not self._device_ok:
            if wc.worker_axis == "shard_map":
                raise ValueError(
                    "worker_axis='shard_map' needs the device-resident path, "
                    "but a layer's in_dim*out_dim exceeds int32"
                )
            if wc.fused:
                warnings.warn(
                    "fused WASAP needs in_dim*out_dim < 2**31 per layer; "
                    "falling back to the seed round loop",
                    stacklevel=2,
                )
        self._fused = wc.fused and self._device_ok
        self._h = 1 if wc.mode == "wassp" else wc.sync_every
        self._mesh = None
        if self._fused:
            self._mesh = (
                make_worker_mesh(wc.n_workers)
                if wc.worker_axis == "shard_map"
                else None
            )
            self._epoch_fn = make_phase1_epoch_fn(
                cfg, self.opt,
                n_workers=wc.n_workers,
                average_momentum=wc.average_momentum,
                worker_axis=wc.worker_axis,
                mesh=self._mesh,
                probe=wc.probe,
            )
            self._segment = make_segment_fn(cfg, self.opt)
            # phase-2 probe segment for worker 0 only (never pass an
            # explicit False — the 2-arg call is the shared cache key)
            self._probe_segment = (
                make_segment_fn(cfg, self.opt, True) if wc.probe else None
            )
        else:
            self._round = _make_worker_round(cfg, self.opt)
        self.loaders = [
            ShardedLoader(
                data.x_train, data.y_train, wc.batch_size,
                seed=wc.seed, shard_id=k, num_shards=wc.n_workers,
            )
            for k in range(wc.n_workers)
        ]
        self.history: Dict[str, list] = {
            "epoch": [], "phase": [], "test_acc": [], "train_loss": [],
            "n_params": [], "epoch_seconds": [],
        }
        self._device_data = None  # lazy: one upload shared by both phases
        # -- resume / elastic surface (DESIGN.md §8), fused path -------------
        self.start_epoch = 0            # absolute epoch run() continues from
        self.epoch_next = 0
        self.fault_hook = None          # hook(gstep) before each epoch call
        self.epoch_end_hook = None      # hook(trainer, epoch) at boundaries
        self.step_retries = 0
        self.retry_backoff_s = 0.0
        # heartbeat-driven elasticity: attach a supervisor.
        # HeartbeatMonitor over worker ids "w0".."w{K-1}" (plus an optional
        # beat_filter(worker_id, epoch) -> bool, e.g. faultinject.
        # StragglerInjector.beats) and phase-1 rounds run with renormalized
        # validity weights: evicted/dead workers contribute zero.
        self.monitor = None
        self.beat_filter = None
        self.elastic_log: List[Dict] = []
        self._phase = 1                 # 1 | 2 — which phase run() enters
        self._p1_state = None           # (params, opt_state, topo) boundary
        self._p2_workers = None         # phase-2 replicas at a boundary
        self._epoch_fn_weighted = None  # built lazily when a monitor attaches
        self._last_churn = None         # (n_pruned, nnz) from master evolve

    def _data_on_device(self):
        if self._device_data is None:
            self._device_data = (
                jnp.asarray(self.data.x_train), jnp.asarray(self.data.y_train)
            )
        return self._device_data

    # -- lr schedules --------------------------------------------------------

    def _lr(self, gstep: int, epoch: int) -> float:
        wc = self.wc
        if wc.mode == "wassp":
            # gradual warmup + linear scaling rule (Goyal et al. 2017)
            target = wc.lr * wc.n_workers
            frac = min(1.0, (gstep + 1) / max(1, wc.warmup_steps))
            return wc.lr + frac * (target - wc.lr)
        # wasap: larger LR for the first few epochs, then fixed (paper §2.3)
        return wc.lr * wc.lr_boost if epoch < wc.lr_boost_epochs else wc.lr

    # -- phases --------------------------------------------------------------

    def run(self) -> Dict[str, list]:
        with obs.span(
            "wasap.run", mode=self.wc.mode, workers=self.wc.n_workers,
            fused=self._fused, worker_axis=self.wc.worker_axis,
        ):
            if self._fused:
                if self._phase == 1:
                    self._run_phase1_fused()
                    self._phase = 2
                worker_states = self._run_phase2_fused()
            else:
                self._run_phase1_roundloop()
                worker_states = self._run_phase2_perbatch()
            with obs.span("wasap.merge", workers=len(worker_states)):
                self._merge_workers(worker_states)
        acc = evaluate(self.model, self.data.x_test, self.data.y_test)
        wc = self.wc
        self.history["epoch"].append(wc.phase1_epochs + wc.phase2_epochs)
        self.history["phase"].append("final")
        self.history["train_loss"].append(float("nan"))
        self.history["test_acc"].append(acc)
        self.history["n_params"].append(self.model.n_params)
        self.history["epoch_seconds"].append(0.0)
        return self.history

    # -- phase 1: local SGD + periodic averaging (device-resident) -----------

    def _run_phase1_fused(self) -> None:
        wc, model = self.wc, self.model
        k, h, bsz = wc.n_workers, self._h, wc.batch_size
        steps = min(ld.steps_per_epoch for ld in self.loaders)
        if steps == 0:
            raise ValueError("batch_size larger than the worker shards")
        rounds = -(-steps // h)
        padded = rounds * h
        x_all, y_all = self._data_on_device()
        if self._p1_state is not None:  # resumed at an epoch boundary
            params, opt_state, topo = self._p1_state
        else:
            params = model.params()
            opt_state = self.opt.init(params)
            topo = model.topo_arrays()
        start = min(self.start_epoch, wc.phase1_epochs)
        gstep = start * steps
        for epoch in range(start, wc.phase1_epochs):
            with obs.span(
                "wasap.epoch", epoch=epoch, phase=1, rounds=rounds
            ) as ep_sp:
                t0 = time.perf_counter()
                weights = (
                    self._worker_weights(epoch)
                    if self.monitor is not None else None
                )
                idx = np.zeros((rounds, k, h, bsz), np.int32)
                for wk, ld in enumerate(self.loaders):
                    order = np.zeros((padded, bsz), np.int32)
                    order[:steps] = (
                        ld.epoch_order(epoch)[: steps * bsz]
                        .astype(np.int32)
                        .reshape(steps, bsz)
                    )
                    idx[:, wk] = order.reshape(rounds, h, bsz)
                valid = np.zeros((rounds * h,), np.float32)
                valid[:steps] = 1.0
                lrs = np.zeros((rounds * h,), np.float32)
                lrs[:steps] = [self._lr(gstep + i, epoch) for i in range(steps)]
                self.key, sub = jax.random.split(self.key)
                keys = jax.random.split(sub, rounds * k).reshape(rounds, k, 2)
                epoch_args = (
                    params, opt_state, topo, x_all, y_all,
                    jnp.asarray(idx), jnp.asarray(lrs.reshape(rounds, h)),
                    jnp.asarray(valid.reshape(rounds, h)), keys,
                )

                def run_epoch():
                    # hook first: a kill/transient fires before the pure
                    # device call, so retry_step re-enters with identical
                    # inputs
                    if self.fault_hook is not None:
                        self.fault_hook(gstep)
                    if weights is None:
                        return self._epoch_fn(*epoch_args)
                    return self._weighted_epoch_fn()(
                        *epoch_args, jnp.asarray(weights)
                    )

                # jitted-call boundary: the whole epoch's sync rounds are one
                # device call; the span times its dispatch and blocks on
                # nothing (the loop first blocks after master evolution)
                with obs.span(
                    "wasap.sync_rounds", rounds=rounds, h=h,
                    elastic=weights is not None,
                ):
                    if self.step_retries:
                        out = retry_step(
                            run_epoch,
                            retries=self.step_retries,
                            backoff_s=self.retry_backoff_s,
                        )
                    else:
                        out = run_epoch()
                    # the elastic (weighted) program stays probe-off: its
                    # epochs simply record no snapshot
                    if wc.probe and weights is None:
                        params, opt_state, loss_sums, probe_dev = out
                    else:
                        params, opt_state, loss_sums = out
                        probe_dev = None
                gstep += steps
                # master topology evolution on the averaged model; momentum
                # is re-aligned (RetainValidUpdates semantics for velocity)
                self.key, sub = jax.random.split(self.key)
                topo, params, opt_state = self._evolve_master_device(
                    topo, params, opt_state, sub
                )
                obs.point("wasap.evolve", epoch=epoch, device=True)
                # dispatch is async — wait for the epoch's device work so
                # epoch_seconds measures compute, not enqueue
                jax.block_until_ready((params, loss_sums))
                dt = time.perf_counter() - t0
                train_loss = float(jnp.sum(loss_sums)) / (k * steps)
                acc = evaluate(
                    model, self.data.x_test, self.data.y_test,
                    params=params, topo_arrays=topo,
                )
                # host-side recording after the block (§11 obs-in-jit)
                if probe_dev is not None:
                    churn = None
                    if self._last_churn is not None:
                        counts, nnz = self._last_churn
                        churn = [
                            float(c) / max(1, n)
                            for c, n in zip(np.asarray(counts), nnz)
                        ]
                        self._last_churn = None
                    probes.record_snapshot(
                        gstep, "wasap", probe_dev, churn=churn,
                        extra={
                            "epoch": epoch, "phase": 1,
                            "loss": train_loss, "acc": float(acc),
                        },
                    )
                ep_sp.set(loss=train_loss, acc=float(acc))
                self._log(epoch, 1, train_loss, dt, acc)
                self._p1_state = (params, opt_state, topo)
                self.epoch_next = epoch + 1
                if self.epoch_end_hook is not None:
                    self.epoch_end_hook(self, epoch)
        model.set_params(params)
        self._sync_topos_to_host(topo)
        self.epoch_next = wc.phase1_epochs

    def _run_phase1_roundloop(self) -> None:
        """Seed-era phase 1: per-round Python dispatch, host replication,
        numpy batch stacking, host numpy evolution — the fused baseline."""
        wc, model = self.wc, self.model
        k, h = wc.n_workers, self._h
        gstep = 0
        params = model.params()
        opt_state = self.opt.init(params)
        for epoch in range(wc.phase1_epochs):
            t0 = time.perf_counter()
            topo = model.topo_arrays()
            batches = [list(ld.epoch(epoch)) for ld in self.loaders]
            steps = min(len(b) for b in batches)
            if steps == 0:
                raise ValueError("batch_size larger than the worker shards")
            loss_total, s = 0.0, 0
            while s < steps:
                hh = min(h, steps - s)
                # pad the tail round to a static H (valid-masked) so one
                # compile serves the whole run
                xs = np.zeros(
                    (k, h) + batches[0][0][0].shape, batches[0][0][0].dtype
                )
                ys = np.zeros((k, h) + batches[0][0][1].shape, batches[0][0][1].dtype)
                for wk, b in enumerate(batches):
                    for i in range(hh):
                        xs[wk, i], ys[wk, i] = b[s + i]
                valid = np.zeros((h,), np.float32)
                valid[:hh] = 1.0
                lrs = np.zeros((h,), np.float32)
                lrs[:hh] = [self._lr(gstep + i, epoch) for i in range(hh)]
                self.key, *subs = jax.random.split(self.key, k + 1)
                sp = _replicate(params, k)
                so = _replicate(opt_state, k)
                sp, so, lsum = self._round(
                    sp, so, topo, jnp.asarray(xs), jnp.asarray(ys),
                    jnp.asarray(lrs), jnp.asarray(valid), jnp.stack(subs),
                )
                params = _cast_like(_average_workers(sp), params)
                if wc.average_momentum:
                    opt_state = _cast_like(_average_workers(so), opt_state)
                else:
                    opt_state = _take_worker0(so)
                loss_total += float(lsum.sum())
                s += hh
                gstep += hh
            model.set_params(params)
            # master topology evolution on the averaged model (host numpy)
            self._evolve_master(opt_state)
            params = model.params()
            opt_state = self._realigned_opt_state
            dt = time.perf_counter() - t0
            acc = evaluate(model, self.data.x_test, self.data.y_test)
            self._log(epoch, 1, loss_total / (k * steps), dt, acc)

    # -- phase 2: independent local training ---------------------------------

    def _run_phase2_fused(self) -> List[tuple]:
        """Each worker owns a device-resident replica: fused epoch segments
        (one jitted call per worker-epoch) + device topology evolution."""
        wc, model = self.wc, self.model
        cfg = model.config
        k, bsz = wc.n_workers, wc.batch_size
        x_all, y_all = self._data_on_device()
        if self._p2_workers is not None:  # resumed at an epoch boundary
            workers = self._p2_workers
        else:
            base = model.params()
            workers = []
            for wk in range(k):
                self.key, sub = jax.random.split(self.key)
                workers.append({
                    # per-worker copies: segments donate their buffers off-CPU
                    "params": jax.tree.map(jnp.array, base),
                    "opt": self.opt.init(base),
                    "topo": model.topo_arrays(),
                    "key": sub,
                })
        steps_per_epoch = min(ld.steps_per_epoch for ld in self.loaders)
        start = max(self.start_epoch, wc.phase1_epochs)
        for epoch in range(start, wc.phase1_epochs + wc.phase2_epochs):
            with obs.span(
                "wasap.epoch", epoch=epoch, phase=2, workers=k
            ) as ep_sp:
                t0 = time.perf_counter()
                if self.fault_hook is not None:
                    self.fault_hook(epoch * steps_per_epoch)
                losses = []
                p2_probe = None       # worker 0's device probe stats
                p2_churn = None       # worker 0's (n_pruned, nnz)
                # one span over all K worker segments+evolutions: the calls
                # are enqueued asynchronously across workers and blocked on
                # once, so a per-worker span would serialize the device queue
                with obs.span("wasap.worker_segments", workers=k) as ws_sp:
                    for wk in range(k):
                        w = workers[wk]
                        ld = self.loaders[wk]
                        steps = ld.steps_per_epoch
                        perm = jnp.asarray(
                            ld.epoch_order(epoch).astype(np.int32).reshape(
                                steps, bsz
                            )
                        )
                        lrs = jnp.full((steps,), wc.lr, jnp.float32)
                        # worker 0 carries the probes: one representative
                        # replica is enough for phase-2 dynamics and keeps
                        # the other K-1 programs byte-identical to probe-off
                        probing = self._probe_segment is not None and wk == 0
                        seg = self._probe_segment if probing else self._segment
                        out = seg(
                            w["params"], w["opt"], w["topo"], x_all, y_all,
                            perm, lrs, w["key"],
                        )
                        if probing:
                            w["params"], w["opt"], w["key"], ls, p2_probe = out
                        else:
                            w["params"], w["opt"], w["key"], ls = out
                        losses.append(ls)
                        # per-worker evolution (divergent topologies)
                        w["key"], sub = jax.random.split(w["key"])
                        if probing:
                            w["topo"], vals, vel, pruned = (
                                evolve_element_layers_device(
                                    w["topo"], list(w["params"]["values"]),
                                    list(w["opt"].velocity["values"]), sub,
                                    layer_dims=cfg.layer_dims, zeta=wc.zeta,
                                    init_scheme=cfg.init, probe=True,
                                )
                            )
                            p2_churn = (
                                pruned,
                                [int(t.rows.shape[0]) for t in w["topo"]],
                            )
                        else:
                            w["topo"], vals, vel = evolve_element_layers_device(
                                w["topo"], list(w["params"]["values"]),
                                list(w["opt"].velocity["values"]), sub,
                                layer_dims=cfg.layer_dims, zeta=wc.zeta,
                                init_scheme=cfg.init,
                            )
                        w["params"] = {
                            "values": tuple(vals),
                            "biases": w["params"]["biases"],
                        }
                        w["opt"] = replace_values_velocity(w["opt"], vel)
                    ws_sp.block_on([w["params"] for w in workers])
                jax.block_until_ready([w["params"] for w in workers])
                dt = time.perf_counter() - t0
                loss = float(np.mean([np.asarray(l).mean() for l in losses]))
                # host-side recording after the block (§11 obs-in-jit)
                if p2_probe is not None:
                    churn = None
                    if p2_churn is not None:
                        counts, nnz = p2_churn
                        churn = [
                            float(c) / max(1, n)
                            for c, n in zip(np.asarray(counts), nnz)
                        ]
                    probes.record_snapshot(
                        (epoch + 1) * steps_per_epoch, "wasap", p2_probe,
                        churn=churn,
                        extra={"epoch": epoch, "phase": 2, "loss": loss},
                    )
                ep_sp.set(loss=loss)
                self._log(epoch, 2, loss, dt, float("nan"))
                self._p2_workers = workers
                self.epoch_next = epoch + 1
                if self.epoch_end_hook is not None:
                    self.epoch_end_hook(self, epoch)
        out = []
        for w in workers:
            topos = [
                ElementTopology(
                    cfg.layer_dims[l], cfg.layer_dims[l + 1],
                    np.asarray(t.rows), np.asarray(t.cols),
                )
                for l, t in enumerate(w["topo"])
            ]
            vals = [np.asarray(v, np.float32) for v in w["params"]["values"]]
            out.append((topos, vals, list(w["params"]["biases"])))
        return out

    def _run_phase2_perbatch(self) -> List[tuple]:
        """Seed-era phase 2: per-batch dispatch + host numpy evolution."""
        wc, model = self.wc, self.model
        cfg = model.config
        k = wc.n_workers
        worker_models = []
        for wk in range(k):
            m = SparseMLP(cfg, seed=wc.seed)  # structure placeholder
            m.topos = [t for t in model.topos]
            m.values = [v for v in model.values]
            m.biases = [b for b in model.biases]
            worker_models.append(m)
        worker_opt = [self.opt.init(m.params()) for m in worker_models]
        worker_rngs = [np.random.default_rng(wc.seed * 97 + 13 * wk) for wk in range(k)]
        step_fn = make_step_fn(cfg, self.opt)
        for epoch in range(wc.phase1_epochs, wc.phase1_epochs + wc.phase2_epochs):
            t0 = time.perf_counter()
            losses = []
            for wk in range(k):
                m = worker_models[wk]
                params = m.params()
                topo = m.topo_arrays()
                ostate = worker_opt[wk]
                for xb, yb in self.loaders[wk].epoch(epoch):
                    self.key, sub = jax.random.split(self.key)
                    params, ostate, loss = step_fn(
                        params, ostate, topo,
                        jnp.asarray(xb), jnp.asarray(yb),
                        jnp.asarray(wc.lr, jnp.float32), sub,
                    )
                    losses.append(float(loss))
                m.set_params(params)
                # per-worker evolution (divergent topologies)
                vel = list(ostate.velocity["values"])
                for l in range(cfg.n_layers):
                    res = evolve_element(
                        m.topos[l],
                        np.asarray(m.values[l], np.float32),
                        wc.zeta,
                        worker_rngs[wk],
                        momentum=np.asarray(vel[l], np.float32),
                        init_scheme=cfg.init,
                    )
                    m.topos[l] = res.topology
                    m.values[l] = jnp.asarray(res.values)
                    vel[l] = jnp.asarray(res.momentum)
                worker_opt[wk] = replace_values_velocity(ostate, vel)
            dt = time.perf_counter() - t0
            self._log(epoch, 2, float(np.mean(losses)) if losses else float("nan"),
                      dt, float("nan"))
        return [
            (
                list(m.topos),
                [np.asarray(v, np.float32) for v in m.values],
                list(m.biases),
            )
            for m in worker_models
        ]

    # -- final: SWA + re-sparsify --------------------------------------------

    def _merge_workers(self, worker_states: List[tuple]) -> None:
        model = self.model
        cfg = model.config
        target_nnz = [t.nnz for t in model.topos]
        for l in range(cfg.n_layers):
            topo, vals = sparse_average_and_resparsify(
                [ws[0][l] for ws in worker_states],
                [ws[1][l] for ws in worker_states],
                target_nnz[l],
            )
            model.topos[l] = topo
            model.values[l] = jnp.asarray(vals)
            model.biases[l] = jnp.mean(
                jnp.stack([ws[2][l] for ws in worker_states]), axis=0
            )

    # -- elasticity (DESIGN.md §8) -------------------------------------------

    def _weighted_epoch_fn(self):
        """Weighted-average variant of the phase-1 epoch, built (and jitted)
        only when a heartbeat monitor is attached — the unweighted program
        keeps its exact float reduction order otherwise."""
        if self._epoch_fn_weighted is None:
            self._epoch_fn_weighted = make_phase1_epoch_fn(
                self.model.config, self.opt,
                n_workers=self.wc.n_workers,
                average_momentum=self.wc.average_momentum,
                worker_axis=self.wc.worker_axis,
                mesh=self._mesh,
                weighted=True,
            )
        return self._epoch_fn_weighted

    def _worker_weights(self, epoch: int) -> np.ndarray:
        """One heartbeat interval per epoch: deliver the beats that arrived
        (``beat_filter`` suppresses an injected straggler's), tick the
        monitor, and weight the round's average 1/0 by liveness. The weights
        are renormalized inside ``_average_pytree``, so the round completes
        elastically over the survivors — the evicted worker's shard still
        trains (its replica exists on device) but contributes nothing."""
        k = self.wc.n_workers
        mon = self.monitor
        for wk in range(k):
            wid = f"w{wk}"
            if wid in mon.evicted:
                continue
            if self.beat_filter is None or self.beat_filter(wid, epoch):
                mon.beat(wid)
        status = mon.tick()
        weights = np.asarray(
            [
                1.0
                if status.get(f"w{wk}", "healthy") in ("healthy", "straggling")
                else 0.0
                for wk in range(k)
            ],
            np.float32,
        )
        if weights.sum() == 0:
            raise RuntimeError(
                "every WASAP worker is dead/evicted — the round cannot "
                "complete elastically"
            )
        self.elastic_log.append(
            {
                "epoch": epoch,
                "status": {f"w{wk}": status.get(f"w{wk}") for wk in range(k)},
                "weights": weights.tolist(),
            }
        )
        return weights

    # -- resume (DESIGN.md §8) ------------------------------------------------

    def save_checkpoint(self, manager) -> None:
        """Phase-aware epoch-boundary snapshot for the fused path. Phase 1
        saves the averaged master (params + velocity + topology); phase 2
        additionally saves every worker replica (params/velocity/topology/
        PRNG key) as extra groups, since the replicas have diverged. Both
        carry the trainer's PRNG streams and history, so a restore replays
        the remaining epochs bit-exactly."""
        if not self._fused:
            raise RuntimeError(
                "WASAP checkpointing covers the fused path; the seed-era "
                "round loop is a measured baseline, not a production path"
            )
        cfg = self.model.config
        resume = {
            "kind": "wasap",
            "phase": self._phase,
            "epoch_next": int(self.epoch_next),
            "jax_key": np.asarray(self.key).tolist(),
            "numpy_rng": self.rng.bit_generator.state,
            "history": self.history,
        }

        def topo_entry(topo_l):
            return {
                "rows": np.asarray(topo_l.rows),
                "cols": np.asarray(topo_l.cols),
            }

        if self._phase == 1 and self._p1_state is not None:
            params, opt_state, topo = self._p1_state
            resume["opt_step"] = int(opt_state.step)
            manager.save(
                self.epoch_next,
                params,
                extra={"velocity": opt_state.velocity},
                topologies={
                    f"layer{l}": topo_entry(topo[l])
                    for l in range(cfg.n_layers)
                },
                meta={"resume": resume},
            )
            return
        # phase 2 (or the phase boundary itself): master + worker replicas
        topologies = {
            f"layer{l}": topo_entry(self.model.topos[l])
            for l in range(cfg.n_layers)
        }
        extra = {}
        worker_keys, worker_opt_steps = [], []
        for wk, w in enumerate(self._p2_workers or []):
            extra[f"w{wk}_params"] = w["params"]
            extra[f"w{wk}_velocity"] = w["opt"].velocity
            worker_keys.append(np.asarray(w["key"]).tolist())
            worker_opt_steps.append(int(w["opt"].step))
            for l in range(cfg.n_layers):
                topologies[f"w{wk}_layer{l}"] = topo_entry(w["topo"][l])
        resume["phase"] = 2
        resume["n_saved_workers"] = len(worker_keys)
        resume["worker_keys"] = worker_keys
        resume["worker_opt_steps"] = worker_opt_steps
        manager.save(
            self.epoch_next,
            self.model.params(),
            extra=extra,
            topologies=topologies,
            meta={"resume": resume},
        )

    def restore_checkpoint(self, manager, step=None) -> int:
        """Rewind to a saved epoch boundary (newest *valid* checkpoint by
        default — corrupt ones are quarantined by the scan); ``run()`` then
        continues from the saved phase and epoch."""
        from repro.train.trainer import _params_like

        if step is None:
            step = manager.latest_valid_step()
            if step is None:
                raise FileNotFoundError(f"no valid checkpoints under {manager.dir}")
        manifest = manager.read_manifest(step)
        res = manifest["meta"]["resume"]
        cfg = self.model.config
        k = self.wc.n_workers
        like = _params_like(manifest["shapes"], cfg.n_layers)

        def layer_topo(l, entry) -> ElementTopology:
            return ElementTopology(
                cfg.layer_dims[l], cfg.layer_dims[l + 1],
                entry["rows"], entry["cols"],
            )

        if res["phase"] == 1:
            params, extra, topologies, _ = manager.restore(
                step, like=like, like_extra={"velocity": like}
            )
            topo = tuple(
                layer_topo(l, topologies[f"layer{l}"]).device_arrays()
                for l in range(cfg.n_layers)
            )
            self._p1_state = (
                jax.tree.map(jnp.asarray, params),
                SGDState(
                    velocity=jax.tree.map(jnp.asarray, extra["velocity"]),
                    step=jnp.asarray(res["opt_step"], jnp.int32),
                ),
                topo,
            )
            self._phase = 1
        else:
            n_saved = int(res.get("n_saved_workers", k))
            like_extra = {}
            for wk in range(n_saved):
                like_extra[f"w{wk}_params"] = like
                like_extra[f"w{wk}_velocity"] = like
            params, extra, topologies, _ = manager.restore(
                step, like=like, like_extra=like_extra
            )
            for l in range(cfg.n_layers):
                self.model.topos[l] = layer_topo(l, topologies[f"layer{l}"])
            self.model.set_params(jax.tree.map(jnp.asarray, params))
            workers = []
            for wk in range(n_saved):
                workers.append({
                    "params": jax.tree.map(jnp.asarray, extra[f"w{wk}_params"]),
                    "opt": SGDState(
                        velocity=jax.tree.map(
                            jnp.asarray, extra[f"w{wk}_velocity"]
                        ),
                        step=jnp.asarray(res["worker_opt_steps"][wk], jnp.int32),
                    ),
                    "topo": tuple(
                        layer_topo(l, topologies[f"w{wk}_layer{l}"])
                        .device_arrays()
                        for l in range(cfg.n_layers)
                    ),
                    "key": jnp.asarray(res["worker_keys"][wk], jnp.uint32),
                })
            self._p2_workers = workers if workers else None
            self._phase = 2
        self.key = jnp.asarray(res["jax_key"], jnp.uint32)
        self.rng.bit_generator.state = res["numpy_rng"]
        self.start_epoch = self.epoch_next = int(res["epoch_next"])
        self.history = {k2: list(v) for k2, v in res["history"].items()}
        return step

    # -- helpers --------------------------------------------------------------

    def _evolve_master_device(self, topo, params, opt_state, key):
        cfg, wc = self.model.config, self.wc
        if wc.probe:
            topo, values, vel, pruned = evolve_element_layers_device(
                topo, list(params["values"]),
                list(opt_state.velocity["values"]), key,
                layer_dims=cfg.layer_dims, zeta=wc.zeta,
                init_scheme=cfg.init, probe=True,
            )
            self._last_churn = (
                pruned, [int(t.rows.shape[0]) for t in topo]
            )
        else:
            topo, values, vel = evolve_element_layers_device(
                topo, list(params["values"]),
                list(opt_state.velocity["values"]), key,
                layer_dims=cfg.layer_dims, zeta=wc.zeta, init_scheme=cfg.init,
            )
        params = {"values": tuple(values), "biases": params["biases"]}
        return topo, params, replace_values_velocity(opt_state, vel)

    def _sync_topos_to_host(self, topo) -> None:
        cfg = self.model.config
        for l in range(cfg.n_layers):
            self.model.topos[l] = ElementTopology(
                cfg.layer_dims[l], cfg.layer_dims[l + 1],
                np.asarray(topo[l].rows), np.asarray(topo[l].cols),
            )

    def _evolve_master(self, opt_state: SGDState) -> None:
        model, wc = self.model, self.wc
        cfg = model.config
        vel = list(opt_state.velocity["values"])
        for l in range(cfg.n_layers):
            res = evolve_element(
                model.topos[l],
                np.asarray(model.values[l], np.float32),
                wc.zeta,
                self.rng,
                momentum=np.asarray(vel[l], np.float32),
                init_scheme=cfg.init,
            )
            model.topos[l] = res.topology
            model.values[l] = jnp.asarray(res.values)
            vel[l] = jnp.asarray(res.momentum)
        self._realigned_opt_state = replace_values_velocity(opt_state, vel)

    def _log(self, epoch, phase, loss, dt, acc) -> None:
        self.history["epoch"].append(epoch)
        self.history["phase"].append(phase)
        self.history["train_loss"].append(loss)
        self.history["test_acc"].append(acc)
        self.history["n_params"].append(self.model.n_params)
        self.history["epoch_seconds"].append(dt)


# ---------------------------------------------------------------------------
# contract auditor registration (repro.analysis, DESIGN.md §10)
# ---------------------------------------------------------------------------


def analysis_programs():
    """Registry hook: the phase-1 fused epoch (K vmapped workers, scan over
    sync rounds). The audit model pins ``element_impl="custom"`` so the
    structural checks exercise the custom-VJP kernels even at the tiny
    audit scale (below the auto-dispatch nnz threshold)."""
    from repro.analysis.registry import AuditProgram, Contract, ProgramSpec

    dims = (20, 16, 10)
    K, R, H, B = 2, 2, 2, 8

    def build() -> AuditProgram:
        cfg = SparseMLPConfig(
            layer_dims=dims, epsilon=6, dropout=0.0, element_impl="custom"
        )
        model = SparseMLP(cfg, seed=0)
        opt = MomentumSGD(momentum=0.9, weight_decay=2e-4)
        n_train = R * H * B
        args = (
            model.params(),
            opt.init(model.params()),
            model.topo_arrays(),
            jnp.zeros((n_train, dims[0]), jnp.float32),
            jnp.zeros((n_train,), jnp.int32),
            jnp.arange(R * K * H * B, dtype=jnp.int32).reshape(R, K, H, B)
            % n_train,
            jnp.full((R, H), 0.01, jnp.float32),
            jnp.ones((R, H), jnp.float32),
            jnp.zeros((R, K, 2), jnp.uint32),
        )
        nnz = [int(t.rows.shape[0]) for t in model.topos]
        return AuditProgram(
            make=lambda donate: make_phase1_epoch_fn(
                cfg, opt, n_workers=K, donate=donate
            ),
            args=args,
            meta={"dims": dims, "workers": K, "rounds": R, "nnz": nnz},
        )

    return [
        ProgramSpec(
            name="wasap.phase1_epoch",
            subsystem=__name__,
            contract=Contract(
                # one CE-loss label scatter, batched over the K worker vmap
                max_unsorted_scatter=1,
                max_unsorted_scatter_elems=K * B * dims[-1],
                max_intermediate_elems=256 * 1024,
                donate_argnums=(0, 1),
                max_temp_bytes=4 * 1024 * 1024,
                expected_compiles=1,
            ),
            build=build,
            notes="K-worker vmapped local SGD + on-device average per round",
        )
    ]
