"""Sequential SET trainer — paper Algorithm 2 (SET + Importance Pruning).

Two execution modes (``TrainerConfig.fused_epochs``):

* **Fused (default, DESIGN.md §3)** — an epoch is ONE jitted, buffer-donated
  device call: the training set lives on the device, the host ships only the
  epoch's shuffled index permutation, and a ``lax.scan`` (launch.steps.
  scan_segment) runs every minibatch step inside the call. Between segments
  the SET prune/regrow cycle also runs jitted on fixed-capacity topology
  arrays (``core.topology.evolve_*_device``), so a whole training run does a
  handful of dispatches per epoch and zero host<->device parameter traffic.
  The host topology mirror is re-synchronised lazily — only when importance
  pruning fires (a genuine shape change, recompiling at most once per event)
  and at the end of the run.
* **Per-batch (legacy)** — one jitted call per minibatch, evolution on the
  host (numpy) every epoch. Kept as the dispatch-bound baseline for the
  ``benchmarks/`` epoch-segment comparison and as the fallback for layers
  whose flat-position encoding exceeds int32.

Per epoch, both modes: jitted momentum-SGD minibatch steps, then
  1. Importance Pruning (if schedule fires): remove weak hidden neurons'
     incoming connections, cascade-remove their outgoing connections, shrink
     the arrays (a recompile happens at most once per pruning event).
  2. SET weight pruning-regrowing cycle (zeta tail by magnitude, random
     regrowth), keeping nnz constant; momentum is remapped (kept for
     surviving connections, reset on regrown ones).

Works with element (paper-faithful) and block (TPU) sparsity, plus the
masked/dense baselines (which simply skip topology ops they do not support).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.importance import (
    PruningSchedule,
    importance_prune_block,
    importance_prune_element,
)
from repro.core.sparsity import (
    BlockMeta,
    BlockTopology,
    ElementTopology,
)
from repro.core.topology import (
    block_device_arrays,
    evolve_block,
    evolve_block_device,
    evolve_element,
    evolve_element_layers_device,
)
from repro.data.loader import ShardedLoader
from repro.data.synthetic import Dataset
from repro.launch.steps import make_mlp_step_core, make_mlp_train_step, scan_segment
from repro.models.mlp import (
    SparseMLP,
    SparseMLPConfig,
    cross_entropy_loss,
    mlp_forward,
)
from repro.optim.sgd import MomentumSGD, SGDState, replace_values_velocity
from repro.runtime import donation
from repro.runtime.supervisor import retry_step
from repro import obs
from repro.obs import probes

__all__ = [
    "TrainerConfig",
    "SequentialTrainer",
    "XLTrainer",
    "evaluate",
    "make_step_fn",
    "make_eval_fn",
    "make_segment_fn",
]


@dataclasses.dataclass
class TrainerConfig:
    epochs: int = 10
    batch_size: int = 128
    lr: float = 0.01
    momentum: float = 0.9
    weight_decay: float = 2e-4
    zeta: float = 0.3
    evolve: bool = True
    pruning: Optional[PruningSchedule] = None
    eval_every: int = 1
    seed: int = 0
    lr_schedule: Optional[Callable] = None
    fused_epochs: bool = True  # one scan-based device call per epoch
    device_evolution: bool = True  # jitted SET evolution between segments
    probe: bool = False  # training-dynamics probes (obs.probes, §12)


def make_step_fn(config: SparseMLPConfig, opt: MomentumSGD):
    """Single-minibatch jitted step — shared with the kernels micro-benchmark
    via ``launch.steps.make_mlp_train_step``."""
    return make_mlp_train_step(config, opt)


def make_segment_program(
    config: SparseMLPConfig, opt: MomentumSGD, probe: bool = False
):
    """The un-jitted epoch-segment program. Exposed separately so the
    contract auditor (DESIGN.md §10) can build fresh jitted variants —
    donated for the aliasing check, undonated for trace/compile probes —
    without touching the lru-cached production jit below.

    ``probe`` is a static python flag (DESIGN.md §12): ``False`` emits the
    exact pre-probe program — the branch below is never traced, so the
    compiled HLO is byte-identical to a build without this feature.
    ``True`` appends ONE extra forward/backward on the segment's last
    half-batch plus the O(n_layers) ``obs.probes.segment_probe``
    reductions, and returns ``(..., losses, probe_stats)``.
    """

    def segment(params, opt_state, topo_arrays, x_all, y_all, perm, lrs, key):
        step_core = make_mlp_step_core(config, opt, topo_arrays, x_all, y_all)
        out = scan_segment(step_core, params, opt_state, key, (perm, lrs))
        if not probe:
            return out
        params2, opt_state2, key2, losses = out
        # probe batch: half of the last minibatch — the stats want post-
        # segment weights, and a half batch keeps the marginal cost of the
        # extra fwd+bwd well under the 2% obs budget at ~any steps/epoch
        n_probe = max(1, perm.shape[1] // 2)
        xb = jnp.take(x_all, perm[-1, :n_probe], axis=0, mode="clip")
        yb = jnp.take(y_all, perm[-1, :n_probe], axis=0, mode="clip")

        def probe_loss(p):
            logits, preacts = mlp_forward(
                p, topo_arrays, xb, config, train=False, return_preacts=True
            )
            return cross_entropy_loss(logits, yb), preacts

        (_, preacts), grads = jax.value_and_grad(probe_loss, has_aux=True)(
            params2
        )
        stats = probes.segment_probe(
            params2, grads, topo_arrays, preacts, config.layer_dims
        )
        return params2, opt_state2, key2, losses, stats

    return segment


@functools.lru_cache(maxsize=32)
def make_segment_fn(
    config: SparseMLPConfig, opt: MomentumSGD, probe: bool = False
):
    """Jitted multi-minibatch epoch segment.

    ``segment(params, opt_state, topo_arrays, x_all, y_all, perm, lrs, key)``
    gathers the epoch's batches from the device-resident dataset by the
    (steps, batch) index permutation and runs them all inside one
    ``lax.scan``; params/opt_state buffers are donated per the central
    policy (``repro.runtime.donation``) so the optimizer state never leaves
    the device. Cached per (model config, optimizer) so repeated trainers
    share the jit cache.

    Call with the default two arguments for the production program;
    probe-enabled callers pass ``probe=True`` explicitly. (Never pass an
    explicit ``False`` — it is a distinct lru_cache key and would compile
    the default program twice.)
    """
    return jax.jit(
        make_segment_program(config, opt, probe),
        donate_argnums=donation.donate_argnums(0, 1),
    )


@functools.lru_cache(maxsize=64)
def make_eval_fn(config: SparseMLPConfig):
    """Cached per config: repeated ``evaluate`` calls (one per epoch) reuse
    the same jitted forward instead of re-tracing every time."""

    @jax.jit
    def fwd(params, topo_arrays, x):
        return mlp_forward(params, topo_arrays, x, config, train=False)

    return fwd


def evaluate(
    model: SparseMLP,
    x: np.ndarray,
    y: np.ndarray,
    batch: int = 512,
    *,
    params=None,
    topo_arrays=None,
) -> float:
    """Accuracy on (x, y). ``params``/``topo_arrays`` override the model's
    host-side views — the fused trainer passes its device-resident state so
    evaluation needs no host synchronisation."""
    fwd = make_eval_fn(model.config)
    params = model.params() if params is None else params
    topo = model.topo_arrays() if topo_arrays is None else topo_arrays
    correct = 0
    for s in range(0, x.shape[0], batch):
        logits = fwd(params, topo, jnp.asarray(x[s : s + batch]))
        correct += int((np.argmax(np.asarray(logits), -1) == y[s : s + batch]).sum())
    return correct / x.shape[0]


def _params_like(shapes: Dict, n_layers: int):
    """Zero pytree in the trainer's params structure with the *checkpoint's*
    leaf shapes — topology evolution means the live model's shapes need not
    match the saved ones, so restore targets come from the manifest."""

    def leaf(name):
        shape, dtype = shapes[name]
        return np.zeros(tuple(shape), np.dtype(dtype))

    return {
        "values": tuple(leaf(f"values__{l}") for l in range(n_layers)),
        "biases": tuple(leaf(f"biases__{l}") for l in range(n_layers)),
    }


class SequentialTrainer:
    """Paper §2.2 protocol (1 worker). History mirrors Table 2 columns."""

    def __init__(self, model: SparseMLP, data: Dataset, tc: TrainerConfig):
        self.model = model
        self.data = data
        self.tc = tc
        self.opt = MomentumSGD(momentum=tc.momentum, weight_decay=tc.weight_decay)
        self.opt_state = self.opt.init(model.params())
        self.rng = np.random.default_rng(tc.seed)
        self.key = jax.random.PRNGKey(tc.seed)
        self._step = make_step_fn(model.config, self.opt)
        self._segment = make_segment_fn(model.config, self.opt)
        # probe variant built only when asked for: with probe off this
        # trainer holds exactly the pre-probe jit surface
        self._probe_segment = (
            make_segment_fn(model.config, self.opt, True) if tc.probe
            else None
        )
        self._last_churn = None  # per-layer churn fracs from the last evolve
        self.history: Dict[str, List] = {
            "epoch": [], "train_loss": [], "test_acc": [], "n_params": [],
            "epoch_seconds": [],
        }
        self.start_params = model.n_params
        # -- resume / fault-tolerance surface (DESIGN.md §8) ----------------
        # counters advance as the run progresses; restore_checkpoint rewinds
        # them to an epoch boundary and run() continues from there.
        self.start_epoch = 0          # first epoch run() will execute
        self.epoch_next = 0           # next epoch at the last boundary
        self.gstep = 0                # global minibatch counter
        self.fault_hook: Optional[Callable[[int], None]] = None
        self.epoch_end_hook: Optional[Callable] = None  # hook(trainer, epoch)
        self.step_retries = 0         # retry_step wrap when > 0
        self.retry_backoff_s = 0.0

    # -- host-side topology mutations --------------------------------------

    def _importance_prune(self, epoch: int) -> None:
        tc, model = self.tc, self.model
        if tc.pruning is None or not tc.pruning.should_prune(epoch):
            return
        cfg = model.config
        if cfg.impl not in ("element", "block"):
            return
        vel = list(self.opt_state.velocity["values"])
        pruned_prev: Optional[np.ndarray] = None
        for l in range(cfg.n_layers):
            topo = model.topos[l]
            vals = np.asarray(model.values[l], np.float32)
            mom = np.asarray(vel[l], np.float32)
            # cascade: connections out of previously-pruned neurons die too
            if pruned_prev is not None and pruned_prev.size and cfg.impl == "element":
                keep = ~np.isin(topo.rows, pruned_prev)
                topo = ElementTopology(
                    topo.in_dim, topo.out_dim, topo.rows[keep], topo.cols[keep]
                )
                vals, mom = vals[keep], mom.reshape(-1)[keep]
            if l == cfg.n_layers - 1:
                # output units are protected — only apply the cascade
                model.topos[l] = topo
                model.values[l] = jnp.asarray(vals)
                vel[l] = jnp.asarray(mom)
                pruned_prev = None
                continue
            fn = (
                importance_prune_element
                if cfg.impl == "element"
                else importance_prune_block
            )
            res = fn(topo, vals, tc.pruning, momentum=mom)
            model.topos[l] = res.topology
            model.values[l] = jnp.asarray(res.values)
            vel[l] = jnp.asarray(res.momentum)
            pruned_prev = res.pruned_neurons
        self.opt_state = replace_values_velocity(self.opt_state, vel)

    def _evolve(self) -> None:
        tc, model = self.tc, self.model
        cfg = model.config
        if not tc.evolve or cfg.impl not in ("element", "block"):
            return
        vel = list(self.opt_state.velocity["values"])
        for l in range(cfg.n_layers):
            vals = np.asarray(model.values[l], np.float32)
            mom = np.asarray(vel[l], np.float32)
            if cfg.impl == "element":
                res = evolve_element(
                    model.topos[l], vals, tc.zeta, self.rng, momentum=mom,
                    init_scheme=cfg.init,
                )
            else:
                res = evolve_block(
                    model.topos[l], vals, tc.zeta, self.rng, momentum=mom
                )
            model.topos[l] = res.topology
            model.values[l] = jnp.asarray(res.values, model.values[l].dtype)
            vel[l] = jnp.asarray(res.momentum)
        self.opt_state = replace_values_velocity(self.opt_state, vel)

    # -- device-side topology mutations -------------------------------------

    def _evolve_device(self, topo, params, opt_state):
        """Jitted SET evolution for every layer; returns the new device
        (topo_arrays, params, opt_state) without touching the host mirror."""
        tc, cfg = self.tc, self.model.config
        values = list(params["values"])
        vel = list(opt_state.velocity["values"])
        if cfg.impl == "element":
            # shared with the WASAP master evolution: dual-order views are
            # rebuilt on-device so the custom-VJP backward never sees a
            # stale permutation after connections move
            self.key, sub = jax.random.split(self.key)
            if tc.probe:
                new_topo, values, vel, pruned = evolve_element_layers_device(
                    topo, values, vel, sub,
                    layer_dims=cfg.layer_dims, zeta=tc.zeta,
                    init_scheme=cfg.init, probe=True,
                )
                self._last_churn = (
                    pruned, [int(t.rows.shape[0]) for t in new_topo]
                )
            else:
                new_topo, values, vel = evolve_element_layers_device(
                    topo, values, vel, sub,
                    layer_dims=cfg.layer_dims, zeta=tc.zeta,
                    init_scheme=cfg.init,
                )
        else:
            new_topo = list(topo)
            pruned_counts = []
            for l in range(cfg.n_layers):
                self.key, sub = jax.random.split(self.key)
                meta = BlockMeta(
                    cfg.layer_dims[l], cfg.layer_dims[l + 1],
                    cfg.block_m, cfg.block_n,
                )
                rows, cols, vals, mom, n_drop = evolve_block_device(
                    topo[l].rows, topo[l].cols, values[l], vel[l], sub,
                    meta=meta, zeta=tc.zeta,
                )
                new_topo[l] = block_device_arrays(rows, cols, meta=meta)
                values[l] = vals
                vel[l] = mom
                pruned_counts.append(n_drop)
            if tc.probe:
                self._last_churn = (
                    pruned_counts,
                    [int(t.rows.shape[0]) for t in new_topo],
                )
        params = {"values": tuple(values), "biases": params["biases"]}
        return tuple(new_topo), params, replace_values_velocity(opt_state, vel)

    def _sync_topology_to_host(self, topo) -> None:
        """Pull device topology back into the host mirror (model.topos) —
        needed only before host-side ops (importance pruning) and at the end
        of a fused run."""
        cfg = self.model.config
        for l in range(cfg.n_layers):
            n_in, n_out = cfg.layer_dims[l], cfg.layer_dims[l + 1]
            if cfg.impl == "element":
                self.model.topos[l] = ElementTopology(
                    n_in, n_out,
                    np.asarray(topo[l].rows), np.asarray(topo[l].cols),
                )
            elif cfg.impl == "block":
                meta = BlockMeta(n_in, n_out, cfg.block_m, cfg.block_n)
                self.model.topos[l] = BlockTopology(
                    meta, np.asarray(topo[l].rows), np.asarray(topo[l].cols)
                )

    def _host_topology_op(self, topo, topo_dirty: bool, op):
        """Run a host-side topology mutation from a fused run: re-sync the
        host mirror if the device topology has diverged, apply ``op`` (which
        mutates model/opt_state), and return the refreshed device views."""
        if topo_dirty:
            self._sync_topology_to_host(topo)
        op()
        return self.model.params(), self.opt_state, self.model.topo_arrays()

    def _supports_device_evolution(self) -> bool:
        # the device paths encode flat positions in int32
        cfg = self.model.config
        if cfg.impl == "element":
            return all(
                cfg.layer_dims[l] * cfg.layer_dims[l + 1] < 2**31
                for l in range(cfg.n_layers)
            )
        if cfg.impl == "block":
            return all(
                BlockMeta(
                    cfg.layer_dims[l], cfg.layer_dims[l + 1],
                    cfg.block_m, cfg.block_n,
                ).total_blocks < 2**31
                for l in range(cfg.n_layers)
            )
        return False

    # -- resume (DESIGN.md §8) ----------------------------------------------

    def save_checkpoint(self, manager) -> None:
        """Epoch-boundary snapshot carrying the *full* resume state: params,
        velocity, topology, epoch/step counters, both PRNG streams and the
        history so far. Restoring it and running the remaining epochs yields
        the same trajectory as the uninterrupted run, bit-exactly — every
        source of randomness (data order, dropout/evolution keys, regrowth
        draws) is derived from state saved here."""
        model, cfg = self.model, self.model.config
        topologies = None
        if cfg.impl in ("element", "block"):
            topologies = {
                f"layer{l}": {
                    "rows": np.asarray(model.topos[l].rows),
                    "cols": np.asarray(model.topos[l].cols),
                }
                for l in range(cfg.n_layers)
            }
        meta = {
            "kind": "sequential",
            "resume": {
                "epoch_next": int(self.epoch_next),
                "gstep": int(self.gstep),
                "jax_key": np.asarray(self.key).tolist(),
                "numpy_rng": self.rng.bit_generator.state,
                "opt_step": int(self.opt_state.step),
                "history": self.history,
                "seed": self.tc.seed,
            },
        }
        manager.save(
            self.gstep,
            model.params(),
            extra={"velocity": self.opt_state.velocity},
            topologies=topologies,
            meta=meta,
        )

    def restore_checkpoint(self, manager, step: Optional[int] = None) -> int:
        """Rewind the trainer to a saved epoch boundary; defaults to the
        newest checkpoint that passes integrity verification (corrupt ones
        are quarantined by the scan). Returns the restored step."""
        if step is None:
            step = manager.latest_valid_step()
            if step is None:
                raise FileNotFoundError(f"no valid checkpoints under {manager.dir}")
        manifest = manager.read_manifest(step)
        res = manifest["meta"]["resume"]
        cfg = self.model.config
        like = _params_like(manifest["shapes"], cfg.n_layers)
        params, extra, topologies, _ = manager.restore(
            step, like=like, like_extra={"velocity": like}
        )
        # topology first: the restored value shapes follow the saved topology
        # (SET keeps nnz constant but importance pruning shrinks it)
        if cfg.impl in ("element", "block"):
            for l in range(cfg.n_layers):
                t = topologies[f"layer{l}"]
                n_in, n_out = cfg.layer_dims[l], cfg.layer_dims[l + 1]
                if cfg.impl == "element":
                    self.model.topos[l] = ElementTopology(
                        n_in, n_out, t["rows"], t["cols"]
                    )
                else:
                    bm = BlockMeta(n_in, n_out, cfg.block_m, cfg.block_n)
                    self.model.topos[l] = BlockTopology(bm, t["rows"], t["cols"])
        self.model.set_params(jax.tree.map(jnp.asarray, params))
        self.opt_state = SGDState(
            velocity=jax.tree.map(jnp.asarray, extra["velocity"]),
            step=jnp.asarray(res["opt_step"], jnp.int32),
        )
        self.key = jnp.asarray(res["jax_key"], jnp.uint32)
        self.rng.bit_generator.state = res["numpy_rng"]
        self.start_epoch = self.epoch_next = int(res["epoch_next"])
        self.gstep = int(res["gstep"])
        self.history = {k: list(v) for k, v in res["history"].items()}
        return step

    # -- main loop -----------------------------------------------------------

    def run(self, log_every: int = 0) -> Dict[str, List]:
        mode = "fused" if self.tc.fused_epochs else "per_batch"
        with obs.span(
            "train.run", mode=mode, epochs=self.tc.epochs,
            start_epoch=self.start_epoch,
        ):
            if self.tc.fused_epochs:
                return self._run_fused(log_every)
            return self._run_per_batch(log_every)

    def _run_fused(self, log_every: int) -> Dict[str, List]:
        tc, model = self.tc, self.model
        cfg = model.config
        loader = ShardedLoader(
            self.data.x_train, self.data.y_train, tc.batch_size, seed=tc.seed
        )
        steps = loader.steps_per_epoch
        if steps == 0:
            raise ValueError("batch_size larger than the training shard")
        lr_fn = tc.lr_schedule or (lambda step: tc.lr)
        x_all = jnp.asarray(self.data.x_train)
        y_all = jnp.asarray(self.data.y_train)
        params = model.params()
        opt_state = self.opt_state
        topo = model.topo_arrays()
        sparse_impl = cfg.impl in ("element", "block")
        device_evo = (
            tc.evolve
            and tc.device_evolution
            and sparse_impl
            and self._supports_device_evolution()
        )
        topo_dirty = False  # device topology has diverged from model.topos
        gstep = self.gstep
        for epoch in range(self.start_epoch, tc.epochs):
            with obs.span("train.epoch", epoch=epoch) as ep_sp:
                t0 = time.perf_counter()
                perm = jnp.asarray(
                    loader.epoch_order(epoch).astype(np.int32).reshape(
                        steps, tc.batch_size
                    )
                )
                lrs = jnp.asarray(
                    [float(lr_fn(gstep + i)) for i in range(steps)], jnp.float32
                )

                def run_segment():
                    # the fault hook (kill switch / transient injector) fires
                    # before the device call, so a retry re-enters cleanly —
                    # the segment itself is pure in its inputs
                    if self.fault_hook is not None:
                        self.fault_hook(gstep)
                    seg = (
                        self._probe_segment
                        if self._probe_segment is not None else self._segment
                    )
                    return seg(
                        params, opt_state, topo, x_all, y_all, perm, lrs,
                        self.key
                    )

                # jitted-call boundary: the span times the dispatch and
                # blocks on nothing (the loop first blocks after evolution
                # is dispatched); the segment's device time is read from
                # the profiler trace's device lines
                with obs.span("train.segment", steps=steps):
                    if self.step_retries:
                        out = retry_step(
                            run_segment,
                            retries=self.step_retries,
                            backoff_s=self.retry_backoff_s,
                        )
                    else:
                        out = run_segment()
                    if tc.probe:
                        params, opt_state, self.key, losses, probe_dev = out
                    else:
                        params, opt_state, self.key, losses = out
                        probe_dev = None
                gstep += steps
                model.set_params(params)
                self.opt_state = opt_state
                # -- topology phase --
                fire_pruning = (
                    sparse_impl
                    and tc.pruning is not None
                    and tc.pruning.should_prune(epoch)
                )
                if fire_pruning:
                    params, opt_state, topo = self._host_topology_op(
                        topo, topo_dirty, lambda: self._importance_prune(epoch)
                    )
                    topo_dirty = False
                    obs.point(
                        "train.prune", epoch=epoch, n_params=model.n_params
                    )
                if epoch < tc.epochs - 1 and tc.evolve and sparse_impl:
                    with obs.span(
                        "train.evolve", epoch=epoch, device=device_evo
                    ):
                        if device_evo:
                            topo, params, opt_state = self._evolve_device(
                                topo, params, opt_state
                            )
                            model.set_params(params)
                            self.opt_state = opt_state
                            topo_dirty = True
                        else:
                            params, opt_state, topo = self._host_topology_op(
                                topo, topo_dirty, self._evolve
                            )
                            topo_dirty = False
                # dispatch is async — wait for the epoch's device work so
                # epoch_seconds measures compute, not enqueue
                jax.block_until_ready((params, losses))
                dt = time.perf_counter() - t0
                if (epoch + 1) % tc.eval_every == 0 or epoch == tc.epochs - 1:
                    with obs.span("train.eval", epoch=epoch) as ev_sp:
                        acc = evaluate(
                            model, self.data.x_test, self.data.y_test,
                            params=params, topo_arrays=topo,
                        )
                        ev_sp.set(acc=float(acc))
                else:
                    acc = float("nan")
                if probe_dev is not None:
                    # host-side, after the block above — the §11 obs-in-jit
                    # rule: probe stats leave the device only here
                    churn = None
                    if self._last_churn is not None:
                        counts, nnz = self._last_churn
                        churn = [
                            float(c) / max(1, n)
                            for c, n in zip(np.asarray(counts), nnz)
                        ]
                        self._last_churn = None
                    probes.record_snapshot(
                        gstep, "train", probe_dev, churn=churn,
                        extra={
                            "epoch": epoch,
                            "loss": float(np.asarray(losses).mean()),
                            "n_params": model.n_params,
                        },
                    )
                self.history["epoch"].append(epoch)
                self.history["train_loss"].append(
                    float(np.asarray(losses).mean())
                )
                self.history["test_acc"].append(acc)
                # element nnz is evolution-invariant, so the host mirror's
                # count stays correct even while topo_dirty
                self.history["n_params"].append(model.n_params)
                self.history["epoch_seconds"].append(dt)
                ep_sp.set(loss=self.history["train_loss"][-1],
                          n_params=model.n_params)
                if log_every and (epoch + 1) % log_every == 0:
                    print(
                        f"epoch {epoch:4d} loss "
                        f"{self.history['train_loss'][-1]:.4f} "
                        f"acc {acc:.4f} params {model.n_params}"
                    )
                self.gstep = gstep
                self.epoch_next = epoch + 1
                if self.epoch_end_hook is not None:
                    # checkpointing reads the host mirror — pay the sync only
                    # when a hook (i.e. the supervisor) is attached
                    if topo_dirty:
                        self._sync_topology_to_host(topo)
                        topo_dirty = False
                    self.epoch_end_hook(self, epoch)
        if topo_dirty:
            self._sync_topology_to_host(topo)
        return self.history

    def _run_per_batch(self, log_every: int) -> Dict[str, List]:
        tc, model = self.tc, self.model
        loader = ShardedLoader(
            self.data.x_train, self.data.y_train, tc.batch_size, seed=tc.seed
        )
        lr_fn = tc.lr_schedule or (lambda step: tc.lr)
        gstep = self.gstep
        for epoch in range(self.start_epoch, tc.epochs):
            with obs.span("train.epoch", epoch=epoch) as ep_sp:
                t0 = time.perf_counter()
                params = model.params()
                topo = model.topo_arrays()
                losses = []
                # one span per epoch's worth of per-batch dispatches — NOT
                # per minibatch, which is exactly the dispatch-bound hot loop
                # this legacy mode exists to measure; like the fused
                # segment's it blocks on nothing
                with obs.span("train.segment", mode="per_batch") as seg_sp:
                    for xb, yb in loader.epoch(epoch):
                        self.key, sub = jax.random.split(self.key)

                        def do_step():
                            # hook first: a kill/transient fires before the
                            # pure jitted step, so retry_step re-enters with
                            # identical inputs (sub is split once, outside)
                            if self.fault_hook is not None:
                                self.fault_hook(gstep)
                            return self._step(
                                params,
                                self.opt_state,
                                topo,
                                jnp.asarray(xb),
                                jnp.asarray(yb),
                                jnp.asarray(lr_fn(gstep), jnp.float32),
                                sub,
                            )

                        if self.step_retries:
                            params, self.opt_state, loss = retry_step(
                                do_step,
                                retries=self.step_retries,
                                backoff_s=self.retry_backoff_s,
                            )
                        else:
                            params, self.opt_state, loss = do_step()
                        losses.append(loss)
                        gstep += 1
                    seg_sp.set(steps=len(losses))
                model.set_params(params)
                # topology phase (host)
                self._importance_prune(epoch)
                if epoch < tc.epochs - 1:  # paper: no evolution after final
                    with obs.span("train.evolve", epoch=epoch, device=False):
                        self._evolve()
                jax.block_until_ready(model.params())
                dt = time.perf_counter() - t0
                if (epoch + 1) % tc.eval_every == 0 or epoch == tc.epochs - 1:
                    with obs.span("train.eval", epoch=epoch) as ev_sp:
                        acc = evaluate(model, self.data.x_test, self.data.y_test)
                        ev_sp.set(acc=float(acc))
                else:
                    acc = float("nan")
                self.history["epoch"].append(epoch)
                self.history["train_loss"].append(
                    float(np.mean([float(l) for l in losses]))
                )
                self.history["test_acc"].append(acc)
                self.history["n_params"].append(model.n_params)
                self.history["epoch_seconds"].append(dt)
                ep_sp.set(loss=self.history["train_loss"][-1],
                          n_params=model.n_params)
                if log_every and (epoch + 1) % log_every == 0:
                    print(
                        f"epoch {epoch:4d} loss "
                        f"{self.history['train_loss'][-1]:.4f} "
                        f"acc {acc:.4f} params {model.n_params}"
                    )
                self.gstep = gstep
                self.epoch_next = epoch + 1
                if self.epoch_end_hook is not None:
                    self.epoch_end_hook(self, epoch)
        return self.history


# ---------------------------------------------------------------------------
# Out-of-core XL trainer (repro.xl, DESIGN.md §7)
# ---------------------------------------------------------------------------


class XLTrainer:
    """Out-of-core SET trainer: the paper's Table-4 regime, where the live
    parameters exceed the device budget.

    Same epoch protocol and history columns as :class:`SequentialTrainer`
    (same ``ShardedLoader`` order for the same seed, same loss/optimizer
    semantics as ``launch.steps.make_mlp_step_core``), but every minibatch
    step runs on the shard-streamed substrate (``repro.xl.StreamExecutor``)
    under the memory plan's device budget, values/momentum stay host-pinned
    (memmap above the plan threshold), and SET evolution runs shard-wise
    (``repro.xl.evolve_model_streamed``) instead of whole-layer.

    Constraints vs the in-core trainer: element impl only, ``dropout == 0``
    (the streamed backward is hand-derived; a dropout mask cache is the
    natural extension) and no importance-pruning schedule (shape changes
    would re-plan; out of scope for the substrate).
    """

    def __init__(self, model_or_state, data: Dataset, tc: TrainerConfig, plan,
                 spool_dir: Optional[str] = None):
        from repro.xl import StreamExecutor, XLModelState

        if isinstance(model_or_state, XLModelState):
            self.state = model_or_state
        else:
            cfg = model_or_state.config
            if cfg.dropout != 0:
                raise ValueError("XLTrainer requires dropout == 0")
            self.state = XLModelState.from_model(
                model_or_state, plan, spool_dir=spool_dir
            )
        if tc.pruning is not None:
            raise ValueError("XLTrainer does not support importance pruning")
        if tc.batch_size != plan.batch:
            raise ValueError(
                f"plan solved for batch {plan.batch}, trainer uses "
                f"{tc.batch_size} — re-plan"
            )
        self.plan = plan
        self.data = data
        self.tc = tc
        self.executor = StreamExecutor(self.state)
        self.rng = np.random.default_rng(tc.seed)
        self.history: Dict[str, List] = {
            "epoch": [], "train_loss": [], "test_acc": [], "n_params": [],
            "epoch_seconds": [],
        }
        # resume / fault-tolerance surface — same contract as
        # SequentialTrainer (DESIGN.md §8); streamed state instead of pytrees
        self.start_epoch = 0
        self.epoch_next = 0
        self.gstep = 0
        self.fault_hook: Optional[Callable[[int], None]] = None
        self.epoch_end_hook: Optional[Callable] = None
        self.step_retries = 0
        self.retry_backoff_s = 0.0

    @property
    def n_params(self) -> int:
        return sum(st.nnz + st.out_dim for st in self.state.layers)

    def evaluate(self, x: np.ndarray, y: np.ndarray) -> float:
        correct = 0
        b = self.plan.batch
        for s in range(0, x.shape[0], b):
            logits = self.executor.logits(x[s : s + b])
            correct += int((np.argmax(logits, -1) == y[s : s + b]).sum())
        return correct / x.shape[0]

    def save_checkpoint(self, manager, step: Optional[int] = None) -> None:
        """Streamed shard-group save — checkpoints of models larger than
        host RAM headroom write incrementally (CheckpointManager
        ``save_streamed``). Carries the trainer's resume state so
        :meth:`from_checkpoint` continues the run (DESIGN.md §8)."""
        self.state.save(
            manager,
            self.gstep if step is None else step,
            extra_meta={
                "plan": self.plan.to_json(),
                "resume": {
                    "epoch_next": int(self.epoch_next),
                    "gstep": int(self.gstep),
                    "numpy_rng": self.rng.bit_generator.state,
                    "history": self.history,
                    "seed": self.tc.seed,
                },
            },
        )

    def restore_checkpoint(
        self, manager, step: Optional[int] = None, spool_dir: Optional[str] = None
    ) -> int:
        """Rewind to a saved epoch boundary: streamed-restore the host state
        in place (fresh StreamExecutor) and rewind the counters so ``run()``
        continues the interrupted trajectory. Defaults to the newest *valid*
        checkpoint (corrupt ones are quarantined by the backward scan).
        Same contract as ``SequentialTrainer.restore_checkpoint``."""
        from repro.xl import StreamExecutor, XLModelState

        if step is None:
            step = manager.latest_valid_step()
            if step is None:
                raise FileNotFoundError(f"no valid checkpoints under {manager.dir}")
        self.state = XLModelState.restore(
            manager, self.plan, step, spool_dir=spool_dir
        )
        self.executor = StreamExecutor(self.state)
        res = manager.read_manifest(step)["meta"].get("resume")
        if res:
            self.start_epoch = self.epoch_next = int(res["epoch_next"])
            self.gstep = int(res["gstep"])
            self.rng.bit_generator.state = res["numpy_rng"]
            self.history = {k: list(v) for k, v in res["history"].items()}
        return step

    @classmethod
    def from_checkpoint(
        cls,
        manager,
        data: Dataset,
        tc: TrainerConfig,
        plan,
        step: Optional[int] = None,
        spool_dir: Optional[str] = None,
    ) -> "XLTrainer":
        """Build a fresh trainer directly from a checkpoint (no in-core
        model required — the streamed state is the source of truth)."""
        from repro.xl import XLModelState

        if step is None:
            step = manager.latest_valid_step()
            if step is None:
                raise FileNotFoundError(f"no valid checkpoints under {manager.dir}")
        state = XLModelState.restore(manager, plan, step, spool_dir=spool_dir)
        trainer = cls(state, data, tc, plan)
        res = manager.read_manifest(step)["meta"].get("resume")
        if res:
            trainer.start_epoch = trainer.epoch_next = int(res["epoch_next"])
            trainer.gstep = int(res["gstep"])
            trainer.rng.bit_generator.state = res["numpy_rng"]
            trainer.history = {k: list(v) for k, v in res["history"].items()}
        return trainer

    def run(self, log_every: int = 0) -> Dict[str, List]:
        from repro.xl import compile_counts, evolve_model_streamed

        tc = self.tc
        loader = ShardedLoader(
            self.data.x_train, self.data.y_train, tc.batch_size, seed=tc.seed
        )
        steps = loader.steps_per_epoch
        if steps == 0:
            raise ValueError("batch_size larger than the training shard")
        lr_fn = tc.lr_schedule or (lambda step: tc.lr)
        gstep = self.gstep
        with obs.span(
            "train.run", mode="xl", epochs=tc.epochs,
            start_epoch=self.start_epoch,
        ):
            for epoch in range(self.start_epoch, tc.epochs):
                with obs.span("train.epoch", epoch=epoch) as ep_sp:
                    t0 = time.perf_counter()
                    losses = []
                    probe_batch = None
                    # one span over the epoch's streamed steps, not one per
                    # shard — StreamExecutor syncs internally, so there is no
                    # async device result to register here
                    with obs.span("train.segment", mode="xl"):
                        for xb, yb in loader.epoch(epoch):
                            probe_batch = (xb, yb)

                            def do_step():
                                # hook fires before the streamed step mutates
                                # host state, so a transient raised here
                                # retries cleanly
                                if self.fault_hook is not None:
                                    self.fault_hook(gstep)
                                return self.executor.train_step(
                                    xb, yb, float(lr_fn(gstep)),
                                    momentum=tc.momentum,
                                    weight_decay=tc.weight_decay,
                                )

                            if self.step_retries:
                                losses.append(
                                    retry_step(
                                        do_step,
                                        retries=self.step_retries,
                                        backoff_s=self.retry_backoff_s,
                                    )
                                )
                            else:
                                losses.append(do_step())
                            gstep += 1
                    evo_stats = None
                    if epoch < tc.epochs - 1 and tc.evolve:
                        with obs.span(
                            "train.evolve", epoch=epoch, device=False
                        ):
                            evo_stats = evolve_model_streamed(
                                self.state, tc.zeta, self.rng
                            )
                    if tc.probe and probe_batch is not None:
                        layer_stats = self.executor.probe_stats(*probe_batch)
                        churn = None
                        if evo_stats is not None:
                            churn = [
                                s["n_pruned"] / max(1, st.nnz)
                                for s, st in zip(evo_stats, self.state.layers)
                            ]
                        probes.record_snapshot(
                            gstep, "xl", layers=layer_stats, churn=churn,
                            extra={"epoch": epoch,
                                   "loss": float(np.mean(losses))},
                        )
                    dt = time.perf_counter() - t0
                    if (epoch + 1) % tc.eval_every == 0 \
                            or epoch == tc.epochs - 1:
                        with obs.span("train.eval", epoch=epoch) as ev_sp:
                            acc = self.evaluate(
                                self.data.x_test, self.data.y_test
                            )
                            ev_sp.set(acc=float(acc))
                    else:
                        acc = float("nan")
                    self.history["epoch"].append(epoch)
                    self.history["train_loss"].append(float(np.mean(losses)))
                    self.history["test_acc"].append(acc)
                    self.history["n_params"].append(self.n_params)
                    self.history["epoch_seconds"].append(dt)
                    ep_sp.set(
                        loss=self.history["train_loss"][-1],
                        peak_dev_bytes=int(self.executor.measured_peak_bytes),
                    )
                    if log_every and (epoch + 1) % log_every == 0:
                        print(
                            f"epoch {epoch:4d} loss "
                            f"{self.history['train_loss'][-1]:.4f} "
                            f"acc {acc:.4f} params {self.n_params} "
                            f"peak_dev {self.executor.measured_peak_bytes}"
                        )
                    self.gstep = gstep
                    self.epoch_next = epoch + 1
                    if self.epoch_end_hook is not None:
                        self.epoch_end_hook(self, epoch)
            # the substrate's whole jit surface as gauges — a cache that grew
            # with scale shows up in the Prometheus snapshot
            obs.record_compile_counts(
                compile_counts(), prefix="xl_compile_cache"
            )
        return self.history


# ---------------------------------------------------------------------------
# contract auditor registration (repro.analysis, DESIGN.md §10)
# ---------------------------------------------------------------------------


def analysis_programs():
    """Registry hook: the fused epoch segment — the headline training hot
    path — at an audit scale sitting ABOVE the espmm auto-dispatch
    thresholds (nnz >= 2048), so the audit traces the custom-VJP kernels
    production uses, not the small-model scatter fallback."""
    from repro.analysis.registry import AuditProgram, Contract, ProgramSpec

    audit_dims = (784, 256, 100)
    audit_eps = 20.0
    batch, steps = 32, 2

    def build() -> AuditProgram:
        cfg = SparseMLPConfig(
            layer_dims=audit_dims, epsilon=audit_eps, dropout=0.0
        )
        model = SparseMLP(cfg, seed=0)
        opt = MomentumSGD(momentum=0.9, weight_decay=2e-4)
        n_train = steps * batch
        args = (
            model.params(),
            opt.init(model.params()),
            model.topo_arrays(),
            jnp.zeros((n_train, audit_dims[0]), jnp.float32),
            jnp.zeros((n_train,), jnp.int32),
            jnp.arange(n_train, dtype=jnp.int32).reshape(steps, batch),
            jnp.full((steps,), 0.01, jnp.float32),
            jax.random.PRNGKey(0),
        )
        program = make_segment_program(cfg, opt)
        nnz = [int(t.rows.shape[0]) for t in model.topos]
        return AuditProgram(
            make=lambda donate: jax.jit(program, donate_argnums=donate),
            args=args,
            meta={"dims": audit_dims, "batch": batch, "nnz": nnz},
        )

    from repro.core import sparsity

    return [
        ProgramSpec(
            name="train.segment",
            subsystem=__name__,
            contract=Contract(
                # the one legal unsorted scatter: the CE-loss label gather's
                # backward, sized (batch, n_classes) — never nnz-scale
                max_unsorted_scatter=1,
                max_unsorted_scatter_elems=batch * audit_dims[-1],
                max_intermediate_elems=sparsity.SPMM_TEMP_BUDGET_ELEMS,
                donate_argnums=(0, 1),
                max_temp_bytes=8 * 1024 * 1024,
                expected_compiles=1,
            ),
            build=build,
            notes="fused epoch: scan over minibatch steps, params/opt donated",
        )
    ]
