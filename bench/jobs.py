"""The one generator of training traffic: it reads a traffic file (a job of
the program's trainers, see ``traffic/*.json``) and drives it.

A job is one trainer object, built once in set-up and run through its own
``run()`` for an open-ended number of epochs:

* epoch 0 is the run the reference follows: its end state, the accuracy
  the trainer recorded and the logits of the trainer's evaluation program
  run again on that state are copied to the host at the epoch's boundary
  (the trainer's ``epoch_end_hook``, which is then removed, so no later
  epoch syncs anything to the host);
* epochs ``0 .. warmup_epochs-1`` are set-up: they compile (or load from the
  cache) every program the window runs, evolution and eval included;
* the window starts at the boundary of epoch ``warmup_epochs`` and ends at
  the first epoch boundary after ``--seconds`` have passed. The trainer's
  ``fault_hook`` fires before every epoch's device call, after the previous
  epoch's evaluation has come back to the host, so both ends of the window
  fall where the device has finished all work.

With ``--trace 1`` the profiler records the first ``trace_epochs`` epochs of
the window, between two host markers at their boundaries.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen

MARKER = "bench.epoch_boundary"
# the rows per call of the trainers' ``evaluate``
EVAL_BATCH = 512


class WindowClosed(Exception):
    """Raised from the trainer's fault hook at the boundary that ends the
    window: ``run()`` unwinds without starting another epoch."""


class CompileCounter:
    """Counts the backend compilations JAX reports (cache hits are not
    compilations)."""

    def __init__(self):
        from jax._src import dispatch

        self.count = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self._event:
            self.count += 1


@dataclasses.dataclass
class Clock:
    """The trainer's fault hook: marks the window and, in a traced run, the
    traced epochs. ``gstep // steps`` is the epoch about to start."""

    steps: int
    warmup: int
    seconds: float
    compiles: CompileCounter
    trace_epochs: int = 0
    trace_dir: Optional[Path] = None
    stop_at_window: bool = False
    t0: Optional[float] = None
    t1: Optional[float] = None
    epochs: int = 0
    compiles_in_window: int = 0
    traced: int = 0
    marks: list = dataclasses.field(default_factory=list)

    def _mark(self):
        with jax.profiler.TraceAnnotation(MARKER):
            pass

    def __call__(self, gstep: int) -> None:
        now = time.perf_counter()
        epoch = gstep // self.steps
        if epoch < self.warmup:
            return
        done = epoch - self.warmup
        self.marks.append(now)
        if done == 0:
            if self.stop_at_window:
                raise WindowClosed
            self.t0, self._c0 = now, self.compiles.count
            if self.trace_epochs:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
                self._mark()
            return
        if self.traced < self.trace_epochs:
            self._mark()
            if done == self.trace_epochs:
                self.traced = done
                jax.profiler.stop_trace()
        if now - self.t0 >= self.seconds and self.traced == self.trace_epochs:
            self.t1, self.epochs = now, done
            self.compiles_in_window = self.compiles.count - self._c0
            raise WindowClosed


@dataclasses.dataclass
class JobRun:
    """What a job leaves for the harness: timings, the window's work, the
    device memory peak, epoch 0's end state and what the reference needs to
    follow epoch 0 (all host arrays or the benchmark's own device data)."""

    setup_s: float
    window_s: float
    epochs: int
    samples_per_epoch: int
    worker_steps_per_chip: int
    losses: list
    compiles_in_window: int
    memory_peak_bytes: int
    data_peak_bytes: int
    epoch_seconds: list
    captured: dict
    follow: dict
    traced_epochs: int


def _host(tree):
    return jax.tree.map(lambda a: np.array(a), tree)


def run_job(config, traffic, seed, seconds, *, t_start, compiles,
            trace_dir=None, stop_at_window=False) -> JobRun:
    """Build the cell's trainer from the seed, run it as the module
    docstring says, and return a :class:`JobRun`."""
    kind = traffic["job"]
    if kind not in ("sequential", "local_sgd"):
        raise ValueError(f"unknown job kind {kind!r}")
    devices = jax.devices()[: traffic["chips"]]
    s31 = gen.seed31(seed)
    ds = config["dataset"]
    x_tr, y_tr, x_te, y_te = jax.block_until_ready(gen.make_dataset(ds, seed))
    data_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in devices)
    topology = gen.make_topology(config, seed)
    values, biases = gen.make_values(config, topology, seed)
    init = {"values": tuple(np.asarray(v) for v in values),
            "biases": tuple(np.asarray(b) for b in biases)}

    from repro.core.sparsity import ElementTopology
    from repro.data.synthetic import Dataset
    from repro.models.mlp import SparseMLP, SparseMLPConfig

    dims = tuple(config["layer_dims"])
    mcfg = SparseMLPConfig(
        layer_dims=dims, epsilon=float(config["epsilon"]),
        activation=config["activation"], alpha=config["alpha"],
        dropout=config["dropout"], init=config["init"], impl="element",
        dtype=config["dtype"],
    )
    topos = [ElementTopology(dims[l], dims[l + 1], r, c)
             for l, (r, c) in enumerate(topology)]
    if kind == "local_sgd":
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.launch.mesh import make_worker_mesh

        rep = NamedSharding(make_worker_mesh(traffic["workers"]), PartitionSpec())
        x_tr, y_tr, x_te = (jax.device_put(a, rep) for a in (x_tr, y_tr, x_te))
        values, biases = jax.device_put((values, biases), rep)
    model = SparseMLP.from_state(mcfg, topos, values, biases)
    data = Dataset(ds["kind"], x_tr, y_tr, x_te, np.asarray(y_te), ds["classes"])
    batch = config["batch_size"]
    n = ds["train"]
    captured = {}

    if kind == "sequential":
        from repro.train.trainer import SequentialTrainer, TrainerConfig

        trainer = SequentialTrainer(model, data, TrainerConfig(
            epochs=10**9, batch_size=batch, lr=config["lr"],
            momentum=config["momentum"], weight_decay=config["weight_decay"],
            zeta=config["zeta"], evolve=True, pruning=None, eval_every=1,
            seed=s31,
        ))
        steps = n // batch
        samples = steps * batch
        per_chip = steps

        def capture(tr, epoch):
            tr.epoch_end_hook = None
            params, topo = tr.model.params(), tr.model.topo_arrays()
            captured.update(
                topology=[(t.rows.copy(), t.cols.copy()) for t in tr.model.topos],
                params=_host(params),
                velocity=_host(tr.opt_state.velocity),
                loss=float(tr.history["train_loss"][0]),
                **_eval_output(tr, params, topo),
            )

        order = _epoch_order(s31, np.arange(n), 0, batch)
        follow = dict(job=kind, perm=order.reshape(steps, batch),
                      key=jax.random.PRNGKey(s31), lr=config["lr"])
    else:
        from repro.core.wasap import WASAPConfig, WASAPTrainer

        k, h = traffic["workers"], traffic["sync_every"]
        lr0 = config["lr"] * traffic["lr_boost"]
        trainer = WASAPTrainer(model, data, WASAPConfig(
            n_workers=k, phase1_epochs=10**9, phase2_epochs=0, sync_every=h,
            lr=config["lr"], lr_boost=traffic["lr_boost"],
            lr_boost_epochs=traffic["lr_boost_epochs"],
            momentum=config["momentum"], weight_decay=config["weight_decay"],
            zeta=config["zeta"], mode="wasap", seed=s31, batch_size=batch,
            average_momentum=True, fused=True, worker_axis=traffic["worker_axis"],
        ))
        steps = (n // k) // batch
        samples = k * steps * batch
        per_chip = (k // len(devices)) * steps

        def capture(tr, epoch):
            tr.epoch_end_hook = None
            params, opt_state, topo = tr._p1_state
            captured.update(
                topology=[(np.asarray(t.rows), np.asarray(t.cols)) for t in topo],
                params=_host(params),
                velocity=_host(opt_state.velocity),
                loss=float(tr.history["train_loss"][0]),
                **_eval_output(tr, params, topo),
            )

        rounds = -(-steps // h)
        idx = np.zeros((rounds * h, k, batch), np.int32)
        for w in range(k):
            idx[:steps, w] = _epoch_order(
                s31, np.arange(n)[w::k], 0, batch)[: steps * batch].reshape(steps, batch)
        idx = idx.reshape(rounds, h, k, batch).transpose(0, 2, 1, 3)
        valid = np.zeros(rounds * h, np.float32)
        valid[:steps] = 1.0
        _, sub = jax.random.split(jax.random.PRNGKey(s31))
        follow = dict(job=kind, idx=idx, valid=valid.reshape(rounds, h),
                      lrs=(valid * lr0).reshape(rounds, h),
                      keys=jax.random.split(sub, rounds * k).reshape(rounds, k, 2))

    clock = Clock(
        steps=steps, warmup=traffic["warmup_epochs"], seconds=seconds,
        compiles=compiles, trace_epochs=traffic["trace_epochs"] if trace_dir else 0,
        trace_dir=trace_dir, stop_at_window=stop_at_window,
    )
    trainer.fault_hook = clock
    trainer.epoch_end_hook = capture
    try:
        trainer.run()
    except WindowClosed:
        pass
    else:
        raise RuntimeError("the trainer ended before the window closed")
    losses = [float(v) for v in trainer.history["train_loss"]]
    losses = losses[traffic["warmup_epochs"]:][: clock.epochs]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    del trainer, model, values, biases
    gc.collect()
    follow.update(init=init, topology=topology, x=x_tr, y=y_tr, x_test=x_te,
                  y_test=np.asarray(y_te))
    return JobRun(
        setup_s=(clock.t0 - t_start) if clock.t0 else float("nan"),
        window_s=(clock.t1 - clock.t0) if clock.t1 else float("nan"),
        epochs=clock.epochs, samples_per_epoch=samples,
        worker_steps_per_chip=per_chip, losses=losses,
        compiles_in_window=clock.compiles_in_window, memory_peak_bytes=int(peak),
        data_peak_bytes=int(data_peak),
        epoch_seconds=list(np.diff(clock.marks)) if clock.t1 else [],
        captured=captured, follow=follow, traced_epochs=clock.traced,
    )


def _eval_output(tr, params, topo):
    """The trainer's evaluation of the epoch just ended: the accuracy it
    recorded, and the logits of its evaluation program run again on the
    same state over the test set, in the slices ``evaluate`` takes."""
    from repro.train.trainer import make_eval_fn

    fwd = make_eval_fn(tr.model.config)
    x = tr.data.x_test
    logits = [np.asarray(fwd(params, topo, x[s:s + EVAL_BATCH]))
              for s in range(0, x.shape[0], EVAL_BATCH)]
    return {"test_acc": float(tr.history["test_acc"][0]),
            "eval_logits": np.concatenate(logits)}


def _epoch_order(seed, shard_idx, epoch, batch):
    """The data order of one epoch as the configuration states it: the
    shard's rows shuffled by a generator seeded with
    ``(seed * 1000003 + epoch) mod 2^31``, the last partial batch dropped."""
    rng = np.random.default_rng((seed * 1_000_003 + epoch) & 0x7FFFFFFF)
    order = rng.permutation(shard_idx)
    return order[: order.size // batch * batch].astype(np.int32)
