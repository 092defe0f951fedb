"""``correct`` comes out false when the timed path is broken underneath:
the harness's look for a chip is skipped and the rest of a run is driven on
the CPU at a tiny size, with one fault planted in the program per case:
in the training step, in the exchange between workers, in SET regrowth or
in evaluation. The control (the reference in bfloat16, in the program's
place) must fail the cell's limits too."""
import json

import benchutil
import jax
import jax.numpy as jnp
import pytest

from bench import check, gen, jobs

ROOT = benchutil.ROOT
# the number each fault outside the training step has to fail
CAUGHT_BY = {"out_of_range": "positions", "duplicate": "positions",
             "eval_altered": "logits", "eval_half_test_set": "eval_acc"}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return benchutil.tiny_copy(tmp_path_factory.mktemp("faults"))


def _unchanged(core):
    def step(p, s, inp, rng):
        _, _, loss = core(p, s, inp, rng)
        return p, s, loss
    return step


def _half_batch(core):
    def step(p, s, inp, rng):
        idx, lr = inp
        return core(p, s, (idx[: idx.shape[0] // 2], lr), rng)
    return step


def _patched_step(monkeypatch, module, wrap):
    from repro.train import trainer

    orig = module.make_mlp_step_core
    monkeypatch.setattr(module, "make_mlp_step_core",
                        lambda *a, **k: wrap(orig(*a, **k)))
    trainer.make_segment_fn.cache_clear()


def _regrow(fault):
    """Evolution whose first layer's first slot lands outside the layer, or
    whose second slot repeats the first's position."""
    def wrap(evolve):
        def call(*a, **k):
            out = evolve(*a, **k)
            t = out[0][0]
            if fault == "out_of_range":
                bad = t._replace(rows=t.rows.at[0].set(k["layer_dims"][0]))
            else:
                bad = t._replace(rows=t.rows.at[1].set(t.rows[0]),
                                 cols=t.cols.at[1].set(t.cols[0]))
            return ((bad,) + tuple(out[0][1:]),) + tuple(out[1:])
        return call
    return wrap


def _eval_fault(monkeypatch, fault):
    from repro.core import wasap
    from repro.train import trainer

    if fault == "eval_altered":
        orig = trainer.make_eval_fn
        monkeypatch.setattr(trainer, "make_eval_fn", lambda cfg: (
            lambda p, t, x: orig(cfg)(p, t, x).at[:, 0].add(0.5)))
    else:
        orig = trainer.evaluate

        def half(model, x, y, *a, **k):
            return orig(model, x[: x.shape[0] // 2], y[: y.shape[0] // 2], *a, **k)
        for module in (trainer, wasap):
            monkeypatch.setattr(module, "evaluate", half)


@pytest.mark.parametrize("workload,fault", [
    ("tiny.seq-train", "unchanged"),
    ("tiny.seq-train", "half_batch"),
    ("tiny.seq-train", "out_of_range"),
    ("tiny.seq-train", "eval_altered"),
    ("tiny.seq-train", "eval_half_test_set"),
    ("tiny.wasap4-train", "unchanged"),
    ("tiny.wasap4-train", "half_batch"),
    ("tiny.wasap4-train", "no_exchange"),
    ("tiny.wasap4-train", "out_of_range"),
    ("tiny.wasap4-train", "duplicate"),
    ("tiny.wasap4-train", "eval_altered"),
])
def test_fault_makes_the_run_incorrect(bench, monkeypatch, workload, fault):
    from repro.core import wasap
    from repro.train import trainer

    module = trainer if workload == "tiny.seq-train" else wasap
    if fault == "no_exchange":
        monkeypatch.setattr(wasap, "_average_pytree", lambda stacked, weights=None:
                            jax.tree.map(lambda a: a[0], stacked))
    elif fault in ("out_of_range", "duplicate"):
        monkeypatch.setattr(module, "evolve_element_layers_device",
                            _regrow(fault)(module.evolve_element_layers_device))
    elif fault.startswith("eval"):
        _eval_fault(monkeypatch, fault)
    else:
        _patched_step(monkeypatch, module,
                      _unchanged if fault == "unchanged" else _half_batch)
    try:
        result = benchutil.run_tiny(bench, workload, seconds=0.2)
    finally:
        trainer.make_segment_fn.cache_clear()
    assert result["correct"] is False
    failed = [k for k, r in result["compared"].items() if r["value"] > r["limit"]]
    assert failed, result["compared"]
    assert CAUGHT_BY.get(fault, failed[0]) in failed, result["compared"]


@pytest.mark.parametrize("cell", ["cifar10.seq-train", "leukemia.seq-train"])
def test_bfloat16_control_fails_the_limits(cell):
    """The control at a size a test run holds: the cell's configuration
    with narrower layers, its reference run in bfloat16 and read against
    the float32 one, must exceed one of the cell's limits."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = {c["name"]: c for c in spec["configs"]}[
        {w["name"]: w for w in spec["workloads"]}[cell]["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    limits = json.loads((ROOT / "bench/limits" / f"{cell}.json").read_text())
    dims = [256, 256, 128, config["layer_dims"][-1]]
    config = dict(config, layer_dims=dims, epsilon=8)
    ds = dict(benchutil.TINY_IMAGE, features=256, classes=dims[-1], train=640)
    x, y, x_te, y_te = gen.make_dataset(ds, 5)
    y_te = jax.device_get(y_te)
    topology = gen.make_topology(config, 5)
    values, biases = gen.make_values(config, topology, 5)
    batch = config["batch_size"]
    steps = 640 // batch
    follow = dict(job="sequential", x=x, y=y, key=jax.random.PRNGKey(5),
                  lr=config["lr"], topology=topology,
                  perm=jobs._epoch_order(5, jax.numpy.arange(640), 0, batch)
                  .reshape(steps, batch),
                  init={"values": tuple(jax.device_get(values)),
                        "biases": tuple(jax.device_get(biases))})
    refst = check.reference_state(config, follow)
    ctrl = check.reference_state(config, follow, dtype=jnp.bfloat16)
    values = check.readings(ctrl, refst, follow["init"])
    # the control's forward in place of the program's evaluation, on the
    # reference's end state
    end = {"values": refst["values"], "biases": refst["biases"]}
    ref_logits = check.reference_logits(config, end, topology, x_te)
    ctrl_logits = check.reference_logits(config, end, topology, x_te,
                                         dtype=jnp.bfloat16)
    acc = float((ctrl_logits.argmax(-1) == y_te).mean())
    values.update(check.eval_readings(ctrl_logits, acc, ref_logits, y_te))
    ok, rows = check.judge(values, limits)
    assert not ok, rows
    assert rows["logits"]["value"] > rows["logits"]["limit"], rows
