"""The plain reference in connection blocks, and the reference found by the
configuration's name: a layer cut into blocks gives what one block gives to
float32 rounding, the real budget leaves the timed cells' layers whole, a
Table-4 step fits a chip, and a configuration that names another file runs
that file."""
import ast
import json

import benchutil
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, gen, harness

ROOT = benchutil.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
TINY = dict(json.loads((ROOT / "bench/configs/set-mlp-cifar10.json").read_text()),
            layer_dims=[48, 64, 32, 4], epsilon=6, batch_size=16,
            dataset=dict(benchutil.TINY_IMAGE, train=256, test=128))
# a budget that cuts every tiny layer into 3 blocks or more at 16 rows
SMALL_BUDGET = 16 * 40


def _config(name):
    return json.loads((ROOT / f"bench/configs/{name}.json").read_text())


def _follow(job, seed=11):
    """Three steps of the tiny configuration for ``job``: three sequential
    steps, or one round of three local steps on two workers."""
    x, y, x_te, _ = gen.make_dataset(TINY["dataset"], seed)
    topology = gen.make_topology(TINY, seed)
    values, biases = gen.make_values(TINY, topology, seed)
    rows = np.random.default_rng(seed).permutation(256)[:96].astype(np.int32)
    follow = dict(job=job, x=x, y=y, x_test=x_te, topology=topology,
                  init={"values": tuple(jax.device_get(values)),
                        "biases": tuple(jax.device_get(biases))})
    if job == "sequential":
        follow.update(perm=rows[:48].reshape(3, 16), key=jax.random.PRNGKey(seed),
                      lr=TINY["lr"])
    else:
        follow.update(idx=rows.reshape(1, 2, 3, 16), valid=np.ones((1, 3), np.float32),
                      lrs=np.full((1, 3), TINY["lr"], np.float32),
                      keys=jax.random.split(jax.random.PRNGKey(seed), 2)[None])
    return follow


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


@pytest.mark.parametrize("job", ["sequential", "local_sgd"])
def test_blocked_reference_agrees_with_one_block(monkeypatch, job):
    ref = check.load_reference(TINY)
    follow = _follow(job)
    for r, _ in follow["topology"]:
        assert ref.block_plan(r.size, 16, SMALL_BUDGET)[1] >= 3
    whole = check.reference_state(TINY, follow)
    end = {"values": whole["values"], "biases": whole["biases"]}
    logits_whole = check.reference_logits(TINY, end, follow["topology"],
                                          follow["x_test"])
    monkeypatch.setattr(check, "BLOCK_ELEMS", SMALL_BUDGET)
    cut = check.reference_state(TINY, follow)
    logits_cut = check.reference_logits(TINY, end, follow["topology"],
                                        follow["x_test"])
    assert abs(cut["loss"] - whole["loss"]) <= 1e-6 * abs(whole["loss"])
    for key in ("values", "biases", "vel_values", "vel_biases"):
        for a, b in zip(cut[key], whole[key]):
            assert _rel(a, b) <= 1e-6, key
    assert _rel(logits_cut, logits_whole) <= 1e-6


@pytest.mark.parametrize("name,rows", [
    ("set-mlp-cifar10", 128), ("set-mlp-cifar10", 64),
    ("set-mlp-leukemia", 5), ("set-mlp-leukemia", 2),
    ("set-mlp-cifar10", "logits"), ("set-mlp-leukemia", "logits"),
])
def test_real_budget_keeps_the_timed_layers_whole(name, rows):
    """At the cells' batches, their halves (the half-batch fault), a WASAP
    worker's batch (128, under ``vmap``) and the logits' calls, every layer
    of the two timed configurations is one block."""
    config = _config(name)
    ref = check.load_reference(config)
    if rows == "logits":
        n = config["dataset"]["test"]
        rows = check.logit_rows(max(config["nnz"]), n)
        # the rows per call before connection blocks
        assert rows == min(n, check.BLOCK_ELEMS // max(config["nnz"]))
    for nnz in config["nnz"]:
        assert ref.block_plan(nnz, rows, check.BLOCK_ELEMS) == (nnz, 1)


def _parent_forward(params, topo, x, cfg):
    """The reference's forward before connection blocks, dropout off."""
    dims = cfg["layer_dims"]
    hT = x.T
    for l in range(len(dims) - 1):
        rows, cols = topo[l]
        zT = jax.ops.segment_sum(hT[rows] * params["values"][l][:, None], cols,
                                 num_segments=dims[l + 1])
        zT = zT + params["biases"][l][:, None]
        if l == len(dims) - 2:
            return zT.T
        slope = cfg["alpha"] if (l + 1) % 2 == 1 else -cfg["alpha"]
        hT = jnp.where(zT > 0, zT, slope * zT)


def test_one_block_is_bitwise_the_unblocked_forward():
    ref = check.load_reference(TINY)
    follow = _follow("sequential")
    params = jax.tree.map(jnp.asarray, follow["init"])
    topo = tuple((jnp.asarray(r), jnp.asarray(c)) for r, c in follow["topology"])
    x, y = follow["x"][:16], follow["y"][:16]

    def loss(fwd):
        return lambda p: -jnp.take_along_axis(
            jax.nn.log_softmax(fwd(p), -1), y[:, None], -1).mean()

    new = jax.jit(lambda p: ref.forward(p, topo, x, TINY, None, jnp.float32,
                                        check.BLOCK_ELEMS))
    old = jax.jit(lambda p: _parent_forward(p, topo, x, TINY))
    assert np.array_equal(new(params), old(params))
    g_new = jax.jit(jax.grad(loss(new)))(params)
    g_old = jax.jit(jax.grad(loss(old)))(params)
    assert all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(g_new), jax.tree.leaves(g_old)))


def test_table4_step_fits_a_chip():
    """One reference step of the paper's Table-4 SET-MLP 65536-500000-500000-2
    at eps=10 (16,655,360 connections from ``gen``) at batch 128, compiled
    from shapes: its temporaries stay under 4 GB (one block per layer would
    take 24.4 GB)."""
    dims, eps = [65536, 500000, 500000, 2], 10
    nnz = [gen.erdos_renyi_nnz(eps, a, b) for a, b in zip(dims, dims[1:])]
    assert sum(nnz) == 16_655_360
    cfg = dict(TINY, layer_dims=dims, epsilon=eps)
    ref = check.load_reference(cfg)
    S = jax.ShapeDtypeStruct
    params = {"values": tuple(S((n,), jnp.float32) for n in nnz),
              "biases": tuple(S((d,), jnp.float32) for d in dims[1:])}
    topo = tuple((S((n,), jnp.int32), S((n,), jnp.int32)) for n in nnz)
    step = jax.jit(lambda p, v, t, x, y, k, lr: ref._step(
        p, v, t, x, y, k, lr, cfg, jnp.float32, check.BLOCK_ELEMS))
    compiled = step.lower(params, params, topo, S((128, dims[0]), jnp.float32),
                          S((128,), jnp.int32), S((2,), jnp.uint32),
                          S((), jnp.float32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= 4e9
    assert check.logit_rows(max(nnz), 3000) == check.LOGIT_ROWS


def test_a_configuration_runs_the_reference_it_names(tmp_path):
    """A cell whose configuration names another reference file runs that
    file: a copy that leaves out the momentum makes the run incorrect."""
    bench = benchutil.tiny_copy(tmp_path)
    src = (bench / "references/set_mlp.py").read_text()
    step = "vel = jax.tree.map(lambda v, g, p: mu * v - lr"
    assert src.count(step) == 1
    (bench / "references/no_momentum.py").write_text(
        src.replace(step, "vel = jax.tree.map(lambda v, g, p: 0 * v - lr"))
    cfg_path = bench / "configs/tiny.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["reference"] = "bench/references/no_momentum.py"
    cfg_path.write_text(json.dumps(cfg))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    config = harness.resolve(spec, "tiny.seq-train", bench)["config"]
    mod = check.load_reference(config)
    assert mod.__file__ == str(bench / "references/no_momentum.py")
    result = benchutil.run_tiny(bench, "tiny.seq-train")
    assert result["correct"] is False
    failed = {k for k, r in result["compared"].items() if r["value"] > r["limit"]}
    assert failed & {"loss", "change", "velocity"}, result["compared"]


def test_reference_imports_nothing_of_the_program():
    tree = ast.parse((ROOT / "bench/references/set_mlp.py").read_text())
    names = {a.name.split(".")[0] for n in ast.walk(tree)
             if isinstance(n, ast.Import) for a in n.names}
    names |= {n.module.split(".")[0] for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom)}
    assert names <= {"__future__", "functools", "jax", "numpy"}, names
