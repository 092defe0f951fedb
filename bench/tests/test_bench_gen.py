"""The benchmark's own seeded data and model generators."""
import ast
import json

import benchutil
import jax
import numpy as np
import pytest

from bench import gen

ROOT = benchutil.ROOT


def _config(name):
    return json.loads((ROOT / f"bench/configs/{name}.json").read_text())


def test_same_seed_same_data_other_seed_other_data():
    a = gen.make_dataset(benchutil.TINY_IMAGE, 2**33 + 5)
    b = gen.make_dataset(benchutil.TINY_IMAGE, 2**33 + 5)
    c = gen.make_dataset(benchutil.TINY_IMAGE, 6)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])


@pytest.mark.parametrize("name", ["set-mlp-cifar10", "set-mlp-leukemia"])
def test_dataset_has_the_papers_shape(name):
    ds = _config(name)["dataset"]
    out = jax.eval_shape(lambda k: gen._dataset(k, dskey=tuple(sorted(ds.items()))),
                         jax.random.PRNGKey(0))
    assert [o.shape for o in out] == [
        (ds["train"], ds["features"]), (ds["train"],),
        (ds["test"], ds["features"]), (ds["test"],)]
    paper = {"set-mlp-cifar10": (3072, 50000, 10000, 10),
             "set-mlp-leukemia": (54675, 1397, 699, 18)}[name]
    assert (ds["features"], ds["train"], ds["test"], ds["classes"]) == paper


def test_classification_rows_are_standardised():
    ds = dict(_config("set-mlp-leukemia")["dataset"], features=500, train=120,
              test=30)
    x, y, xt, yt = gen.make_dataset(ds, 11)
    assert np.allclose(np.asarray(x).mean(0), 0, atol=1e-4)
    assert np.allclose(np.asarray(x).std(0), 1, atol=1e-3)
    assert set(np.unique(np.asarray(y))) <= set(range(ds["classes"]))


@pytest.mark.parametrize("name", ["set-mlp-cifar10", "set-mlp-leukemia"])
def test_topology_has_the_stated_connections(name):
    cfg = _config(name)
    topo = gen.make_topology(cfg, 7)
    dims = cfg["layer_dims"]
    assert [r.size for r, _ in topo] == cfg["nnz"]
    for l, (r, c) in enumerate(topo):
        key = c.astype(np.int64) * dims[l] + r
        assert np.all(np.diff(key) > 0)  # unique and sorted by (col, row)
        assert r.max() < dims[l] and c.max() < dims[l + 1]
    again = gen.make_topology(cfg, 7)
    assert all(np.array_equal(a[0], b[0]) for a, b in zip(topo, again))


def test_values_follow_the_init():
    cfg = dict(_config("set-mlp-cifar10"), layer_dims=[3072, 40, 10], epsilon=2)
    topo = gen.make_topology(cfg, 1)
    vals, biases = gen.make_values(cfg, topo, 1)
    lim = np.sqrt(6.0 / 3072)
    assert float(np.abs(np.asarray(vals[0])).max()) <= lim
    assert all(float(np.abs(np.asarray(b)).max()) == 0 for b in biases)


def test_nothing_in_the_benchmark_imports_the_programs_data():
    for path in (ROOT / "bench").rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module:
                names = [node.module] + [f"{node.module}.{a.name}" for a in node.names]
            assert not any(n.startswith("repro.data") and path.name != "jobs.py"
                           for n in names), path
