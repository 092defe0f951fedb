"""The main path's programs compile for a TPU v5e, at real widths.

Nothing runs: each program is lowered from shapes and compiled for a v5e that
is described, not attached, so what the chip's compiler refuses (VMEM
overruns, unaligned kernel tiles, programs that do not fit, missing
collectives) fails here at no chip time. ``JAX_PLATFORMS=cpu`` stays set;
only the compiler is the TPU's.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.analysis import registry
from repro.configs.set_mlp import mlp_config
from repro.core.topology import evolve_element_layers_device
from repro.core.wasap import make_phase1_epoch_fn
from repro.kernels import block_sparse_matmul as bsm
from repro.kernels.all_relu_fused import bias_all_relu
from repro.models.mlp import SparseMLP
from repro.optim.sgd import MomentumSGD
from repro.train.trainer import make_segment_program

# the paper's Table-2 CIFAR-10 run: 3072-4000-1000-4000-10, eps=20, batch 128
N_TRAIN, BATCH = 50_000, 128
STEPS = N_TRAIN // BATCH


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one — keep the cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cifar():
    cfg = mlp_config("cifar10")
    return cfg, SparseMLP(cfg, seed=0), MomentumSGD(momentum=0.9, weight_decay=2e-4)


def _spec(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
    )


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_fused_segment_compiles_at_cifar10_width(cifar, one_chip):
    cfg, model, opt = cifar
    assert [int(t.nnz) for t in model.topos] == [141_440, 100_000, 100_000, 40_000]
    params = model.params()
    args = (
        _spec(params, one_chip),
        _spec(opt.init(params), one_chip),
        _spec(model.topo_arrays(), one_chip),
        _shape((N_TRAIN, cfg.layer_dims[0]), jnp.float32, one_chip),
        _shape((N_TRAIN,), jnp.int32, one_chip),
        _shape((STEPS, BATCH), jnp.int32, one_chip),
        _shape((STEPS,), jnp.float32, one_chip),
        _shape((2,), jnp.uint32, one_chip),
    )
    program = make_segment_program(cfg, opt)
    compiled = jax.jit(program, donate_argnums=(0, 1)).lower(*args).compile()
    mem = compiled.memory_analysis()
    # params + velocity alias their outputs, and the whole run fits the chip
    assert mem.alias_size_in_bytes >= 2 * 4 * sum(int(t.nnz) for t in model.topos)
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < 4 * 1024**3


# the paper's Table-2 Leukemia run: 54675-27500-27500-18, eps=10, batch 5
LEUKEMIA_TRAIN, LEUKEMIA_BATCH = 1397, 5
# temp bytes of the same compile with XLA's sorted segment_sum in the
# forward and dX passes (JAX 0.9.0; the tree before the MXU segment-sum
# kernel): the kernel's program may not need more
LEUKEMIA_SEGMENT_SUM_TEMP_BYTES = 516_511_744


def _segment_program_args(cfg, model, opt, n_train, batch, sharding):
    params = model.params()
    return (
        _spec(params, sharding),
        _spec(opt.init(params), sharding),
        _spec(model.topo_arrays(), sharding),
        _shape((n_train, cfg.layer_dims[0]), jnp.float32, sharding),
        _shape((n_train,), jnp.int32, sharding),
        _shape((n_train // batch, batch), jnp.int32, sharding),
        _shape((n_train // batch,), jnp.float32, sharding),
        _shape((2,), jnp.uint32, sharding),
    )


def test_fused_segment_compiles_at_leukemia_width(one_chip):
    cfg = mlp_config("leukemia")
    model = SparseMLP(cfg, seed=0)
    opt = MomentumSGD(momentum=0.9, weight_decay=2e-4)
    assert cfg.layer_dims == (54_675, 27_500, 27_500, 18)
    args = _segment_program_args(
        cfg, model, opt, LEUKEMIA_TRAIN, LEUKEMIA_BATCH, one_chip
    )
    program = make_segment_program(cfg, opt)
    compiled = jax.jit(program, donate_argnums=(0, 1)).lower(*args).compile()
    text = compiled.as_text()
    # the segment sums of the forward and dX passes are the MXU kernel, under
    # the pass scopes the benchmark's pass readers look for
    kernel_passes = set()
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            path = re.search(r'op_name="([^"]*)"', line).group(1)
            kernel_passes.add(re.findall(r"\b(espmm_(?:fwd|dx|dw))\b", path)[-1])
    assert kernel_passes == {"espmm_fwd", "espmm_dx"}
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= LEUKEMIA_SEGMENT_SUM_TEMP_BYTES


def test_segment_sum_kernel_meets_its_contract(one_chip):
    spec = registry.get("kernels.sorted_segment_sum")
    prog = spec.build()
    compiled = prog.make(()).lower(*_spec(prog.args, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes <= spec.contract.max_temp_bytes


def test_device_evolution_compiles_at_cifar10_width(cifar, one_chip):
    cfg, model, _ = cifar
    params = model.params()
    compiled = evolve_element_layers_device.lower(
        _spec(model.topo_arrays(), one_chip),
        _spec(params["values"], one_chip),
        _spec(params["values"], one_chip),
        _shape((2,), jnp.uint32, one_chip),
        layer_dims=cfg.layer_dims, zeta=0.3, init_scheme=cfg.init,
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1024**3


# qwen1.5-0.5b FFN widths, 128x128 MXU tiles, a quarter of the blocks live
@pytest.mark.parametrize("d_in,d_out", [(1024, 2816), (2816, 1024)])
@pytest.mark.parametrize("kernel", ["fwd", "dx", "dw"])
def test_block_sparse_kernels_compile(one_chip, kernel, d_in, d_out):
    bm = bn = 128
    B = 256
    grid_m, grid_n = d_in // bm, d_out // bn
    nb = grid_m * grid_n // 4
    i32 = functools.partial(_shape, (nb,), jnp.int32, one_chip)
    values = _shape((nb, bm, bn), jnp.float32, one_chip)
    if kernel == "fwd":
        fn = functools.partial(bsm.bsmm_fwd, grid_n=grid_n)
        args = (_shape((B, d_in), jnp.float32, one_chip), values, i32(), i32(), i32())
    elif kernel == "dx":
        fn = functools.partial(bsm.bsmm_dx, grid_m=grid_m)
        args = (
            _shape((B, d_out), jnp.float32, one_chip), values,
            i32(), i32(), i32(), i32(),
        )
    else:
        fn = functools.partial(
            bsm.bsmm_dw, n_blocks=nb, block_m=bm, block_n=bn
        )
        args = (
            _shape((B, d_in), jnp.float32, one_chip),
            _shape((B, d_out), jnp.float32, one_chip),
            i32(), i32(),
        )
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width", [2816, 4000, 8192])
def test_bias_all_relu_compiles(one_chip, width):
    fn = functools.partial(bias_all_relu, alpha=0.6, layer_index=1)
    compiled = jax.jit(fn).lower(
        _shape((1024, width), jnp.float32, one_chip),
        _shape((width,), jnp.float32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_wasap_phase1_shard_map_compiles_on_four_chips(topo, cifar):
    cfg, model, opt = cifar
    k, h = 4, 4
    rounds = -(-(N_TRAIN // k // BATCH) // h)
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    rep = NamedSharding(mesh, P())
    split = NamedSharding(mesh, P(None, "data"))
    params = model.params()
    args = (
        _spec(params, rep),
        _spec(opt.init(params), rep),
        _spec(model.topo_arrays(), rep),
        _shape((N_TRAIN, cfg.layer_dims[0]), jnp.float32, rep),
        _shape((N_TRAIN,), jnp.int32, rep),
        _shape((rounds, k, h, BATCH), jnp.int32, split),
        _shape((rounds, h), jnp.float32, rep),
        _shape((rounds, h), jnp.float32, rep),
        _shape((rounds, k, 2), jnp.uint32, split),
    )
    epoch_fn = make_phase1_epoch_fn(
        cfg, opt, n_workers=k, worker_axis="shard_map", mesh=mesh,
        donate=(0, 1),
    )
    compiled = epoch_fn.lower(*args).compile()
    assert "all-gather" in compiled.as_text()
    out = compiled.output_shardings[0]
    assert len(jax.tree.leaves(out)[0].device_set) == 4
