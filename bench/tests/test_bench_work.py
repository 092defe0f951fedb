"""Work counts and the peaks table, against hand arithmetic."""
import benchutil  # noqa: F401  (puts the repo on the path)
import pytest

from bench import work

DIMS, NNZ, BATCH = (4, 3, 2), (6, 5), 2


def test_flops_per_sample_by_hand():
    # forward 2*(6+5), weight gradient 2*(6+5), input gradient 2*5
    assert work.flops_per_sample(NNZ) == 22 + 22 + 10


def test_bytes_per_step_by_hand():
    # layer 0 (in 8, out 6 activations): fwd 18+8+6, wgrad 12+8+6+6,
    # update 6+24, biases 15; layer 1 (in 6, out 4): fwd 15+6+4,
    # igrad 15+4+6, wgrad 10+6+4+5, update 5+20, biases 10
    words = (32 + 32 + 30 + 15) + (25 + 25 + 25 + 25 + 10)
    assert work.bytes_per_step(DIMS, NNZ, BATCH) == 4 * words


def test_least_time_takes_the_larger_bound():
    peak = {"flops_per_s": 1e9, "hbm_bytes_per_s": 1e12}
    t, bound = work.least_step_seconds(DIMS, NNZ, BATCH, peak)
    assert bound == "flops" and t == pytest.approx(BATCH * 54 / 1e9)
    peak = {"flops_per_s": 1e15, "hbm_bytes_per_s": 1e3}
    t, bound = work.least_step_seconds(DIMS, NNZ, BATCH, peak)
    assert bound == "bytes" and t == pytest.approx(4 * 219 / 1e3)


def test_v5e_peaks_and_unknown_device():
    p = work.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")


@pytest.mark.parametrize("name,flops", [
    ("set-mlp-cifar10", 2_005_760),
    ("set-mlp-leukemia", 2 * 1_646_930 * 2 + 2 * 825_180),
])
def test_config_flops(name, flops):
    import json

    cfg = json.loads((benchutil.ROOT / f"bench/configs/{name}.json").read_text())
    assert work.flops_per_sample(cfg["nnz"]) == flops
