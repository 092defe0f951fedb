"""Work counts of a SET-MLP training step, from shapes alone, and the table
of device peaks they are held against.

Counted from ``(layer_dims, nnz per layer, batch)`` and never from the
compiled program, so a later kernel that does the same work reads the same
numbers. All values are float32 and all indices int32: 4 bytes each.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
WORD = 4


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def flops_per_sample(nnz) -> int:
    """Model FLOPs of forward + backward per sample: forward 2*sum(nnz),
    the weight gradient 2*sum(nnz), the input gradient 2*sum(nnz) of every
    layer but the first (the data needs no gradient). No recomputation."""
    return 2 * sum(nnz) + 2 * sum(nnz) + 2 * sum(nnz[1:])


def bytes_per_step(layer_dims, nnz, batch: int) -> int:
    """Least bytes a training step must move to and from memory.

    Per layer: the forward reads values and both index arrays, its input
    activations and writes its output; the input-gradient pass (every layer
    but the first) reads values, both index arrays and the output gradient
    and writes the input gradient; the weight-gradient pass reads both
    index arrays, the input activations and the output gradient and writes
    one gradient per connection; the update reads that gradient and reads
    and writes values and velocity once. Biases: gradient, value and
    velocity once each way.
    """
    total = 0
    for l, n in enumerate(nnz):
        a_in, a_out = batch * layer_dims[l], batch * layer_dims[l + 1]
        total += 3 * n + a_in + a_out             # forward
        if l > 0:
            total += 3 * n + a_out + a_in         # input gradient
        total += 2 * n + a_in + a_out + n         # weight gradient
        total += n + 4 * n                        # update
        total += 5 * layer_dims[l + 1]            # biases
    return WORD * total


def least_step_seconds(layer_dims, nnz, batch: int, peak: dict):
    """``(seconds, bound)``: the larger of FLOPs over the FLOP peak and
    bytes over the memory bandwidth, and which of the two it is."""
    t_flops = batch * flops_per_sample(nnz) / peak["flops_per_s"]
    t_bytes = bytes_per_step(layer_dims, nnz, batch) / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
