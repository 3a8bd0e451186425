"""The yardstick's arithmetic: model FLOPs, and the operations and bytes
each named kernel *needs* (recomputation never counts), from shapes.

Copied from ``paddlefleetx_tpu/observability/flops.py`` (PR 21) so that a
later PR can edit the program and not the yardstick; the original is
listed under Open questions in PERF.md for deletion or import-from-here.
Every function returns plain floats and touches no device.
"""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind):
    """The peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r}: add it "
            f"to chipbench/peaks.json with its source")
    return table[device_kind]


def model_flops_per_token(num_layers, hidden_size, vocab_size, seq):
    """Megatron fwd+bwd model FLOPs per trained token of a GPT geometry:
    ``72 L h^2 (1 + s/6h + V/12Lh)`` (ffn = 4h, true of every
    configuration that uses it here; recomputation is not counted)."""
    L, h, V = num_layers, hidden_size, vocab_size
    return 72.0 * L * h * h * (1 + seq / (6.0 * h) + V / (12.0 * L * h))


def mfu(tokens_per_s, flops_per_token, peak_flops_per_chip, chips):
    return tokens_per_s * flops_per_token / (peak_flops_per_chip * chips)


def roofline(ops, nbytes, peaks):
    """``(least seconds, which bound)`` for ``ops`` FLOPs and ``nbytes``
    bytes of HBM traffic on one chip."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")


def flash_train(batch, heads, seq, head_dim, layers, dtype_bytes=2):
    """Causal flash attention, forward + backward, for ``layers`` layers
    of one training step on ONE chip's share (``batch`` and ``heads`` are
    that chip's). Forward: QK^T and PV over the causal half. Backward:
    the five matmuls the algorithm needs (S again, dV, dP, dQ, dK) — a
    kernel split that recomputes S twice does more, and that does not
    count. Bytes: q, k, v read and o written forward; q, k, v, o, do
    read and dq, dk, dv written backward (the log-sum-exp rows are
    1/head_dim of that and ignored)."""
    matmul = 2.0 * batch * heads * seq * seq * head_dim * 0.5
    ops = (2 + 5) * matmul * layers
    tensor = batch * heads * seq * head_dim * dtype_bytes
    nbytes = (4 + 8) * tensor * layers
    return ops, nbytes


def paged_decode(kv_tokens_read, heads, head_dim, layers, kv_bytes=2):
    """Decode attention over a paged KV cache: ``kv_tokens_read`` is the
    sum over decode ticks and live slots of the tokens of context the
    tick attends to. Each costs a K row and a V row of ``heads x
    head_dim`` per layer, read once; 2 FLOPs per element for QK^T and
    for PV. Memory-bound by construction (1 FLOP per byte)."""
    elems = 2.0 * kv_tokens_read * heads * head_dim * layers
    return 2.0 * elems, elems * kv_bytes
