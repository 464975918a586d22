"""Operations and bytes the benchmark's work requires, from its shapes alone,
and the table of peaks they are set against.

The counts are fixed by the work as defined (the model, the operator), never
by how the program stores or computes it: a program that does less work for
the same answers reads a larger share, and none can read over 100%.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")


def peaks_for(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; an unknown kind is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; have {sorted(table)}")
    return table[device_kind]


def axo_matmul_work(m: int, k: int, n: int, rank: int, n_bits: int = 8):
    """(ops, bytes) of one AxO matmul (M, K) x (K, N) at rank R.

    Ops: the exact product and R rank-one error terms, 2*M*K*N each, on
    n-bit codes.  Bytes: one n-bit code per weight, bfloat16 activations in
    and out, and the operator's two factor tables (2^n x R float32).
    """
    ops = 2 * m * k * n * (rank + 1)
    code = n_bits / 8
    nbytes = k * n * code + 2 * m * k + 2 * m * n + 2 * (2 ** n_bits) * rank * 4
    return ops, nbytes


def projections(model: dict):
    """(group, K, N) of every linear projection of every layer, in order,
    from a model family's ``shapes``."""
    for layer in model["layers"]:
        for group, projs in layer.items():
            for k, n in projs.values():
                yield group, k, n


def model_flops(model: dict, n_tokens: int, context: int) -> float:
    """Model FLOPs of ``n_tokens`` tokens that each attend over ``context``
    positions: 2 x matmul parameters (the tied head included) plus QK^T and
    PV over the context in each attention layer, per token."""
    params = sum(k * n for _, k, n in projections(model))
    params += model["d_model"] * model["vocab"]
    attn = 2 * 2 * model["attn_layers"] * context * model["d_model"]
    return n_tokens * (2 * params + attn)


def serve_flops(model: dict, batch: int, prompt_len: int, gen: int,
                head_positions: int = 1) -> float:
    """Model FLOPs of one served batch: a prefill of the prompt (its layers
    over every position, the head at the last) and gen - 1 decode steps."""
    p, d, v = prompt_len, model["d_model"], model["vocab"]
    params = sum(k * n for _, k, n in projections(model))
    # prefill: every prompt position through every layer, causal context
    pre = batch * p * 2 * params
    pre += batch * 2 * 2 * model["attn_layers"] * d * (p * (p + 1) // 2)
    pre += batch * head_positions * 2 * d * v
    dec = sum(model_flops(model, batch, p + i + 1) for i in range(gen - 1))
    return pre + dec


def axo_calls(model: dict, layers: tuple, batch: int, prompt_len: int,
              gen: int) -> list:
    """(M, K, N) of every AxO matmul one served batch makes: each projection
    of a group named in ``layers``, in every layer, at every step."""
    shapes = [(k, n) for group, k, n in projections(model) if group in layers]
    return [(m, k, n) for m in [batch * prompt_len] + [batch] * (gen - 1)
            for k, n in shapes]


def idle_share(ctx) -> float | None:
    """Percent of the traced window with no operation on the device.  Not
    clamped: a busy time longer than the window reads below 0, so a clock
    mismatch or an op counted twice shows."""
    tr, run = ctx["trace"], ctx["run"]
    if tr is None or not tr.n_devices:
        return None
    return 100.0 * (run.window_s - tr.busy_s) / run.window_s


def idle_between(busy: list, t0: float, t1: float) -> float:
    """Length of [t0, t1] not covered by the merged ``busy`` intervals."""
    covered = sum(max(0.0, min(e, t1) - max(s, t0)) for s, e in busy)
    return (t1 - t0) - covered
