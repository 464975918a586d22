"""Plain reference for the approximate-operator configurations (numpy, float64).

Imports nothing of the program.  From the configuration's own description:

* the LUT-level model of the signed N x N multiplier (AxOMaP / AppAxO): rows
  of multiplier-bit pairs, each a (N+2)-bit carry-chain adder whose columns
  0..N are removable LUTs.  Removing a column zeroes its sum bit and its
  carry out.  The top row subtracts (two's complement);
* BEHAV: AVG_ABS_REL_ERR (percent), exhaustive over all 2^(2N) operand pairs;
* PPA: the analytical synthesis model's PDPLUT = POWER * CPD * LUTS;
* the exact 2-D hypervolume of a front (minimisation).

Every product is evaluated from the config bits: nothing is read from the
program's tables.
"""

from __future__ import annotations

import functools

import numpy as np

# Synthesis-model constants, as the configuration states them.
T_ROUTE, T_LUT, T_MUX, T_FAN = 0.60, 0.45, 0.065, 0.004       # ns
P_BASE, K_SUM, K_MERGE, K_LUT = 40.0, 9.0, 7.0, 1.4           # uW


def _values(n: int) -> np.ndarray:
    """Operand values of codes 0..2^n-1, two's complement."""
    u = np.arange(1 << n, dtype=np.int64)
    return np.where(u >= (1 << (n - 1)), u - (1 << n), u)


@functools.lru_cache(maxsize=None)
def row_table(n: int) -> np.ndarray:
    """(2[top], 2[a0], 2[a1], 2^n[b], 2^(n+1)[mask]) signed row values."""
    w, cols = n + 2, n + 1
    b = _values(n)[None, None, None, :, None]
    top = np.arange(2)[:, None, None, None, None]
    a0 = np.arange(2)[None, :, None, None, None]
    a1 = np.arange(2)[None, None, :, None, None]
    mask = np.arange(1 << cols)[None, None, None, None, :]
    modw = (1 << w) - 1
    t1 = np.where(a0 == 1, b & modw, 0)
    t2 = np.where(a1 == 1, (np.where(top == 1, -b, b) << 1) & modw, 0)
    shape = np.broadcast_shapes(t1.shape, t2.shape, mask.shape)
    s = np.zeros(shape, np.int64)
    carry = np.zeros(shape, np.int64)
    for j in range(w):
        x, y = (t1 >> j) & 1, (t2 >> j) & 1
        bit = x ^ y ^ carry
        carry = (x & y) | (carry & (x ^ y))
        if j < cols:  # a removed LUT: no sum bit, no carry out
            keep = (mask >> j) & 1
            bit, carry = bit * keep, carry * keep
        s = s | (bit << j)
    return np.where(s >= (1 << (w - 1)), s - (1 << w), s)


def masks(n: int, configs: np.ndarray) -> np.ndarray:
    """(D, L) LUT bits -> (D, rows) per-row integer keep masks."""
    cols = n + 1
    c = np.asarray(configs, np.int64).reshape(len(configs), n // 2, cols)
    return (c << np.arange(cols)).sum(-1)


def products(n: int, configs: np.ndarray) -> np.ndarray:
    """(D, 2^n[a], 2^n[b]) approximate products, from the config bits."""
    tab = row_table(n)
    m = masks(n, configs)
    a = np.arange(1 << n)
    out = np.zeros((len(m), 1 << n, 1 << n), np.int64)
    for r in range(n // 2):
        top = int(r == n // 2 - 1)
        a0, a1 = (a >> (2 * r)) & 1, (a >> (2 * r + 1)) & 1
        # (D, a, b): row value for each config's mask, each a's bit pair
        rows = tab[top][a0, a1][:, :, m[:, r]]          # (a, b, D)
        out += np.moveaxis(rows, -1, 0) << (2 * r)
    return out


def behav(n: int, configs: np.ndarray) -> np.ndarray:
    """AVG_ABS_REL_ERR (percent) of each config, exhaustive, float64."""
    v = _values(n)
    exact = v[:, None] * v[None, :]
    denom = np.maximum(np.abs(exact), 1).astype(np.float64)
    out = np.empty(len(configs))
    for i in range(0, len(configs), 64):
        err = np.abs(products(n, configs[i:i + 64]) - exact[None])
        out[i:i + 64] = 100.0 * (err / denom[None]).mean(axis=(1, 2))
    return out


@functools.lru_cache(maxsize=None)
def _activity(n: int) -> tuple[np.ndarray, np.ndarray]:
    """Per (top, mask): summed 2p(1-p) of the row's sum bits and of the
    16-bit row output bits, under uniform (a0, a1, b)."""
    tab = row_table(n)
    w = n + 2
    u = tab & ((1 << w) - 1)
    u16 = tab & 0xFFFF
    p_sum = np.stack([((u >> j) & 1).mean(axis=(1, 2, 3)) for j in range(w)], -1)
    p_out = np.stack([((u16 >> j) & 1).mean(axis=(1, 2, 3)) for j in range(16)], -1)
    return ((2 * p_sum * (1 - p_sum)).sum(-1),
            (2 * p_out * (1 - p_out)).sum(-1))


def _merge_tree(n: int) -> tuple[int, float]:
    """(LUTs, delay ns) of the always-accurate row-merge adder tree."""
    vals, width, offset, luts, delay = n // 2, n + 2, 2, 0, 0.0
    while vals > 1:
        adders = vals // 2
        width += 2 * offset
        luts += adders * width
        delay += T_LUT + width * T_MUX
        vals = adders + vals % 2
        offset *= 2
    return luts, delay


def _longest_run(mask: int, cols: int) -> int:
    best = run = 0
    for j in range(cols):
        if (mask >> j) & 1:
            run += 1
        else:
            best, run = max(best, run), 0
    return max(best, run + 1)  # the always-kept sign column extends it


def pdplut(n: int, configs: np.ndarray) -> np.ndarray:
    """PDPLUT of each config under the analytical synthesis model."""
    configs = np.asarray(configs)
    m = masks(n, configs)
    rows = n // 2
    kept = configs.sum(-1).astype(np.float64)
    max_run = np.array([max(_longest_run(int(x), n + 1) for x in row)
                        for row in m], np.float64)
    merge_luts, merge_delay = _merge_tree(n)
    luts = kept + rows + merge_luts
    cpd = T_ROUTE + T_LUT + T_MUX * max_run + merge_delay + T_FAN * kept
    act_sum, act_merge = _activity(n)
    top = (np.arange(rows) == rows - 1).astype(int)
    a_sum = act_sum[top[None], m].sum(-1)
    a_merge = act_merge[top[None], m].sum(-1)
    power = P_BASE + K_SUM * a_sum + K_MERGE * a_merge + K_LUT * kept
    return power * cpd * luts


def objectives(config: dict, configs: np.ndarray) -> np.ndarray:
    """(D, 2) [BEHAV, PPA] of each config, as the configuration defines them."""
    n = int(config["operator"]["n_bits"])
    configs = np.asarray(configs).reshape(-1, (n // 2) * (n + 1))
    return np.stack([behav(n, configs), pdplut(n, configs)], -1)


def hypervolume(points: np.ndarray, ref: np.ndarray) -> float:
    """Exact 2-D hypervolume dominated by ``points`` below ``ref``."""
    pts = np.asarray(points, np.float64).reshape(-1, 2)
    pts = pts[np.all(pts <= ref, axis=1)]
    pts = pts[np.lexsort((pts[:, 1], pts[:, 0]))]
    hv, y_prev = 0.0, float(ref[1])
    for x, y in pts:
        if y < y_prev:
            hv += (ref[0] - x) * (y_prev - y)
            y_prev = y
    return float(hv)
