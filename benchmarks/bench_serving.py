"""Serving benchmark: tokens/sec vs BEHAV across AxO rank x batch (EXPERIMENTS.md
§Serving).

Serves a reduced LM exactly and fully-AxO-deployed (every attention q/k/v/o,
MLP projection and the LM head on the approximate operator, weights quantized
once at deploy time), sweeping factorization rank R x batch through
``ExecutionContext``-resolved kernels.  Per cell:

  * tokens/sec for prefill+decode greedy generation,
  * free-running token match vs the exact serving path,
  * teacher-forced top-1 agreement + mean logit rel-err along the exact
    trajectory (scored on REAL generations -- the historical example compared
    logits on random normal inputs, which exercised nothing),

plus the kernel dispatch hit-rate of the padded registry-gated ``axo_matmul``
vs the historical ``% 128`` gate over the deployment's actual matmul shapes
(decode M=batch, head_dim 64 etc. all failed the old gate).

Standalone:  PYTHONPATH=src python -m benchmarks.bench_serving --quick
"""

from __future__ import annotations

import functools
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.axo import AxOOperator, deploy_axo
from repro.configs.base import ShapeConfig
from repro.configs.registry import get_arch
from repro.core.engine import ExecutionContext
from repro.core.operator_model import (
    accurate_config,
    error_tables,
    exact_product_table,
    spec_for,
)
from repro.data.synthetic import SyntheticLM
from repro.kernels.ops import on_tpu
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models.model import model_spec
from repro.models.sharding import BASE_RULES
from repro.models.spec import init_params

from .common import BenchCtx, row

ARCH = "granite-3-2b"


def _truncated_cfg(n_rows: int) -> np.ndarray:
    """Truncate the lowest partial-product column of the first ``n_rows`` CC
    rows of the 8x8 multiplier -- a deterministic family of Pareto designs,
    mild (n_rows=1) to the classic 1-column truncation (n_rows=4)."""
    spec8 = spec_for(8)
    cfgv = accurate_config(spec8)
    for r in range(n_rows):
        cfgv[r * spec8.cols_removable] = 0
    return cfgv


def _op_behav(cfgv) -> float:
    """AVG_ABS_REL_ERR (%) of the operator table vs exact products."""
    spec8 = spec_for(8)
    err = np.abs(error_tables(spec8, cfgv[None])[0]).astype(np.float64)
    exact = np.maximum(np.abs(exact_product_table(8)), 1).astype(np.float64)
    return float(100.0 * (err / exact).mean())


def _gen(prefill, decode, params, toks, gen):
    """Greedy generation; returns (tokens (B,gen), per-step logits list)."""
    plen = toks.shape[1]
    logits, cache = prefill(params, toks)
    nxt = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out, lgs = [nxt], [logits[:, -1]]
    for i in range(plen, plen + gen - 1):
        logits, cache = decode(params, cache, nxt, jnp.int32(i))
        nxt = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out.append(nxt)
        lgs.append(logits[:, -1])
    jax.block_until_ready(lgs[-1])
    return jnp.concatenate(out, 1), lgs


def _replay(prefill, decode, params, toks, trajectory):
    """Teacher-forced per-step logits along ``trajectory``."""
    plen = toks.shape[1]
    logits, cache = prefill(params, toks)
    lgs = [logits[:, -1]]
    for j in range(trajectory.shape[1] - 1):
        logits, cache = decode(
            params, cache, trajectory[:, j:j + 1], jnp.int32(plen + j))
        lgs.append(logits[:, -1])
    return lgs


def _gate_hit_rates(dep, cfg, batch, prompt_len):
    """Kernel dispatch rate over the deployment's matmul shapes: the padded
    registry path (always dispatches) vs the historical ``% 128`` gate."""
    shapes = []

    def walk(ent):
        if isinstance(ent, dict) and "bv" in ent:
            k, n = int(ent["bv"].shape[-2]), int(ent["bv"].shape[-1])
            for m in (batch * prompt_len, batch):   # prefill and decode M
                shapes.append((m, k, n))
        elif isinstance(ent, dict):
            for v in ent.values():
                walk(v)

    walk(dep.stages)
    if dep.head is not None:
        walk({"h": dep.head})
    old = sum(1 for (m, k, n) in shapes
              if m % 128 == 0 and k % 128 == 0 and n % 128 == 0)
    return len(shapes), old


def run(ctx: BenchCtx) -> list[dict]:
    rows: list[dict] = []
    ranks = (1, 16) if ctx.quick else (1, 4, 8, 16, 32)
    designs = (1, 4) if ctx.quick else (1, 2, 4)     # truncated CC rows
    batches = (2,) if ctx.quick else (2, 8)
    prompt_len, gen = (12, 8) if ctx.quick else (24, 24)
    impl = "pallas" if on_tpu() else "xla"
    ectx = ExecutionContext(backend="jax", tuning="off")

    cfg = get_arch(ARCH).reduced()
    rules = BASE_RULES
    params = init_params(model_spec(cfg), seed=ctx.seed, dtype=jnp.float32)
    max_seq = prompt_len + gen

    cfgs = {t: _truncated_cfg(t) for t in designs}
    for t, cfgv in cfgs.items():
        rows.append(row(f"serving.op_t{t}_behav_pct", 0.0,
                        f"{_op_behav(cfgv):.3f}"))

    for batch in batches:
        data = SyntheticLM(cfg, ShapeConfig("serve", max_seq, batch, "train"),
                           seed=ctx.seed)
        toks = jnp.asarray(data.batch(0)["tokens"])[:, :prompt_len]

        prefill = jax.jit(make_prefill_step(cfg, rules, max_seq=max_seq))
        decode = jax.jit(make_decode_step(cfg, rules))
        _gen(prefill, decode, params, toks, gen)            # warm
        t0 = time.perf_counter()
        exact_toks, exact_lgs = _gen(prefill, decode, params, toks, gen)
        dt = time.perf_counter() - t0
        rows.append(row(f"serving.exact_b{batch}", dt * 1e6 / (batch * gen),
                        f"{batch * gen / dt:.1f} tok/s"))

        for t, cfgv in cfgs.items():
            for rank in ranks:
                op = AxOOperator.from_config(cfgv, rank=rank)
                dep = deploy_axo(params, op, cfg, impl=impl, ctx=ectx)
                pre_a = functools.partial(prefill, axo=dep)
                dec_a = functools.partial(decode, axo=dep)
                _gen(pre_a, dec_a, params, toks, gen)       # warm
                t0 = time.perf_counter()
                axo_toks, _ = _gen(pre_a, dec_a, params, toks, gen)
                dt = time.perf_counter() - t0

                match = float((axo_toks == exact_toks).mean())
                rep = _replay(pre_a, dec_a, params, toks, exact_toks)
                top1 = float(np.mean([
                    (jnp.argmax(a, -1) == jnp.argmax(e, -1)).mean()
                    for a, e in zip(rep, exact_lgs)]))
                rel = float(np.mean([
                    jnp.linalg.norm(a - e) / jnp.maximum(jnp.linalg.norm(e), 1e-9)
                    for a, e in zip(rep, exact_lgs)]))
                rows.append(row(
                    f"serving.axo_t{t}_r{rank}_b{batch}",
                    dt * 1e6 / (batch * gen),
                    f"{batch * gen / dt:.1f} tok/s match={match:.2f} "
                    f"top1={top1:.2f} rel={rel:.4f}"))

        total, old_hits = _gate_hit_rates(
            deploy_axo(params, AxOOperator.from_config(cfgs[designs[0]],
                                                       rank=ranks[-1]),
                       cfg, impl=impl), cfg, batch, prompt_len)
        rows.append(row(
            f"serving.kernel_hit_rate_b{batch}", 0.0,
            f"padded {total}/{total} vs old %128 gate {old_hits}/{total}"))
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    for r in run(BenchCtx(quick=args.quick, seed=args.seed)):
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
