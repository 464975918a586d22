"""End-to-end driver: serve a (reduced) LM with batched requests through
prefill + KV-cache decode, with the paper's approximate operators deployed in
EVERY linear layer (attention q/k/v/o, MLP, LM head) via ``deploy_axo`` -- and
measure what the approximation does to the generations.

The comparison is on *actual generations*: the AxO model free-runs greedily
(its own tokens feed back) and is also replayed teacher-forced along the exact
model's trajectory, so top-1 agreement and logit error are scored where serving
actually lives -- not on random synthetic hidden states.

  PYTHONPATH=src python examples/axo_serving.py [--arch granite-3-2b]
      [--batch 4] [--prompt-len 24] [--gen 24] [--ranks 1 4 16]
      [--layers attn mlp moe head] [--impl xla|pallas]
"""

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.axo import AXO_LAYERS, AxOOperator, deploy_axo
from repro.configs.base import ShapeConfig
from repro.configs.registry import ARCH_IDS, get_arch
from repro.core.dataset import build_training_dataset
from repro.core.dse import DSESettings, map_solution_pool, run_dse
from repro.core.operator_model import accurate_config, spec_for
from repro.data.synthetic import SyntheticLM
from repro.kernels.ops import on_tpu
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models.model import model_spec
from repro.models.sharding import BASE_RULES
from repro.models.spec import init_params


def pick_operator(seed: int = 0, behav_cap: float = 1.0) -> np.ndarray:
    """Quick 8x8 DSE + library, validated exactly; cheapest design under cap.

    Serving needs the *accurate* corner of the Pareto space, which a
    demo-budget GA (pop 32, 15 generations over 2^36 configs) never reaches
    on its own -- so the DSE's validated front is merged with the
    deterministic column-truncation library, every candidate is re-scored
    with the exact behavioral + PPA models, and the cheapest (min PDPLUT)
    design with BEHAV <= ``behav_cap`` % is deployed (min-BEHAV fallback if
    none qualifies).
    """
    from repro.core.metrics import behav_metrics
    from repro.core.ppa import ppa_metrics

    spec = spec_for(8)
    ds = build_training_dataset(
        spec, n_random=600, seed=seed,
        cache_path="experiments/cache/ds8_serving.npz")
    st = DSESettings(const_sf=1.5, pop_size=32, n_gen=15, n_quad_grid=(0, 4),
                     pool_size=4, seed=seed)
    pool = map_solution_pool(spec, ds, st)
    res = run_dse(spec, ds, "map+ga", settings=st, map_pool=pool)
    library = []
    for t in range(spec.rows + 1):           # accurate, t1 .. full truncation
        cfgv = accurate_config(spec)
        for r in range(t):
            cfgv[r * spec.cols_removable] = 0
        library.append(cfgv)
    cands = np.concatenate([np.atleast_2d(res.vpf_configs),
                            np.stack(library)], axis=0).astype(np.uint8)
    behav = behav_metrics(spec, cands)["AVG_ABS_REL_ERR"]
    pdplut = ppa_metrics(spec, cands)["PDPLUT"]
    ok = behav <= behav_cap
    idx = (int(np.flatnonzero(ok)[np.argmin(pdplut[ok])]) if ok.any()
           else int(np.argmin(behav)))
    src = "dse-front" if idx < len(res.vpf_configs) else "library"
    print(f"picked {src} design: BEHAV={behav[idx]:.3f}% "
          f"PDPLUT={pdplut[idx]:.0f} (cap {behav_cap}%, "
          f"{len(cands)} validated candidates)")
    return cands[idx]


def build_steps(cfg, rules, max_seq, axo=None):
    """jit'd (prefill, decode) step pair, optionally AxO-deployed."""
    prefill = jax.jit(make_prefill_step(cfg, rules, max_seq=max_seq))
    decode = jax.jit(make_decode_step(cfg, rules))
    return (functools.partial(prefill, axo=axo),
            functools.partial(decode, axo=axo))


def generate(prefill, decode, params, toks, gen: int):
    """Greedy decode ``gen`` tokens.  Returns (tokens (B,gen), logits list)."""
    prompt_len = toks.shape[1]
    logits, cache = prefill(params, toks)
    nxt = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out, lgs = [nxt], [logits[:, -1]]
    for i in range(prompt_len, prompt_len + gen - 1):
        logits, cache = decode(params, cache, nxt, jnp.int32(i))
        nxt = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out.append(nxt)
        lgs.append(logits[:, -1])
    return jnp.concatenate(out, 1), lgs


def replay(prefill, decode, params, toks, trajectory):
    """Teacher-forced logits along a fixed generated ``trajectory`` (B, gen)."""
    prompt_len = toks.shape[1]
    logits, cache = prefill(params, toks)
    lgs = [logits[:, -1]]
    for j in range(trajectory.shape[1] - 1):
        tok = trajectory[:, j:j + 1]
        logits, cache = decode(params, cache, tok, jnp.int32(prompt_len + j))
        lgs.append(logits[:, -1])
    return lgs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b", choices=sorted(ARCH_IDS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--ranks", type=int, nargs="+", default=[1, 4, 16])
    ap.add_argument("--layers", nargs="+", default=list(AXO_LAYERS),
                    choices=list(AXO_LAYERS))
    ap.add_argument("--impl", default=None, choices=["xla", "pallas"],
                    help="AxO matmul impl (default: pallas on TPU, else the "
                         "identical-math xla contraction)")
    args = ap.parse_args()

    cfg = get_arch(args.arch).reduced()
    rules = BASE_RULES
    max_seq = args.prompt_len + args.gen
    impl = args.impl or ("pallas" if on_tpu() else "xla")
    params = init_params(model_spec(cfg), seed=0, dtype=jnp.float32)
    data = SyntheticLM(cfg, ShapeConfig("serve", max_seq, args.batch, "train"))
    toks = jnp.asarray(data.batch(0)["tokens"])[:, : args.prompt_len]

    prefill, decode = build_steps(cfg, rules, max_seq)
    generate(prefill, decode, params, toks, args.gen)  # warm the exact steps
    t0 = time.time()
    exact_toks, exact_lgs = generate(prefill, decode, params, toks, args.gen)
    dt = time.time() - t0
    print(f"exact serving: {args.batch}x{args.gen} tokens in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")

    op_cfg = pick_operator()
    for rank in args.ranks:
        op = AxOOperator.from_config(op_cfg, rank=rank)
        dep = deploy_axo(params, op, cfg, layers=tuple(args.layers), impl=impl)
        pre_a, dec_a = build_steps(cfg, rules, max_seq, axo=dep)
        generate(pre_a, dec_a, params, toks, args.gen)  # warm
        t0 = time.time()
        axo_toks, _ = generate(pre_a, dec_a, params, toks, args.gen)
        dt = time.time() - t0

        # free-running agreement: do the two serving paths emit the same tokens?
        match = float((axo_toks == exact_toks).mean())
        # teacher-forced: AxO logits along the exact trajectory, scored per step
        axo_replay = replay(pre_a, dec_a, params, toks, exact_toks)
        top1 = float(np.mean([
            (jnp.argmax(a, -1) == jnp.argmax(e, -1)).mean()
            for a, e in zip(axo_replay, exact_lgs)
        ]))
        rel = float(np.mean([
            jnp.linalg.norm(a - e) / jnp.maximum(jnp.linalg.norm(e), 1e-9)
            for a, e in zip(axo_replay, exact_lgs)
        ]))
        print(f"rank={rank:3d} ({dep.n_entries} deployed projections, {impl}): "
              f"{args.batch * args.gen / dt:.1f} tok/s  "
              f"free-run match={match:.1%}  teacher-forced top1={top1:.1%}  "
              f"logit rel_err={rel:.4f}  "
              f"(factorization cost {op.rank_behav()['AVG_ABS_REL_ERR']:.3f}% "
              f"AVG_ABS_REL_ERR)")

    print("generated ids (exact, row 0):",
          np.asarray(exact_toks[0, :12]).tolist(), "...")


if __name__ == "__main__":
    main()
