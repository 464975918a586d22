"""Serving driver: prefill a batch of prompts, decode with a KV cache --
optionally with AxO-approximate arithmetic deployed in every linear layer
(the paper's operators in the serving path, via ``deploy_axo``).

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \\
      --batch 4 --prompt-len 24 --gen 16 [--axo-rank 8]
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..axo import AXO_LAYERS, AxOOperator, deploy_axo
from ..configs.base import ShapeConfig
from ..configs.registry import ARCH_IDS, get_arch
from ..data.synthetic import SyntheticLM
from ..kernels.ops import on_tpu
from ..models.model import model_spec
from ..models.sharding import BASE_RULES
from ..models.spec import init_params
from ..obs import telemetry as obs
from .compile_cache import enable_compile_cache
from .steps import make_decode_step, make_prefill_step


def demo_operator(rank: int) -> AxOOperator:
    """The classic 1-column truncated multiplier (drop the lowest
    partial-product column of every row) -- a mild, deterministic Pareto
    design; no DSE run needed for a serving demo."""
    from ..core.operator_model import accurate_config, spec_for

    spec8 = spec_for(8)
    op_cfg = accurate_config(spec8)
    for r in range(spec8.rows):
        op_cfg[r * spec8.cols_removable] = 0
    return AxOOperator.from_config(op_cfg, rank=rank)


def main(argv=None) -> dict:
    """Serve the requests; returns what it measured (timings, the AxO fidelity
    numbers and the DSE smoke results) for callers that check it."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCH_IDS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--axo-rank", type=int, default=0,
                    help=">0: deploy a rank-R AxO operator into every linear "
                         "layer and report divergence on the decoded trajectory")
    ap.add_argument("--axo-layers", nargs="+", default=list(AXO_LAYERS),
                    choices=list(AXO_LAYERS))
    ap.add_argument("--axo-impl", default=None, choices=["xla", "pallas"])
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace JSON of the serving spans "
                         "(load at ui.perfetto.dev) and print the per-request "
                         "latency histograms")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve GET /metrics (Prometheus text exposition of "
                         "the live obs.GLOBAL state) and GET /healthz (device "
                         "liveness + tuning cache + deployment status) on "
                         "this port; 0 picks an ephemeral port")
    ap.add_argument("--requests", type=int, default=1,
                    help="number of exact serving requests to run (>1 fills "
                         "the latency histograms for scraping)")
    ap.add_argument("--hold", type=float, default=0.0, metavar="SECONDS",
                    help="keep the process (and the metrics endpoint) alive "
                         "this long after serving, so a scraper can collect")
    ap.add_argument("--dse-service", action="store_true",
                    help="mount the persistent DSE service on the metrics "
                         "server: POST /dse submits a (n_bits, op, signed, "
                         "app, const_sf, seed, method) job into the batched "
                         "queue, GET /dse?id=<job> polls its result, GET "
                         "/dse/library reports the operator-library status; "
                         "requires --metrics-port")
    ap.add_argument("--dse-smoke", type=int, default=0, metavar="N",
                    help="after serving, POST N small DSE requests to the "
                         "live endpoint and wait for their fronts (endpoint "
                         "self-test; implies --dse-service)")
    ap.add_argument("--dse-pop", type=int, default=16,
                    help="service GA population per request lane")
    ap.add_argument("--dse-gens", type=int, default=8,
                    help="service GA generations per request lane")
    args = ap.parse_args(argv)
    if args.dse_smoke:
        args.dse_service = True
    if args.dse_service and args.metrics_port is None:
        ap.error("--dse-service requires --metrics-port")
    enable_compile_cache()

    # one sink for the whole driver: prefill/decode latency histograms and
    # tokens/sec gauges always collect (counters chain to the process
    # aggregate); --trace additionally exports the span tree
    tel = obs.Telemetry("serve", parent=obs.GLOBAL)

    # /metrics scrapes the process-wide aggregate (which sees this driver's
    # sink through the parent chain), so anything else the process records --
    # kernel dispatch counters, pad waste, tuning traffic -- is exposed too
    metrics = None
    if args.metrics_port is not None:
        from ..obs.prom import MetricsServer

        metrics = MetricsServer(tel=obs.GLOBAL, port=args.metrics_port).start()
        print(f"metrics: {metrics.url}/metrics  health: {metrics.url}/healthz")

    # DSE service: job intake + result polling + library status ride the
    # same server; the queue coalesces compatible requests into single
    # run_dse_sweep dispatches and the operator library persists their fronts
    dse_queue = None
    if args.dse_service:
        from ..core.dse import DSESettings
        from ..service import (
            DSEJobQueue, DSERequest, OperatorStore, default_runner,
        )
        from ..service.store import store_status

        dse_store = OperatorStore()
        dse_queue = DSEJobQueue(default_runner(
            settings=DSESettings(pop_size=args.dse_pop, n_gen=args.dse_gens,
                                 backend="jax"),
            store=dse_store,
        ))

        def post_dse(payload: dict) -> dict:
            job_id = dse_queue.submit(DSERequest.from_dict(payload))
            return {"job_id": job_id, "queued": dse_queue.depth()}

        def get_dse(params: dict) -> dict:
            res = dse_queue.result(params["id"])
            return res if res is not None else {"status": "pending"}

        metrics.add_route("POST", "/dse", post_dse)
        metrics.add_route("GET", "/dse", get_dse)
        metrics.add_route("GET", "/dse/library",
                          lambda params: store_status(dse_store))
        print(f"dse service: POST {metrics.url}/dse "
              f"(library: {dse_store.root})")

    cfg = get_arch(args.arch)
    if not args.full_config:
        cfg = cfg.reduced()
    rules = BASE_RULES
    max_seq = args.prompt_len + args.gen

    params = init_params(model_spec(cfg), seed=args.seed)
    shape = ShapeConfig("serve", max_seq, args.batch, "train")
    data = SyntheticLM(cfg, shape, seed=args.seed)
    b = data.batch(0)
    toks = jnp.asarray(b["tokens"])[:, : args.prompt_len]
    frontend = None
    if "enc_embeds" in b:
        frontend = jnp.asarray(b["enc_embeds"], jnp.bfloat16)
    if "img_embeds" in b:
        frontend = jnp.asarray(b["img_embeds"], jnp.bfloat16)

    prefill = jax.jit(make_prefill_step(cfg, rules, max_seq=max_seq))
    decode = jax.jit(make_decode_step(cfg, rules))

    def serve(pre_fn, dec_fn, label="exact"):
        """Greedy generation; returns (tokens, last-step logits, timings).

        Each call is one request span: prefill latency + per-step decode
        latency land in the telemetry histograms, the request's decode
        throughput in a tokens/sec gauge.
        """
        with tel.span("serve.request", label=label, batch=args.batch,
                      prompt_len=args.prompt_len, gen=args.gen):
            t0 = time.perf_counter()
            with tel.span("serve.prefill"):
                pre_args = (
                    (params, toks) if frontend is None else (params, toks, frontend)
                )
                logits, cache = pre_fn(*pre_args)
                nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
            generated, lgs = [nxt], [logits[:, -1]]
            t_pre = time.perf_counter() - t0
            tel.observe("serve.prefill_ms", t_pre * 1e3)
            t0 = time.perf_counter()
            with tel.span("serve.decode", steps=args.gen - 1):
                for i in range(args.prompt_len, args.prompt_len + args.gen - 1):
                    ts = time.perf_counter()
                    logits, cache = dec_fn(params, cache, nxt, jnp.int32(i))
                    nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(
                        jnp.int32
                    )
                    tel.observe(
                        "serve.decode_step_ms",
                        (time.perf_counter() - ts) * 1e3,
                    )
                    generated.append(nxt)
                    lgs.append(logits[:, -1])
            t_dec = time.perf_counter() - t0
            n_tok = args.batch * (args.gen - 1)
            if t_dec > 0:
                tel.gauge("serve.tokens_per_s", n_tok / t_dec)
                tel.observe("serve.tokens_per_s", n_tok / t_dec)
            tel.count("serve.requests")
        return jnp.concatenate(generated, axis=1), lgs, (t_pre, t_dec)

    for _ in range(max(0, args.requests - 1)):
        serve(prefill, decode)  # warm repeats: histogram filler for scraping
    out, exact_lgs, (t_prefill, t_decode) = serve(prefill, decode)
    print(f"arch={cfg.name} prefill({args.batch}x{args.prompt_len})="
          f"{t_prefill*1e3:.1f}ms decode({args.gen - 1} steps)={t_decode*1e3:.1f}ms")
    print("generated token ids (row 0):", np.asarray(out[0]).tolist())
    report = {"arch": cfg.name, "prefill_s": t_prefill, "decode_s": t_decode}
    if metrics is not None:
        metrics.set_deployment({"mode": "exact", "arch": cfg.name})

    if args.axo_rank > 0:
        # deploy the operator into every requested linear layer, rebuild the
        # steps around the deployment, and serve the SAME prompts -- the
        # divergence is scored on the decoded trajectory, not random inputs
        op = demo_operator(args.axo_rank)
        impl = args.axo_impl or ("pallas" if on_tpu() else "xla")
        dep = deploy_axo(params, op, cfg, layers=tuple(args.axo_layers),
                         impl=impl)
        pre_a = functools.partial(prefill, axo=dep)
        dec_a = functools.partial(decode, axo=dep)
        out_a, _, _ = serve(pre_a, dec_a, label="axo")  # warm + free-run tokens
        _, axo_lgs, (tp, td) = serve(pre_a, dec_a, label="axo")

        # teacher-forced comparison along the exact trajectory
        pre_args = (params, toks) if frontend is None else (params, toks, frontend)
        logits, cache = pre_a(*pre_args)
        replay = [logits[:, -1]]
        for j in range(out.shape[1] - 1):
            logits, cache = dec_a(params, cache, out[:, j:j + 1],
                                  jnp.int32(args.prompt_len + j))
            replay.append(logits[:, -1])
        top1 = float(np.mean([
            float((jnp.argmax(a, -1) == jnp.argmax(e, -1)).mean())
            for a, e in zip(replay, exact_lgs)]))
        # norms in f32: bf16 logits have no numpy scalar equivalent
        rel = float(np.mean([
            float(jnp.linalg.norm((a - e).astype(jnp.float32))
                  / jnp.maximum(jnp.linalg.norm(e.astype(jnp.float32)), 1e-9))
            for a, e in zip(replay, exact_lgs)]))
        match = float((out_a == out).mean())
        print(f"axo rank={args.axo_rank} ({dep.n_entries} projections, {impl}): "
              f"prefill={tp*1e3:.1f}ms decode={td*1e3:.1f}ms  "
              f"free-run match={match:.2%} teacher-forced top1={top1:.2%} "
              f"logit rel_err={rel:.4f}")
        report["axo"] = {"impl": impl, "projections": dep.n_entries,
                         "top1": top1, "free_run_match": match,
                         "logit_rel_err": rel, "prefill_s": tp, "decode_s": td}
        tel.gauge("serve.axo_top1", top1)
        tel.gauge("serve.axo_free_run_match", match)
        tel.gauge("serve.axo_logit_rel_err", rel)
        if metrics is not None:
            metrics.set_deployment({
                "mode": "axo", "arch": cfg.name, "rank": args.axo_rank,
                "impl": impl, "layers": list(args.axo_layers),
                "projections": dep.n_entries,
                "top1": top1, "free_run_match": match,
            })

    if args.trace is not None:
        tel.to_chrome_trace(args.trace)
        print(f"chrome trace: {args.trace} ({len(tel.spans)} spans; "
              "load at ui.perfetto.dev)")
        for h in ("serve.prefill_ms", "serve.decode_step_ms"):
            s = tel.histogram_summary(h)
            print(f"{h}: n={s['count']} p50={s['p50']:.1f} p90={s['p90']:.1f} "
                  f"max={s['max']:.1f}")
        print(f"serve.tokens_per_s: {tel.gauges['serve.tokens_per_s']:.1f} "
              f"(last request)")

    if args.dse_smoke:
        # endpoint self-test: post a small burst through the live HTTP
        # surface (not the queue object) and wait for every front
        import json as _json
        import urllib.request

        t0 = time.perf_counter()
        jobs = []
        report["dse"] = []
        for i in range(args.dse_smoke):
            body = _json.dumps({
                "n_bits": 4, "const_sf": 0.5 + 0.3 * (i % 2), "seed": i // 2,
            }).encode()
            req = urllib.request.Request(
                f"{metrics.url}/dse", data=body,
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req) as resp:
                jobs.append(_json.loads(resp.read())["job_id"])
        if not dse_queue.join(timeout=600):
            raise RuntimeError("dse smoke: jobs did not finish in 600s")
        for jid in jobs:
            with urllib.request.urlopen(f"{metrics.url}/dse?id={jid}") as resp:
                res = _json.loads(resp.read())
            if res["status"] != "done":
                raise RuntimeError(f"dse smoke: {jid} -> {res}")
            report["dse"].append(res)
            print(f"dse {jid}: const_sf={res['request']['const_sf']} "
                  f"seed={res['request']['seed']} hv={res['hv_vpf']:.4g} "
                  f"front={len(res['front'])}")
        print(f"dse smoke: {args.dse_smoke} requests -> "
              f"{obs.GLOBAL.counter('service.batches')} batched dispatch(es) "
              f"in {time.perf_counter() - t0:.1f}s")

    if metrics is not None and args.hold > 0:
        print(f"holding {args.hold:.0f}s for scrapers ({metrics.url}/metrics)")
        time.sleep(args.hold)
    if dse_queue is not None:
        dse_queue.close()
    if metrics is not None:
        metrics.stop()
    return report


if __name__ == "__main__":
    main()
