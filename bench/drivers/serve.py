"""Driver for serving traffic: closed-loop greedy batches through the jitted
prefill and decode steps, with or without an AxO deployment.

Set-up builds the program's model from the configuration file, makes every
weight on the device from the seed in one jitted call (bfloat16, as served,
with the Granite multipliers folded in: see ``served_model``), deploys the traffic's approximate operator (``deploy_axo``) if it names one,
and serves one warm-up batch, so every program the window runs is compiled.
The window serves batches back to back.  Each batch is ``batch`` requests
of ``prompt_len`` token ids from the seed, decoded greedily for ``gen``
tokens; a token counts as produced when it is on the host.  A batch started
before the window's end runs to its last token, and the window closes then.

After the window the program's state is freed, the weights are drawn again
from the seed as the configuration states them (no multiplier folded in),
and a sample of whole batches drawn from the seed (the first and the last
always among them) is checked: the plain reference runs teacher-forced over
each prompt and its served tokens, and
the number compared is the widest gap by which a served token's reference
logit lies below the reference's best at that position.
"""

from __future__ import annotations

import functools
import time

import numpy as np

import generate
from run import Check


def served_model(config: dict):
    """The program's ModelConfig for a configuration file, and the factors
    that fold the Granite multipliers into its weights.

    The program's dense decoder has no Granite multiplier: it scales scores
    by 1/sqrt(head_dim), embeds unscaled, adds each branch unscaled and does
    not divide the logits.  Served on the residual stream divided by
    ``residual_multiplier`` r, the published model is that decoder exactly:
    the embedding times e/r, every branch then added unscaled, RMSNorm's eps
    divided by r^2 (the norm sees the stream r times smaller), the final
    norm's gain times r/(e * logits_scaling) (the tied head reads the
    embedding e/r times larger), and the query weights times
    attention_multiplier * sqrt(head_dim), a power of two here, so the
    query projection's 8-bit codes are the published weights' own.
    """
    from repro.configs.base import ModelConfig, StageConfig

    for key, want in (("hidden_act", "silu"), ("model_type", "granite")):
        if config[key] != want:
            raise ValueError(f"the program runs {key}={want!r}, the "
                             f"configuration states {config[key]!r}")
    d, h = config["hidden_size"], config["num_attention_heads"]
    e, r = config["embedding_multiplier"], config["residual_multiplier"]
    fold = {"tok": e / r,
            "norm_f": r / (e * config["logits_scaling"]),
            "wq": config["attention_multiplier"] * (d // h) ** 0.5}
    cfg = ModelConfig(
        name=config["name"], family="dense", d_model=d, n_heads=h,
        kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        stages=(StageConfig(repeats=config["num_hidden_layers"],
                            layers=(("attn", "dense"),)),),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]) / r ** 2,
        tie_embeddings=bool(config["tie_word_embeddings"]),
    )
    return cfg, fold


def make_weights(cfg, std: float, seed: int, fold: dict | None = None):
    """Every weight of the program's parameter tree, on the device, from the
    seed, in one jitted call: normal(0, std) matrices, RMSNorm gains 1, each
    leaf named in ``fold`` multiplied by its factor before the cast to
    bfloat16.  The draws do not depend on ``fold``."""
    import jax
    import jax.numpy as jnp

    from repro.models.model import model_spec

    leaves, treedef = jax.tree_util.tree_flatten_with_path(model_spec(cfg))
    fold = fold or {}

    @jax.jit
    def make(key):
        out = []
        for i, (path, s) in enumerate(leaves):
            if s.init == "ones":
                x = jnp.ones(s.shape, jnp.bfloat16)
            else:
                x = jax.random.normal(jax.random.fold_in(key, i),
                                      s.shape, jnp.bfloat16) * std
            f = fold.get(getattr(path[-1], "key", None), 1.0)
            out.append(x if f == 1.0 else
                       (x.astype(jnp.float32) * f).astype(jnp.bfloat16))
        return jax.tree_util.tree_unflatten(treedef, out)

    return make(jax.random.key(generate.weight_seed(seed)))


def reference_weights(params: dict, cfg) -> dict:
    """The same arrays in the reference's layout (views, no copies)."""
    st = params["stages"]["0"]["0"]
    n, d = cfg.n_layers, cfg.d_model
    mix, mlp = st["mixer"], st["mlp"]
    return {
        "embed": params["embed"]["tok"], "norm_f": params["norm_f"],
        "layers": {
            "norm1": st["norm1"], "norm2": st["norm2"],
            "wq": mix["wq"].reshape(n, d, -1), "wk": mix["wk"].reshape(n, d, -1),
            "wv": mix["wv"].reshape(n, d, -1), "wo": mix["wo"].reshape(n, -1, d),
            "w_gate": mlp["w_gate"], "w_up": mlp["w_up"],
            "w_down": mlp["w_down"],
        },
    }


def deploy(params, cfg, axo: dict | None):
    """The traffic's AxO deployment (None for the exact model)."""
    if axo is None:
        return None
    from repro.axo.deploy import AxOOperator, deploy_axo
    from repro.kernels.ops import on_tpu

    bits = np.frombuffer(axo["lut_config"].encode(), np.uint8) - ord("0")
    op = AxOOperator.from_config(bits, rank=int(axo["rank"]))
    return deploy_axo(params, op, cfg, layers=tuple(axo["layers"]),
                      impl="pallas" if on_tpu() else "xla")


class Server:
    """The jitted steps and the greedy loop between them."""

    def __init__(self, cfg, traffic: dict, params, dep, run):
        import jax
        import jax.numpy as jnp

        from repro.launch.steps import make_decode_step, make_prefill_step
        from repro.models.sharding import BASE_RULES

        self.p, self.g = int(traffic["prompt_len"]), int(traffic["gen"])
        kw = {} if dep is None else {"axo": dep}
        self.prefill = functools.partial(jax.jit(make_prefill_step(
            cfg, BASE_RULES, max_seq=self.p + self.g)), **kw)
        self.decode = functools.partial(
            jax.jit(make_decode_step(cfg, BASE_RULES)), **kw)
        self.argmax = jax.jit(
            lambda lg: jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32))
        self.params, self.run, self.jnp = params, run, jnp

    def batch(self, prompts: np.ndarray, gaps: list, keep: list | None = None):
        """Serve one batch; returns its tokens (B, gen) and appends the
        inter-token gaps (seconds) to ``gaps``.  ``keep`` collects the logits
        each step produced (on the device, for the check)."""
        jnp, run = self.jnp, self.run
        with run.span("bench.prefill"):
            logits, cache = self.prefill(self.params, jnp.asarray(prompts))
            nxt = self.argmax(logits)
            out = [np.asarray(nxt)]
        if keep is not None:
            keep.append(logits[:, -1])
        t_prev = time.perf_counter()
        for i in range(self.p, self.p + self.g - 1):
            with run.span("bench.decode"):
                logits, cache = self.decode(self.params, cache, nxt,
                                            jnp.int32(i))
                nxt = self.argmax(logits)
                out.append(np.asarray(nxt))
            if keep is not None:
                keep.append(logits[:, -1])
            t = time.perf_counter()
            gaps.append(t - t_prev)
            t_prev = t
        run.note_memory()   # the batch's cache is still alive here
        return np.concatenate(out, axis=1)


def logit_err(ref_logits, logits) -> float:
    """Widest relative error of served logits over the positions: the norm
    of their difference from the reference's, over the norm of the
    reference's logits about their mean."""
    import jax.numpy as jnp

    ref = ref_logits.astype(jnp.float32)
    diff = jnp.linalg.norm(logits.astype(jnp.float32) - ref, axis=-1)
    spread = jnp.linalg.norm(ref - ref.mean(-1, keepdims=True), axis=-1)
    return float(jnp.max(diff / spread))


def logit_gaps(ref_logits, tokens: np.ndarray):
    """Per position: how far the token's reference logit lies below the
    reference's best there (0 where the token is the reference's argmax)."""
    import jax.numpy as jnp

    tok = jnp.asarray(tokens)[..., None]
    got = jnp.take_along_axis(ref_logits, tok, axis=-1)[..., 0]
    return np.asarray(ref_logits.max(-1) - got).ravel()


def run(cell, run, reference, control: str | None = None) -> dict:
    """Set up, measure, check.  ``control`` (a precision of the reference,
    "fp8") reads in place of the served tokens the tokens that the
    reference computed in that precision puts first at the same positions."""
    import gc

    config, traffic = cell.config, cell.traffic
    std = float(config["initializer_range"])
    with run.span("bench.setup"):
        cfg, fold = served_model(config)
        params = make_weights(cfg, std, run.seed, fold)
        dep = deploy(params, cfg, traffic["axo"])
        server = Server(cfg, traffic, params, dep, run)
        server.batch(generate.prompts(traffic, cfg.vocab, run.seed, -1), [],
                     keep=[])

    seconds = run.seconds
    if run.trace:  # a traced window is short: traces are large
        seconds = min(seconds, float(traffic["trace_seconds"]))
    served, gaps, kept = [], [], []
    run.open_window()
    t_end = run.t_window + seconds
    while time.perf_counter() < t_end:
        prompts = generate.prompts(traffic, cfg.vocab, run.seed, len(served))
        with run.span("bench.batch"):
            served.append(server.batch(prompts, gaps,
                                       keep=None if served else kept))
    run.close_window()
    del server, dep, params
    gc.collect()

    # the first batch's logits, and the tokens of a sample of batches drawn
    # from the seed (the first and the last always among them)
    n_tok = sum(s.size for s in served)
    picked = generate.sample(len(served), int(traffic["check_batches"]),
                             run.seed, must=(0, len(served) - 1))
    # the weights as the configuration states them, drawn again from the seed
    weights = reference_weights(make_weights(cfg, std, run.seed), cfg)
    gaps_ref, err = [], None
    for b in picked:
        prompts = generate.prompts(traffic, cfg.vocab, run.seed, b)
        ref = reference.served_logits(config, weights, prompts, served[b],
                                      axo=traffic["axo"])
        tokens, logits = served[b], None
        if b == 0:
            import jax.numpy as jnp

            logits = jnp.stack(kept, axis=1)            # (B, gen, V)
        if control:
            low = reference.served_logits(config, weights, prompts, served[b],
                                          axo=traffic["axo"], precision=control)
            tokens = np.asarray(low.argmax(-1))
            logits = low if b == 0 else None
        if logits is not None:
            err = logit_err(ref, logits)
        gaps_ref.append(logit_gaps(ref, tokens))
        del ref, logits
    gaps_ref = np.concatenate(gaps_ref)
    readings = {"logit_gap": float(gaps_ref.max()), "logit_err": err}
    checks = [Check(k, readings[k], float(v))
              for k, v in traffic["limits"].items()]
    batch = int(traffic["batch"])
    return {
        "attempted": batch * len(served), "failed": 0,
        "checks": checks,
        "metrics": {
            "output_tok_per_s": n_tok / run.window_s,
            "token_gap_p95_ms": float(np.percentile(gaps, 95)) * 1e3,
        },
        "layer": {"tokens": n_tok, "batches": len(served), "batch": batch,
                  "prompt_len": int(traffic["prompt_len"]),
                  "gen": int(traffic["gen"]),
                  "axo": traffic["axo"],
                  "model": {"d_model": cfg.d_model, "n_heads": cfg.n_heads,
                            "kv_heads": cfg.kv_heads, "d_ff": cfg.d_ff,
                            "vocab": cfg.vocab, "n_layers": cfg.n_layers}},
    }
