"""Driver for serving traffic: closed-loop greedy batches through the jitted
prefill and decode steps, with or without an AxO deployment.

Set-up builds the program's model from the configuration file through its
model family (``bench/models/<model_type>.py``), makes every weight on the
device from the seed in one jitted call (bfloat16, as served, with any
factors the family folds in), deploys the traffic's approximate operator
(``deploy_axo``) if it names one,
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
from pathlib import Path

import numpy as np

import generate
from run import Check, load_module

MODELS = Path(__file__).resolve().parents[1] / "models"


def family(config: dict):
    """The model family ``bench/models/<model_type>.py`` of a configuration:
    its ``served_model``, ``reference_weights`` and ``shapes``."""
    path = MODELS / f"{config.get('model_type')}.py"
    if not path.is_file():
        raise ValueError(f"no model family for model_type "
                         f"{config.get('model_type')!r}: looked for {path}")
    return load_module(path)


def served_model(config: dict):
    """The program's ModelConfig for a configuration file, and the factors
    folded into its weights where the program's model has no such knob."""
    return family(config).served_model(config)


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
        each step produced, (B, 1, V) on the device, for the check: held as
        they are, so that the batch whose logits are kept dispatches no more
        work a step than any other."""
        jnp, run = self.jnp, self.run
        with run.span("bench.prefill"):
            logits, cache = self.prefill(self.params, jnp.asarray(prompts))
            nxt = self.argmax(logits)
            out = [np.asarray(nxt)]
        if keep is not None:
            keep.append(logits)
        t_prev = time.perf_counter()
        for i in range(self.p, self.p + self.g - 1):
            with run.span("bench.decode"):
                logits, cache = self.decode(self.params, cache, nxt,
                                            jnp.int32(i))
                nxt = self.argmax(logits)
                out.append(np.asarray(nxt))
            if keep is not None:
                keep.append(logits)
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
    fam = family(config)
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
    weights = fam.reference_weights(make_weights(cfg, std, run.seed), cfg)
    gaps_ref, err = [], None
    for b in picked:
        prompts = generate.prompts(traffic, cfg.vocab, run.seed, b)
        ref = reference.served_logits(config, weights, prompts, served[b],
                                      axo=traffic["axo"])
        tokens, logits = served[b], None
        if b == 0:
            import jax.numpy as jnp

            logits = jnp.concatenate(kept, axis=1)     # (B, gen, V)
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
                  "model": fam.shapes(config)},
    }
