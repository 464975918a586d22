"""The Granite 4.0 hybrid decoder (``model_type`` "granitemoehybrid"):
Mamba-2 mixers and attention without positions, each layer with a dense
SiLU-gated MLP.  How the serving driver builds it from a configuration
file, how its weights map onto the plain reference's layout, and the
shapes of its linear projections.
"""

from __future__ import annotations

#: factors on the drawn SSM weights, so that the recurrence carries most of
#: a Mamba layer's output: normal(0, 0.02) conv weights pass about 2% of
#: their input, and A = -exp(1) forgets the state within a step or two.
#: Powers of two, so the served bfloat16 weights are the reference's.
SSM_DRAW = {"conv_w": 32.0, "a_log": -2.0}


def _period(types: list) -> int:
    """The shortest period of the layer pattern; a pattern that does not
    repeat whole is refused."""
    n = len(types)
    for p in range(1, n + 1):
        if n % p == 0 and types == types[:p] * (n // p):
            if p == n:
                break
            return p
    raise ValueError(f"layer_types does not repeat a block: {types}")


def served_model(config: dict):
    """The program's ModelConfig for a configuration file, and the factors
    folded into its weights: Granite's multipliers as ``granite.py`` folds
    them (the residual stream divided by ``residual_multiplier``), and the
    SSM draw (``SSM_DRAW``).  The gated norm inside each Mamba mixer reads
    no residual stream and keeps the published ``rms_norm_eps``."""
    from repro.configs.base import ModelConfig, SSMConfig, StageConfig

    for key, want in (("hidden_act", "silu"), ("model_type", "granitemoehybrid"),
                      ("position_embedding_type", "nope"),
                      ("num_local_experts", 0), ("mamba_proj_bias", False),
                      ("mamba_conv_bias", True), ("attention_bias", False)):
        if config[key] != want:
            raise ValueError(f"the program runs {key}={want!r}, the "
                             f"configuration states {config[key]!r}")
    types = list(config["layer_types"])
    if len(types) != config["num_hidden_layers"]:
        raise ValueError("layer_types does not name every layer")
    period = _period(types)
    kinds = {"mamba": "mamba", "attention": "attn"}
    block = tuple((kinds[t], "dense") for t in types[:period])
    d, h = config["hidden_size"], config["num_attention_heads"]
    e, r = config["embedding_multiplier"], config["residual_multiplier"]
    fold = {"tok": e / r,
            "norm_f": r / (e * config["logits_scaling"]),
            "wq": config["attention_multiplier"] * (d // h) ** 0.5,
            **SSM_DRAW}
    eps = float(config["rms_norm_eps"])
    ssm = SSMConfig(
        d_state=config["mamba_d_state"], d_conv=config["mamba_d_conv"],
        expand=config["mamba_expand"], head_dim=config["mamba_d_head"],
        n_groups=config["mamba_n_groups"], chunk=config["mamba_chunk_size"],
        norm_eps=eps)
    if ssm.expand * d // ssm.head_dim != config["mamba_n_heads"]:
        raise ValueError("mamba_n_heads is not expand x hidden_size / mamba_d_head")
    cfg = ModelConfig(
        name=config["name"], family="hybrid", d_model=d, n_heads=h,
        kv_heads=config["num_key_value_heads"],
        d_ff=config["shared_intermediate_size"], vocab=config["vocab_size"],
        stages=(StageConfig(repeats=len(types) // period, layers=block),),
        ssm=ssm, pos_encoding="none", norm_eps=eps / r ** 2,
        tie_embeddings=bool(config["tie_word_embeddings"]),
    )
    return cfg, fold


def reference_weights(params: dict, cfg) -> dict:
    """The program's parameter tree in the reference's layout: per layer,
    in order, its kind, the block position's weights stacked over repeats
    (views, no copies) and its repeat; the SSM draw applied."""
    stage, st = cfg.stages[0], params["stages"]["0"]
    d = cfg.d_model
    blocks = []
    for i, (mixer, _) in enumerate(stage.layers):
        lp, mix = st[str(i)], st[str(i)]["mixer"]
        w = {"norm1": lp["norm1"], "norm2": lp["norm2"],
             "w_gate": lp["mlp"]["w_gate"], "w_up": lp["mlp"]["w_up"],
             "w_down": lp["mlp"]["w_down"]}
        if mixer == "mamba":
            w.update({k: mix[k] for k in ("in_proj", "out_proj", "conv_b",
                                          "dt_bias", "d_skip", "norm")})
            w.update({k: mix[k] * f for k, f in SSM_DRAW.items()})
        else:
            n = stage.repeats
            w.update(wq=mix["wq"].reshape(n, d, -1), wk=mix["wk"].reshape(n, d, -1),
                     wv=mix["wv"].reshape(n, d, -1), wo=mix["wo"].reshape(n, -1, d))
        blocks.append((mixer, w))
    layers = [(blocks[i][0], blocks[i][1], r)
              for r in range(stage.repeats) for i in range(len(blocks))]
    return {"embed": params["embed"]["tok"], "norm_f": params["norm_f"],
            "layers": layers}


def shapes(config: dict) -> dict:
    """From the configuration file alone: the width and vocabulary, the
    number of attention layers, and the (K, N) of each linear projection of
    each layer by name, grouped as an AxO deployment names them ("ssm",
    "attn", "mlp")."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    g, f = config["num_key_value_heads"], config["shared_intermediate_size"]
    hd = d // h
    inner = config["mamba_expand"] * d
    state = config["mamba_n_groups"] * config["mamba_d_state"]
    mlp = {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    kinds = {
        "mamba": {"ssm": {"in_proj": (d, 2 * inner + 2 * state + config["mamba_n_heads"]),
                          "out_proj": (inner, d)}, "mlp": mlp},
        "attention": {"attn": {"wq": (d, h * hd), "wk": (d, g * hd),
                               "wv": (d, g * hd), "wo": (h * hd, d)}, "mlp": mlp},
    }
    types = config["layer_types"]
    return {"d_model": d, "vocab": config["vocab_size"],
            "attn_layers": types.count("attention"),
            "layers": [kinds[t] for t in types]}
