"""The Granite dense decoder (``model_type`` "granite"): how the serving
driver builds it from a configuration file, how its weights map onto the
plain reference's layout, and the shapes of its linear projections.

A model family is one file ``bench/models/<model_type>.py`` that gives
``served_model``, ``reference_weights`` and ``shapes``.
"""

from __future__ import annotations


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


def reference_weights(params: dict, cfg) -> dict:
    """The program's parameter tree in the reference's layout (views, no
    copies)."""
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


def shapes(config: dict) -> dict:
    """From the configuration file alone: the width and vocabulary, the
    number of attention layers, and the (K, N) of each linear projection of
    each layer by name, grouped as an AxO deployment names them ("attn",
    "mlp")."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    g, f = config["num_key_value_heads"], config["intermediate_size"]
    hd = d // h
    layer = {"attn": {"wq": (d, h * hd), "wk": (d, g * hd), "wv": (d, g * hd),
                      "wo": (h * hd, d)},
             "mlp": {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}}
    n = config["num_hidden_layers"]
    return {"d_model": d, "vocab": config["vocab_size"], "attn_layers": n,
            "layers": [layer] * n}
