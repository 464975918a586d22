"""Mixture-of-Experts with sort-based (one-hot-free) dispatch + shard_map EP.

Dispatch/combine via argsort-by-expert + capacity-bounded scatter/gather -- the
only representation that stays tractable at 256-384 experts x 1M tokens (an
einsum one-hot dispatch tensor would be ~10^15 elements).  Tokens over capacity
are dropped (scatter mode='drop'), matching capacity-factor semantics of
Switch/GShard-family systems.

Two execution paths, one math:

* **reference / single-device**: all experts local, plain dispatch.
* **expert-parallel (EP)**: expert weights are sharded over the ``model`` mesh
  axis; activations are replicated across it (they are batch-sharded over
  ``data``).  A ``shard_map`` over ``model`` gives each shard its E/ep local
  experts; each shard dispatches *its own* experts' tokens from its full local
  activation copy (no all-to-all needed -- the activations are already there),
  computes, and the combine is a single ``psum`` over ``model`` -- the same
  collective volume as a tensor-parallel dense FFN.  Routing (softmax, top-k,
  aux loss) happens *outside* the shard_map so it is computed once under SPMD.

The EP path engages automatically when a mesh with a >1 ``model`` axis is
active and the expert count divides; otherwise the reference path runs.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from .layers import mlp_apply, mlp_spec
from .sharding import ShardingRules, constrain, _current_mesh
from .spec import ParamSpec

__all__ = ["moe_spec", "moe_apply", "moe_capacity"]


def moe_capacity(n_tokens: int, cfg: ModelConfig) -> int:
    e = cfg.moe
    c = math.ceil(n_tokens * e.top_k / e.n_experts * e.capacity_factor)
    return max(8, -(-c // 8) * 8)  # round up to 8 for layout friendliness


def moe_spec(cfg: ModelConfig) -> dict:
    e = cfg.moe
    d, f = cfg.d_model, e.d_ff_expert
    out = {
        "router": ParamSpec((d, e.n_experts), ("embed", "experts")),
        "w_gate": ParamSpec((e.n_experts, d, f), ("experts", "embed", "mlp")),
        "w_up": ParamSpec((e.n_experts, d, f), ("experts", "embed", "mlp")),
        "w_down": ParamSpec((e.n_experts, f, d), ("experts", "mlp", "embed")),
    }
    if e.n_shared:
        out["shared"] = mlp_spec(cfg, d_ff=e.n_shared * f)
    return out


def _dispatch_compute(
    x: jnp.ndarray,          # (T, d) local tokens
    top_i: jnp.ndarray,      # (T, k) global expert ids
    gates: jnp.ndarray,      # (T, k)
    w_gate: jnp.ndarray,     # (E_loc, d, f)
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    e0: jnp.ndarray | int,   # first global expert id owned locally
    cap: int,
    axo=None,                # (AxODeployment, expert entry dict) or None
) -> jnp.ndarray:
    """Sort-based dispatch -> expert FFN -> weighted combine for local experts.

    Entries routed to non-local experts get the sentinel bucket ``E_loc`` and are
    dropped by the capacity scatter.  Returns the (T, d) partial output covering
    only locally-owned expert contributions.

    ``axo`` runs each expert's FFN on the approximate operator (a static Python
    loop over the E_loc capacity buffers -- dispatch/combine stay exact).
    """
    t, d = x.shape
    e_loc = w_gate.shape[0]
    k = top_i.shape[1]

    flat_e = top_i.reshape(-1)
    lid = flat_e - e0
    local = (lid >= 0) & (lid < e_loc)
    assign = jnp.where(local, lid, e_loc)                  # sentinel = E_loc
    sort_idx = jnp.argsort(assign)                         # stable
    sorted_e = assign[sort_idx]
    tok = sort_idx // k
    starts = jnp.searchsorted(sorted_e, jnp.arange(e_loc + 1), side="left")
    pos = jnp.arange(t * k) - starts[jnp.minimum(sorted_e, e_loc)]

    buf = jnp.zeros((e_loc, cap, d), x.dtype)
    buf = buf.at[sorted_e, pos].set(x[tok], mode="drop")   # sentinel/over-cap dropped

    if axo is None:
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * jnp.einsum(
            "ecd,edf->ecf", buf, w_up
        )
        y = jnp.einsum("ecf,efd->ecd", h, w_down)
    else:
        dep, ent = axo
        ys = []
        for ei in range(e_loc):
            sel = lambda sub: {kk: vv[ei] for kk, vv in sub.items()}
            he = jax.nn.silu(dep.apply(buf[ei], sel(ent["w_gate"]))) * dep.apply(
                buf[ei], sel(ent["w_up"])
            )
            ys.append(dep.apply(he, sel(ent["w_down"])))
        y = jnp.stack(ys).astype(buf.dtype)

    kept = (sorted_e < e_loc) & (pos >= 0) & (pos < cap)
    y_tok = (
        y[jnp.minimum(sorted_e, e_loc - 1), jnp.clip(pos, 0, cap - 1)]
        * kept[:, None].astype(y.dtype)
    )
    w = gates.reshape(-1)[sort_idx].astype(y.dtype)
    return jnp.zeros((t, d), y.dtype).at[tok].add(y_tok * w[:, None])


def _ep_body(cfg: ModelConfig, cap: int, w_gate, w_up, w_down, x, top_i, gates):
    """shard_map body: one model-shard's experts over its local token copy."""
    e_loc = w_gate.shape[0]
    e0 = jax.lax.axis_index("model") * e_loc
    b, s, d = x.shape
    out = _dispatch_compute(
        x.reshape(b * s, d), top_i.reshape(b * s, -1), gates.reshape(b * s, -1),
        w_gate, w_up, w_down, e0, cap,
    )
    return jax.lax.psum(out.reshape(b, s, d), "model")


def _ep_decode_body(cfg: ModelConfig, cap: int,
                    w_gate, w_up, w_down, x, top_i, gates):
    """Weight-stationary decode body (perf opt P2, see EXPERIMENTS.md §Perf).

    Serving with FSDP-sharded expert weights must NOT gather weights per token
    (measured ~660 MB x 61 layers per decoded batch on kimi-1T): with T tokens
    << params, gather the *activations* instead.  Weights stay sharded over
    (experts -> model, embed-d -> data); every shard sees the full (tiny) token
    batch, contracts its local d-slice, and the partial sums are psum'd over
    ``data`` (pre-activation) and ``model`` (expert partition).

    w_gate/w_up: (E_loc, d_loc, f); w_down: (E_loc, f, d_loc); x: (B, S, d) full.
    Returns the (B, S, d_loc) output d-slice for this data shard.
    """
    e_loc = w_gate.shape[0]
    d_loc = w_gate.shape[1]
    e0 = jax.lax.axis_index("model") * e_loc
    d0 = jax.lax.axis_index("data") * d_loc
    b, s, d = x.shape
    t = b * s
    k = top_i.shape[-1]

    xs = jax.lax.dynamic_slice_in_dim(x.reshape(t, d), d0, d_loc, axis=1)
    flat_e = top_i.reshape(-1)
    lid = flat_e - e0
    local = (lid >= 0) & (lid < e_loc)
    assign = jnp.where(local, lid, e_loc)
    sort_idx = jnp.argsort(assign)
    sorted_e = assign[sort_idx]
    tok = sort_idx // k
    starts = jnp.searchsorted(sorted_e, jnp.arange(e_loc + 1), side="left")
    pos = jnp.arange(t * k) - starts[jnp.minimum(sorted_e, e_loc)]

    buf = jnp.zeros((e_loc, cap, d_loc), xs.dtype)
    buf = buf.at[sorted_e, pos].set(xs[tok], mode="drop")

    # contract the local d-slice; psum over data BEFORE the nonlinearity
    pre_g = jax.lax.psum(jnp.einsum("ecd,edf->ecf", buf, w_gate), "data")
    pre_u = jax.lax.psum(jnp.einsum("ecd,edf->ecf", buf, w_up), "data")
    h = jax.nn.silu(pre_g) * pre_u
    y = jnp.einsum("ecf,efd->ecd", h, w_down)          # (E_loc, cap, d_loc)

    kept = (sorted_e < e_loc) & (pos >= 0) & (pos < cap)
    y_tok = (
        y[jnp.minimum(sorted_e, e_loc - 1), jnp.clip(pos, 0, cap - 1)]
        * kept[:, None].astype(y.dtype)
    )
    w = gates.reshape(-1)[sort_idx].astype(y.dtype)
    out = jnp.zeros((t, d_loc), y.dtype).at[tok].add(y_tok * w[:, None])
    return jax.lax.psum(out, "model").reshape(b, s, d_loc)


def _batch_spec(mesh, b: int):
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n = math.prod(mesh.shape[a] for a in axes) if axes else 1
    return axes if (axes and b % n == 0) else None


def moe_apply(
    p: dict,
    x: jnp.ndarray,                 # (B, S, d)
    cfg: ModelConfig,
    rules: ShardingRules,
    axo=None,                       # (AxODeployment, layer mlp entries) or None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (out (B, S, d), router aux loss scalar).

    ``axo`` swaps the expert FFNs (and the shared experts) onto the approximate
    operator.  The router stays exact -- it picks *which* experts run, a routing
    decision rather than arithmetic -- and AxO serving targets the single-device
    reference path (EP/weight-stationary shard_map paths keep exact experts).
    """
    e = cfg.moe
    b, s, d = x.shape
    t = b * s
    k = e.top_k

    # --- routing (once, under SPMD) -----------------------------------------
    logits = (x @ p["router"]).astype(jnp.float32)          # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)                  # (B, S, k)
    gates = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance auxiliary loss.
    me = probs.reshape(t, -1).mean(axis=0)                  # (E,)
    ce = (
        jnp.zeros((e.n_experts,), jnp.float32)
        .at[top_i.reshape(-1)]
        .add(1.0)
        / (t * k)
    )
    aux = e.n_experts * jnp.sum(me * ce) * e.router_aux_weight

    mesh = _current_mesh()
    ep_ok = (
        mesh is not None
        and not mesh.empty
        and "model" in mesh.axis_names
        and mesh.shape["model"] > 1
        and e.n_experts % mesh.shape["model"] == 0
    )

    # Decode / tiny-batch serving: weight-stationary path (perf opt P2) --
    # engage when the token batch is far smaller than the expert weights and
    # the weights carry an FSDP (data) shard on their d dim.  Weights stay put
    # (E -> model, d -> data); the tiny activation batch is gathered instead.
    data_n = mesh.shape["data"] if (ep_ok and "data" in mesh.axis_names) else 1
    decode_ws = (
        ep_ok
        and t <= 8192
        and data_n > 1
        and d % data_n == 0
    )

    axo_experts = axo is not None and "experts" in axo[1]
    if axo_experts:
        cap = moe_capacity(t, cfg)
        out = _dispatch_compute(
            x.reshape(t, d), top_i.reshape(t, k), gates.reshape(t, k),
            p["w_gate"], p["w_up"], p["w_down"], 0, cap,
            axo=(axo[0], axo[1]["experts"]),
        ).reshape(b, s, d)
    elif decode_ws:
        cap = moe_capacity(t, cfg)
        out = jax.shard_map(
            partial(_ep_decode_body, cfg, cap),
            mesh=mesh,
            in_specs=(
                P("model", "data", None),      # w_gate (E/ep, d/dp, f)
                P("model", "data", None),      # w_up
                P("model", None, "data"),      # w_down (E/ep, f, d/dp)
                P(None, None, None),           # x: full token batch everywhere
                P(None, None, None),           # top_i
                P(None, None, None),           # gates
            ),
            out_specs=P(None, None, "data"),
            check_vma=False,
        )(p["w_gate"], p["w_up"], p["w_down"], x, top_i, gates)
    elif ep_ok:
        ep = mesh.shape["model"]
        bspec = _batch_spec(mesh, b)
        data_n_tok = (
            math.prod(mesh.shape[a] for a in bspec) if bspec else 1
        )
        cap = moe_capacity(t // data_n_tok, cfg)
        tok_spec = P(bspec, None, None)
        out = jax.shard_map(
            partial(_ep_body, cfg, cap),
            mesh=mesh,
            in_specs=(
                P("model", None, None),   # w_gate
                P("model", None, None),   # w_up
                P("model", None, None),   # w_down
                tok_spec,                 # x
                tok_spec,                 # top_i
                tok_spec,                 # gates
            ),
            out_specs=tok_spec,
            check_vma=False,
        )(p["w_gate"], p["w_up"], p["w_down"], x, top_i, gates)
    else:
        cap = moe_capacity(t, cfg)
        out = _dispatch_compute(
            x.reshape(t, d), top_i.reshape(t, k), gates.reshape(t, k),
            p["w_gate"], p["w_up"], p["w_down"], 0, cap,
        ).reshape(b, s, d)

    if "shared" in p:
        sh_axo = None
        if axo is not None and "shared" in axo[1]:
            sh_axo = (axo[0], axo[1]["shared"])
        out = out + mlp_apply(p["shared"], x, cfg, axo=sh_axo)
    out = constrain(out, rules, "batch", "seq", "embed")
    return out.astype(x.dtype), aux
