"""AxO deployment: run LM linear layers on a DSE-selected approximate operator.

The bridge from the paper's DSE output (a LUT config) to the framework's
serving path:

  1. ``AxOOperator.from_config``: behavioral-model product table -> error table
     ``E = T - ab`` -> rank-R SVD factors ``(f, g)`` + the signed-value table.
     R is a quality knob characterized with the same BEHAV metrics as the
     operator itself (``rank_behav``).
  2. ``axo_linear``: per-tensor symmetric int8 quantization of activations and
     weights, then the AxO matmul -- the Pallas kernel (registry-tiled, padded
     to blocks for arbitrary shapes), or its jnp reference (identical math) --
     and dequantization.
  3. ``deploy_axo``: walk a model's param tree and build an
     :class:`AxODeployment` -- per-layer **cached** weight codes/scales and
     pre-computed ``G_r(W)`` factors for every attention q/k/v/o, Mamba
     in/out, MLP and MoE expert projection (plus the LM head), so decode
     steps never requantize or look weights up again per token.  The
     deployment is a pytree and threads through
     ``models.model.forward(axo=...)`` and the ``launch.steps`` steps as a
     jit argument.

Both the deployment and its decode steps turn codes into operand values and
factors with :func:`code_lookup`: arithmetic decoding and a select over the
operator's small factor table where the operator allows it, since a TPU
gather of scalars runs about one element at a time.

The bit-exact table path (exhaustive gather) stays available for validation;
production uses the rank-R MXU path (DESIGN.md §3.2).
"""

from __future__ import annotations

import functools
from collections import Counter
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..core.operator_model import (
    OperatorSpec,
    error_tables,
    exact_product_table,
    product_tables,
    spec_for,
)
from ..kernels import ops
from ..kernels import ref as kref
from ..kernels.axo_matmul_kernel import axo_matmul_pallas
from ..kernels.tuning import tiles_for
from ..obs import telemetry as obs

__all__ = [
    "AxOOperator",
    "AxODeployment",
    "AXO_LAYERS",
    "quantize_tensor",
    "code_lookup",
    "lookup_path",
    "axo_linear",
    "deploy_axo",
]


@dataclass(frozen=True, eq=False)
class AxOOperator:
    """A deployable approximate multiplier: rank-R factorized error tables.

    Compared and hashed by identity: it is static metadata of the
    :class:`AxODeployment` pytree, and its tables are numpy arrays.
    """

    n_bits: int
    rank: int
    f_table: np.ndarray          # (2^n, R) float32
    g_table: np.ndarray          # (2^n, R) float32
    signed_vals: np.ndarray      # (2^n,) int32
    table: np.ndarray            # (2^n, 2^n) int32 exact approximate products

    @staticmethod
    def from_config(config: np.ndarray, rank: int = 8, n_bits: int = 8) -> "AxOOperator":
        spec = spec_for(n_bits)
        table = product_tables(spec, np.asarray(config)[None])[0]
        err = error_tables(spec, np.asarray(config)[None])[0].astype(np.float64)
        u, s, vt = np.linalg.svd(err)
        r = min(rank, len(s))
        f = (u[:, :r] * s[:r]).astype(np.float32)
        g = vt[:r].T.astype(np.float32)
        return AxOOperator(
            n_bits=n_bits, rank=r, f_table=f, g_table=g,
            signed_vals=spec.operand_values.astype(np.int32), table=table,
        )

    # -- quality of the rank knob --------------------------------------------

    def rank_table(self) -> np.ndarray:
        """Rank-R reconstruction of the product table (float)."""
        exact = exact_product_table(self.n_bits).astype(np.float64)
        return exact + self.f_table.astype(np.float64) @ self.g_table.astype(np.float64).T

    def rank_behav(self) -> dict:
        """BEHAV metrics of the rank-R approximation vs the TRUE operator table
        (how much fidelity the factorization itself costs)."""
        t_true = self.table.astype(np.float64)
        t_rank = self.rank_table()
        d = np.abs(t_rank - t_true)
        exact = np.maximum(np.abs(exact_product_table(self.n_bits)), 1).astype(np.float64)
        return {
            "AVG_ABS_ERR": float(d.mean()),
            "AVG_ABS_REL_ERR": float(100.0 * (d / exact).mean()),
            "MAX_ABS_ERR": float(d.max()),
        }


def quantize_tensor(x: jnp.ndarray, n_bits: int = 8) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-tensor int8-style quantization -> (codes, scale).

    Codes are already masked into table-index (two's complement) space.
    """
    qmax = (1 << (n_bits - 1)) - 1
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
    scale = amax / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax).astype(jnp.int32)
    return q & ((1 << n_bits) - 1), scale


#: widest operand whose factor table is selected over: a select costs
#: 2^n - 1 elementwise ``where``s a code, a gather one serialized read
SELECT_MAX_BITS = 8


def _decoding(op: AxOOperator) -> str | None:
    """How ``op.signed_vals`` decodes a code: "signed" (two's complement),
    "unsigned" (the code itself), or None when it is neither, or the
    operator is too wide to select over."""
    if op.n_bits > SELECT_MAX_BITS:
        return None
    n = 1 << op.n_bits
    codes = np.arange(n)
    if np.array_equal(op.signed_vals, np.where(codes >= n // 2, codes - n, codes)):
        return "signed"
    if np.array_equal(op.signed_vals, codes):
        return "unsigned"
    return None


def lookup_path(op: AxOOperator) -> str:
    """The path :func:`code_lookup` takes for ``op``: "select" or "gather"."""
    return "gather" if _decoding(op) is None else "select"


def code_lookup(
    op: AxOOperator,
    codes: jnp.ndarray,          # (...) integer codes in [0, 2^n)
    side: str,                   # "f": left (activation) factors, "g": right
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Operand values (...) float32 and factors (R, ...) of ``codes``.

    Bit-identical to ``signed_vals[codes]`` and ``moveaxis(table[codes],
    -1, 0)`` for ``op``'s float32 tables, which it computes as they read
    where :func:`lookup_path` says "gather".  Otherwise the value is decoded
    arithmetically, and each factor is picked by a depth-n tree of
    ``where`` on the code's bits over the table's 2^n rows: exact, one
    fusion, no array larger than the output.  The tables are the
    operator's static numpy arrays, so they enter the program as constants.
    """
    table = np.asarray(op.f_table if side == "f" else op.g_table, np.float32)
    decoding = _decoding(op)
    if decoding is None:
        sv = jnp.asarray(op.signed_vals, jnp.float32)
        return sv[codes], jnp.moveaxis(jnp.asarray(table)[codes], -1, 0)
    n = 1 << op.n_bits
    vals = codes if decoding == "unsigned" else jnp.where(
        codes >= n // 2, codes - n, codes)
    nodes = list(table.reshape(n, table.shape[1], *(1,) * codes.ndim))
    for b in range(op.n_bits):
        bit = ((codes >> b) & 1)[None] == 1
        nodes = [jnp.where(bit, hi, lo)
                 for lo, hi in zip(nodes[0::2], nodes[1::2])]
    return vals.astype(jnp.float32), nodes[0]


def axo_linear(
    x: jnp.ndarray,              # (..., K) float activations
    w: jnp.ndarray,              # (K, N) float weights
    op: AxOOperator,
    use_kernel: bool = True,
    ctx=None,                    # optional dse.context.ExecutionContext
) -> jnp.ndarray:
    """y = x @ w evaluated through the approximate operator's arithmetic.

    The kernel path handles *arbitrary* shapes: the Pallas wrapper pads every
    operand to the registry-selected block grid and slices the output (the old
    ``% 128`` gate silently demoted decode-shaped inputs -- M=4, or any
    head_dim < 128 -- to the slow reference path).  ``ctx`` may override the
    impl via its kernel menu and supplies tuned tiles through ``tiles_for``.
    """
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[1]
    with jax.named_scope("axo.quantize"):
        xq, sx = quantize_tensor(x.reshape(-1, k), op.n_bits)
        wq, sw = quantize_tensor(w, op.n_bits)
    f = jnp.asarray(op.f_table)
    g = jnp.asarray(op.g_table)
    sv = jnp.asarray(op.signed_vals, jnp.float32)
    impl = "pallas" if use_kernel else "xla"
    if ctx is not None:
        impl = ctx.resolve_impl("axo_matmul", impl)
    # trace-time resolution count: one per (re)trace per call site, the
    # serving-path analogue of the registry dispatch counters
    obs.of(ctx).count(f"dispatch.axo_linear.{impl}")
    with jax.named_scope("axo.matmul"):
        if impl == "pallas":
            tiles = tiles_for(ctx, "axo_matmul.pallas",
                              m=xq.shape[0], k=k, n=n, rank=op.rank)
            y = ops.axo_matmul(xq, wq, f, g, sv, **tiles)
        else:
            y = kref.ref_axo_matmul_lowrank(xq, wq, f, g, sv)
        return (y * (sx * sw)).reshape(*lead, n).astype(x.dtype)


# ---------------------------------------------------------------------------
# Whole-model deployment
# ---------------------------------------------------------------------------

#: parts of the network ``deploy_axo`` can swap onto the approximate operator
AXO_LAYERS = ("attn", "ssm", "mlp", "moe", "head")

#: the Pallas kernel's lane width: a ``"pallas"`` entry's K and N are padded
#: to it at deploy time, so no call pads a weight-sized table
LANE = 128


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["stages", "encoder", "head"],
    meta_fields=["op", "impl", "layers", "ctx", "n_entries"],
)
@dataclass(frozen=True)
class AxODeployment:
    """DSE-selected operator deployed into every linear layer of a model.

    Weights are quantized ONCE at deploy time: each entry caches the weight's
    signed value matrix ``bv = signed_vals[Wq]`` (K, N), its right factors
    ``gb = G_r(Wq)`` (R, K, N) and the weight scale -- decode steps only
    quantize the (tiny) activation and look up its values and left factors
    (:func:`code_lookup`).  A ``"pallas"`` entry whose K or N is not a
    multiple of :data:`LANE` holds ``bv``/``gb`` zero-padded to it (exact:
    zero values and factors add nothing) and a shape-only leaf ``cols``
    (0, N) that keeps the output's width.
    Entries for stacked layers carry a leading ``repeats`` axis so they ride
    through ``jax.lax.scan`` next to the params.

    A pytree whose leaves are the entries (the operator and its tables are
    static metadata): pass it to a jitted step as an argument.  Closed
    over, it would be embedded in the program as constants -- 3.4 GB for
    granite-3-2b's attention projections at rank 1, enough for lowering to
    exhaust a 40 GiB host.

    ``stages[str(si)][str(li)]`` mirrors ``params["stages"]`` with per-layer
    ``{"mixer": ..., "mlp": ...}`` entry dicts; ``encoder`` mirrors the
    optional encoder stage; ``head`` is a single (d, vocab) entry.
    """

    op: AxOOperator
    impl: str                            # "pallas" | "xla"
    layers: tuple
    stages: dict = field(default_factory=dict)
    encoder: dict | None = None
    head: dict | None = None
    ctx: object | None = None            # ExecutionContext for tuned tiles
    n_entries: int = 0

    def apply(self, x: jnp.ndarray, entry: dict) -> jnp.ndarray:
        """x @ W through the approximate operator, W cached in ``entry``."""
        lead = x.shape[:-1]
        k = x.shape[-1]
        bv = entry["bv"]
        kp, np_ = bv.shape[-2:]
        n = entry["cols"].shape[-1] if "cols" in entry else np_
        with jax.named_scope("axo.quantize"):
            xq, sx = quantize_tensor(
                x.reshape(-1, k).astype(jnp.float32), self.op.n_bits
            )
        with jax.named_scope("axo.gather"):
            # (M, K) values, (R, M, K) factors
            av, fa = code_lookup(self.op, xq, "f")
            if kp != k:   # the activation side of a padded entry
                av = jnp.pad(av, ((0, 0), (0, kp - k)))
                fa = jnp.pad(fa, ((0, 0), (0, 0), (0, kp - k)))
        tel = obs.of(self.ctx)
        tel.count(f"dispatch.axo_apply.{self.impl}")
        tel.count(f"dispatch.axo_lookup.{lookup_path(self.op)}")
        with jax.named_scope("axo.matmul"):
            if self.impl == "pallas":
                tiles = tiles_for(self.ctx, "axo_matmul.pallas",
                                  m=av.shape[0], k=kp, n=np_, rank=self.op.rank)
                y = axo_matmul_pallas(
                    av, bv, fa, entry["gb"],
                    interpret=not ops.on_tpu(), **tiles,
                )
            else:
                y = av @ bv + jnp.einsum("rmk,rkn->mn", fa, entry["gb"])
            if np_ != n:
                y = y[:, :n]
            y = y * (sx * entry["scale"])
            return y.reshape(*lead, n).astype(x.dtype)


def deploy_axo(
    params: dict,
    op: AxOOperator,
    cfg,
    *,
    layers: tuple = AXO_LAYERS,
    impl: str = "pallas",
    ctx=None,
) -> AxODeployment:
    """Build an :class:`AxODeployment` for ``params`` of a model ``cfg``.

    Walks ``cfg.stages`` next to ``params["stages"]`` and prepares a cached
    entry for every deployable projection:

    * ``"attn"``  -- attention wq/wk/wv/wo (dense, no-cache, cross- and
      self-halves of attn_x, gated xattn) and MLA wq_a/wq_b/wkv_a/wo.  MLA's
      ``wkv_b`` stays exact: the absorbed form contracts its two halves
      per-head against latents, not as a plain last-dim linear.
    * ``"ssm"``   -- Mamba mixers' in_proj and out_proj, nearly all of a
      mixer's weights; the depthwise conv, A, dt, D and the gated norm stay
      exact, as the router does.
    * ``"mlp"``   -- dense FFN w_gate/w_up/w_down, plus MoE *shared* experts.
    * ``"moe"``   -- routed expert banks (per-expert entries; the router stays
      exact -- approximating the argmax selector changes *which* experts run,
      which is a routing decision, not arithmetic).
    * ``"head"``  -- the unembedding (tied: embed.T).

    ``impl="pallas"`` runs the registry-tiled kernel on entries padded to
    its lane width; ``"xla"`` runs the jnp reference contraction (identical
    math, faster under CPU jit).

    The build, until its arrays are on the device, is the ``axo.deploy``
    span of ``ctx``'s telemetry, with the entry count, the count of each
    group (``group_entries``) and the deployment's bytes as attributes.
    """
    unknown = set(layers) - set(AXO_LAYERS)
    if unknown:
        raise ValueError(f"unknown AxO layer groups {sorted(unknown)}; "
                         f"choose from {AXO_LAYERS}")
    if impl not in ("pallas", "xla"):
        raise ValueError(f"impl must be 'pallas' or 'xla', got {impl!r}")
    tel = obs.of(ctx)
    with tel.span("axo.deploy", impl=impl, layers=tuple(layers)) as span:
        dep, groups = _build_deployment(
            params, op, cfg, tuple(layers), impl, ctx)
        dep = jax.block_until_ready(dep)
    if tel.enabled:   # the disabled sink's span is shared
        span.attrs.update(entries=dep.n_entries, group_entries=groups,
                          bytes=sum(x.nbytes for x in jax.tree.leaves(dep)))
    return dep


#: one program for the lookup: eagerly, the select's tree would be 2^n - 1
#: dispatches, each writing a whole weight-sized array
_code_lookup_jit = jax.jit(code_lookup, static_argnums=(0, 2))


def _build_deployment(params, op, cfg, layers, impl, ctx):
    """The deployment and its entry count by group."""
    counts = Counter()

    def prep(w2d, group):
        """(K, N) weight -> cached codes/values/factors entry."""
        wq, sw = quantize_tensor(jnp.asarray(w2d, jnp.float32), op.n_bits)
        counts[group] += 1
        bv, gb = _code_lookup_jit(op, wq, "g")   # (K, N), (R, K, N)
        ent = {"bv": bv, "gb": gb, "scale": sw}
        k, n = w2d.shape
        pk, pn = -k % LANE, -n % LANE
        if impl == "pallas" and (pk or pn):
            ent["bv"] = jnp.pad(bv, ((0, pk), (0, pn)))
            ent["gb"] = jnp.pad(gb, ((0, 0), (0, pk), (0, pn)))
            ent["cols"] = jnp.zeros((0, n), jnp.int8)
        return ent

    def prep_r(w, group, tail2=None):
        """Stacked (repeats, ...) weight -> entry with a leading repeats axis."""
        if tail2 is not None:
            w = w.reshape(w.shape[0], *tail2)
        return jax.vmap(functools.partial(prep, group=group))(w)

    def prep_experts(w):
        """(repeats, E, K, N) expert bank -> doubly-stacked entry."""
        return jax.vmap(jax.vmap(functools.partial(prep, group="moe")))(w)

    def attn_entries(mp):
        rep, d, h, hd = mp["wq"].shape
        g = mp["wk"].shape[2]
        return {
            "wq": prep_r(mp["wq"], "attn", (d, h * hd)),
            "wk": prep_r(mp["wk"], "attn", (d, g * hd)),
            "wv": prep_r(mp["wv"], "attn", (d, g * hd)),
            "wo": prep_r(mp["wo"], "attn", (h * hd, mp["wo"].shape[3])),
        }

    def mla_entries(mp):
        r_q, h, qd = mp["wq_b"].shape[1:]
        _, v_hd, d = mp["wo"].shape[1:]
        return {
            "wq_a": prep_r(mp["wq_a"], "attn"),
            "wq_b": prep_r(mp["wq_b"], "attn", (r_q, h * qd)),
            "wkv_a": prep_r(mp["wkv_a"], "attn"),
            "wo": prep_r(mp["wo"], "attn", (mp["wo"].shape[1] * v_hd, d)),
        }

    def mlp_entries(mp):
        return {k: prep_r(mp[k], "mlp")
                for k in ("w_gate", "w_up", "w_down") if k in mp}

    def layer_entries(mixer, mlp, lp):
        ent = {}
        if "attn" in layers:
            if mixer in ("attn", "attn_nc", "xattn"):
                ent["mixer"] = attn_entries(lp["mixer"])
            elif mixer == "attn_x":
                ent["mixer"] = {
                    "self": attn_entries(lp["mixer"]["self"]),
                    "cross": attn_entries(lp["mixer"]["cross"]),
                }
            elif mixer == "mla":
                ent["mixer"] = mla_entries(lp["mixer"])
        if mixer == "mamba" and "ssm" in layers:
            ent["mixer"] = {k: prep_r(lp["mixer"][k], "ssm")
                            for k in ("in_proj", "out_proj")}
        if mlp == "dense" and "mlp" in layers:
            ent["mlp"] = mlp_entries(lp["mlp"])
        elif mlp == "moe":
            sub = {}
            if "mlp" in layers and "shared" in lp["mlp"]:
                sub["shared"] = mlp_entries(lp["mlp"]["shared"])
            if "moe" in layers:
                sub["experts"] = {
                    k: prep_experts(lp["mlp"][k])
                    for k in ("w_gate", "w_up", "w_down")
                }
            if sub:
                ent["mlp"] = sub
        return ent

    stages = {}
    for si, stage in enumerate(cfg.stages):
        sp = params["stages"][str(si)]
        stages[str(si)] = {
            str(li): layer_entries(mixer, mlp, sp[str(li)])
            for li, (mixer, mlp) in enumerate(stage.layers)
        }

    encoder = None
    if getattr(cfg, "encoder", None) is not None and "encoder" in params:
        ep = params["encoder"]["stage"]
        encoder = {
            str(li): layer_entries("attn_nc", "dense", ep[str(li)])
            for li in range(len(ep))
        }

    head = None
    if "head" in layers:
        w = (params["embed"]["tok"].T if cfg.tie_embeddings
             else params["embed"]["unembed"])
        head = prep(w, "head")

    dep = AxODeployment(
        op=op, impl=impl, layers=layers,
        stages=stages, encoder=encoder, head=head,
        ctx=ctx, n_entries=sum(counts.values()),
    )
    return dep, dict(counts)
