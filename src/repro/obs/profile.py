"""Compiled-cost profiling: XLA's own accounting as telemetry gauges.

The registry's analytical cost formulas (``KernelSpec.cost_fn`` -> wrapped
into ``pl.CostEstimate``) and the roofline model both *predict* FLOPs and
bytes; nothing validated those predictions against what XLA actually
compiled.  ApproxFPGAs (PAPERS.md) makes the general point: cost estimators
drift, and an estimator nobody checks against ground truth is worse than no
estimator -- the scheduler/autotuner trusts it.  This module closes that
loop:

  * :func:`profile_fn` compiles a callable via ``jit -> lower -> compile``
    and captures ``cost_analysis()`` (FLOPs, bytes accessed,
    transcendentals) + ``memory_analysis()`` (temp/argument/peak bytes)
    as telemetry **gauges** ``profile.<name>.<stat>`` plus one record in the
    ``profile`` series, using the same extraction as
    :func:`repro.launch.roofline.compiled_cost`;
  * :func:`check_estimate` cross-checks a measurement against an analytical
    estimate and flags any stat diverging **more than 2x** either way
    (counter ``profile.estimate_divergence`` + a WARN-ish gauge per kernel);
  * :func:`profile_registry` runs the check for every registry Pallas engine
    -- ``behav_stats_pallas``, ``table_gemv_pallas``,
    ``dominance_counts_pallas`` -- on small example shapes, comparing
    XLA's numbers against the registered ``cost_fn`` formulas;
  * :func:`trace_capture` wraps a block in ``jax.profiler.trace`` when the
    profiler is available (and a no-op otherwise), so
    ``ExecutionContext(telemetry="on")`` users can grab a device trace
    without importing jax.profiler themselves.

JAX is imported lazily inside the functions (module import stays stdlib-only,
like the rest of ``repro.obs``).  On CPU/interpret-mode the Pallas bodies are
executed via the interpreter, so XLA's accounting of the *wrapper* program
understates the analytical kernel formulas -- divergence flags there are
expected and informational; on real TPUs they mean a stale formula.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass, field

from . import telemetry as obs

__all__ = [
    "ProfileRecord",
    "profile_fn",
    "check_estimate",
    "profile_registry",
    "trace_capture",
    "DIVERGENCE_RATIO",
]

#: estimate-vs-measured ratio beyond which a kernel's cost formula is flagged
DIVERGENCE_RATIO = 2.0

#: stats cross-checked against analytical estimates (memory stats have no
#: analytical twin -- they are capture-only)
_CHECKED = ("flops", "bytes_accessed")


@dataclass
class ProfileRecord:
    """One profiled compile: XLA's accounting + optional estimate check."""

    name: str
    cost: dict                               # compiled_cost() output
    estimate: dict | None = None             # analytical cost_fn() output
    divergence: dict = field(default_factory=dict)   # stat -> measured/est
    flagged: tuple = ()                      # stats beyond DIVERGENCE_RATIO

    def to_record(self) -> dict:
        return {
            "name": self.name,
            "cost": dict(self.cost),
            "estimate": None if self.estimate is None else dict(self.estimate),
            "divergence": dict(self.divergence),
            "flagged": list(self.flagged),
        }


def _gauge_cost(tel: obs.Telemetry, name: str, cost: dict) -> None:
    for stat, val in cost.items():
        tel.gauge(f"profile.{name}.{stat}", float(val))


def profile_fn(fn, *args, name: str | None = None, tel=None,
               static_argnums=(), **kwargs) -> ProfileRecord:
    """Compile ``fn(*args, **kwargs)`` and record XLA's cost accounting.

    ``fn`` may already be jitted (``jax.jit`` output exposes ``.lower``);
    plain callables are jitted here with ``static_argnums``.  The compiled
    artifact is discarded -- this is a dry-run costing, not a benchmark, so
    it is safe on shapes too big to execute quickly.  Gauges land on ``tel``
    (default: the current telemetry) as ``profile.<name>.flops`` etc., plus
    one record in the ``profile`` series.
    """
    import jax

    from ..launch.roofline import compiled_cost

    tel = obs.current() if tel is None else tel
    label = name or getattr(fn, "__name__", "fn")
    if not hasattr(fn, "lower"):
        fn = jax.jit(fn, static_argnums=static_argnums)
    with tel.span(f"profile.{label}"):
        compiled = fn.lower(*args, **kwargs).compile()
        cost = compiled_cost(compiled)
    _gauge_cost(tel, label, cost)
    rec = ProfileRecord(name=label, cost=cost)
    tel.emit("profile", rec.to_record())
    tel.count("profile.compiles")
    return rec


def check_estimate(record: ProfileRecord, estimate: dict, tel=None,
                   ratio: float = DIVERGENCE_RATIO) -> ProfileRecord:
    """Cross-check XLA's accounting against an analytical estimate.

    For each stat in both records, the divergence is ``measured / estimate``;
    anything outside ``[1/ratio, ratio]`` is flagged (gauge
    ``profile.<name>.divergence.<stat>`` + counter
    ``profile.estimate_divergence``).  A zero estimate with a nonzero
    measurement flags as ``inf``.
    """
    tel = obs.current() if tel is None else tel
    record.estimate = dict(estimate)
    flagged = []
    for stat in _CHECKED:
        if stat not in estimate:
            continue
        est = float(estimate[stat])
        meas = float(record.cost.get(stat, 0.0))
        if est <= 0.0:
            div = float("inf") if meas > 0.0 else 1.0
        else:
            div = meas / est
        record.divergence[stat] = div
        tel.gauge(f"profile.{record.name}.divergence.{stat}", div)
        if not (1.0 / ratio <= div <= ratio):
            flagged.append(stat)
            tel.count("profile.estimate_divergence")
    record.flagged = tuple(flagged)
    return record


# ---------------------------------------------------------------------------
# Registry sweep: every Pallas engine against its own cost formula
# ---------------------------------------------------------------------------


def _char_inputs(n_bits: int):
    """(small, exact, w) for behav_stats_pallas at a tiny config batch."""
    import numpy as np

    import jax.numpy as jnp

    from ..core.fastchar import _device_tables, _gather_small
    from ..core.operator_model import config_to_masks, spec_for

    spec = spec_for(n_bits)
    rng = np.random.default_rng(0)
    cfgs = rng.integers(0, 2, (8, spec.n_luts)).astype(np.uint8)
    masks = config_to_masks(spec, cfgs).astype(np.int32)
    _, exact, w, _ = _device_tables(n_bits)
    small = _gather_small(jnp.asarray(masks), n_bits)
    return small, jnp.asarray(exact), jnp.asarray(w)


def _app_inputs(n_bits: int):
    """(small, a_codes, b_codes) for table_gemv_pallas."""
    import numpy as np

    import jax.numpy as jnp

    from ..core.fastchar import _gather_small
    from ..core.operator_model import config_to_masks, spec_for

    spec = spec_for(n_bits)
    rng = np.random.default_rng(1)
    cfgs = rng.integers(0, 2, (4, spec.n_luts)).astype(np.uint8)
    masks = config_to_masks(spec, cfgs).astype(np.int32)
    small = _gather_small(jnp.asarray(masks), n_bits)   # (R, D, 4, B)
    m, k, n = 8, 16, 8
    a = jnp.asarray(rng.integers(0, spec.n_inputs, (m, k)), jnp.int32)
    b = jnp.asarray(rng.integers(0, spec.n_inputs, (k, n)), jnp.int32)
    return small, a, b


def _moo_inputs(p: int = 128, n_obj: int = 2):
    """(objs, viol, active) for dominance_counts_pallas."""
    import numpy as np

    import jax.numpy as jnp

    rng = np.random.default_rng(2)
    objs = jnp.asarray(rng.standard_normal((p, n_obj)), jnp.float32)
    viol = jnp.asarray(
        np.where(rng.uniform(size=p) < 0.5, 0.0, rng.uniform(0.1, 2.0, size=p)),
        jnp.float32,
    )
    active = jnp.asarray(rng.uniform(size=p) < 0.8)
    return objs, viol, active


def profile_registry(tel=None, n_bits: int = 8,
                     interpret: bool | None = None) -> list[ProfileRecord]:
    """Profile the three registry Pallas engines against their cost formulas.

    Compiles each kernel on a small example shape, captures XLA's
    cost/memory accounting as gauges, and flags estimate-vs-measured
    divergence beyond :data:`DIVERGENCE_RATIO`.  ``interpret=None`` picks
    interpret mode off-TPU (required there); on CPU the flags are expected
    (XLA costs the interpreter wrapper, not the kernel body) and serve as a
    smoke test of the *mechanism* -- on real TPUs a flag means the
    registered formula went stale.
    """
    import functools

    from ..kernels import registry
    from ..kernels.app_kernels import table_gemv_pallas
    from ..kernels.char_kernels import behav_stats_pallas
    from ..kernels.moo_kernels import dominance_counts_pallas
    from ..kernels.ops import on_tpu

    tel = obs.current() if tel is None else tel
    if interpret is None:
        interpret = not on_tpu()
    records: list[ProfileRecord] = []

    # fastchar: BEHAV partial stats
    small, exact, w = _char_inputs(n_bits)
    spec = registry.get("fastchar.pallas")
    d = int(small.shape[1])
    a, b = int(exact.shape[0]), int(exact.shape[1])
    bucket = spec.bucket(n_bits=n_bits, d=d)
    tiles = spec.default_tiles(bucket)
    rec = profile_fn(
        functools.partial(behav_stats_pallas, interpret=interpret, **tiles),
        small, exact, w, name="fastchar.pallas", tel=tel,
    )
    est = spec.cost_estimate(rows=int(small.shape[0]), d=d, a=a, b=b, **tiles)
    records.append(check_estimate(rec, est, tel=tel))

    # fastapp: table-GEMV
    small, ac, bc = _app_inputs(n_bits)
    spec = registry.get("fastapp.pallas")
    rows, d = int(small.shape[0]), int(small.shape[1])
    m, k = int(ac.shape[0]), int(ac.shape[1])
    n = int(bc.shape[1])
    bucket = spec.bucket(n_bits=n_bits, d=d, m=m, k=k, n=n)
    tiles = spec.default_tiles(bucket)
    tiles["k_tile"] = min(tiles["k_tile"], k)
    rec = profile_fn(
        functools.partial(table_gemv_pallas, interpret=interpret, **tiles),
        small, ac, bc, name="fastapp.pallas", tel=tel,
    )
    est = spec.cost_estimate(d=d, m=m, k=k, n=n, a=1 << n_bits, rows=rows,
                             **tiles)
    records.append(check_estimate(rec, est, tel=tel))

    # fastmoo: dominance counts
    objs, viol, active = _moo_inputs()
    spec = registry.get("fastmoo.pallas")
    p, n_obj = int(objs.shape[0]), int(objs.shape[1])
    bucket = spec.bucket(p=p, n_obj=n_obj)
    tiles = spec.default_tiles(bucket)
    rec = profile_fn(
        functools.partial(dominance_counts_pallas, interpret=interpret, **tiles),
        objs, viol, active, name="fastmoo.pallas", tel=tel,
    )
    est = spec.cost_estimate(p=p, n_obj=n_obj, **tiles)
    records.append(check_estimate(rec, est, tel=tel))
    return records


@contextlib.contextmanager
def trace_capture(path: str, tel=None):
    """``with trace_capture("/tmp/trace"):`` -- a ``jax.profiler.trace``
    block when the profiler is importable, a no-op otherwise.  Pairs with
    ``Telemetry(annotate=True)`` so spans line up with XLA activity."""
    tel = obs.current() if tel is None else tel
    try:
        import jax.profiler as _prof
    except Exception:
        _prof = None
    if _prof is None:
        yield None
        return
    with tel.span("profile.trace_capture", path=path):
        try:
            _prof.start_trace(path)
        except Exception:
            yield None
            return
        try:
            yield path
        finally:
            _prof.stop_trace()
            tel.count("profile.traces")
