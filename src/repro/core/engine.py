"""Unified execution policy for the whole DSE stack (the ``ExecutionContext``).

PRs 1-3 grew three device engines -- ``core.fastchar`` (characterization),
``apps.fastapp`` (application BEHAV) and ``core.fastmoo`` (NSGA-II) -- and each
grew its own ``backend="numpy"|"jax"`` string plumbing plus per-engine impl /
interpret knobs.  That left no single place to hang a device mesh, which is
exactly what the remaining scale items need (sharding the config axis of
characterization and the lane axis of ``run_dse_sweep`` batteries).

:class:`ExecutionContext` is the one execution-policy object threaded through
every engine:

  * ``backend`` / ``ga_backend`` -- which engine family runs (the old strings);
  * ``n_devices`` + ``shard_axes`` -- a 1-D device mesh and which batch axes
    are sharded over it (``"configs"``: the D axis of ``fastchar.
    behav_partials`` and the fastapp table primitives; ``"lanes"``: the
    independent (seed x const_sf) axis of ``fastmoo.CompiledNSGA2.run_sweep``);
  * ``kernel_impl`` -- preferred kernel implementation where an engine offers a
    menu; the menus live in the kernel registry (``repro.kernels.registry``:
    ``fastchar``: xla/pallas/entry/entry_pallas; ``fastapp``:
    gemm/xla/pallas/entry/entry_pallas; ``fastmoo`` rank kernel: xla/pallas)
    and :meth:`ExecutionContext.resolve_impl` resolves a
    preference against an engine's registered menu; engines fall back to
    their own default when the named impl is not on their menu;
  * ``tuning`` -- block-shape autotune policy for the registered kernels
    (``"off"``: registry defaults; ``"cached"``: per-(shape bucket, device)
    winners from the on-disk cache, searching once on a miss; ``"search"``:
    ignore persisted winners and re-search once per process per bucket).
    Consumed by ``repro.kernels.tuning.tiles_for``;
  * ``interpret`` -- Pallas interpret-mode override (None = auto off-TPU);
  * ``prng_impl`` -- the JAX PRNG family used for GA keys *and* for device-
    side dataset generation (None = default threefry2x32 for keys and the
    legacy numpy generator for datasets; ``"rbg"``/``"unsafe_rbg"`` for
    TPU-friendly generators end to end);
  * ``telemetry`` -- where this context's engines report spans/counters/
    device taps (``repro.obs``).  ``None`` (default) follows the process-
    wide sink; ``"on"`` creates a fresh per-run sink with on-device metric
    taps enabled (counters still chain to the global aggregate); ``"off"``
    is the no-op sink (compiled programs contain no taps at all); an
    explicit :class:`repro.obs.Telemetry` is used as-is.  Engines read it
    via :attr:`ExecutionContext.tel`, never the raw field.

The legacy ``backend=``/``ga_backend=`` string parameters everywhere in the
code base are **deprecated shims**: they still work, and they resolve to the
equivalent context via :func:`as_context` -- every dispatch decision is made by
the context, nowhere else.

Sharding model: the mesh is 1-D (axis name :data:`MESH_AXIS`) over the first
``n_devices`` of ``jax.devices()``.  Batch entries are fully independent in
every engine (per-config characterization/scoring, per-lane GA runs), so
sharded execution is the *same* per-entry program on ``1/n``-th of the batch
and results are bit-identical to the unsharded dispatch; the existing tiny
int64 host combines are unchanged.  Multi-device CPU validation uses
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the same trick
``launch/mesh.py`` documents), which must be set before JAX first initializes.

This module imports JAX lazily -- constructing a numpy-backend context (the
default everywhere) keeps the numpy modules JAX-free.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

__all__ = [
    "BACKENDS",
    "KERNEL_IMPLS",
    "SHARD_AXES",
    "PRNG_IMPLS",
    "TUNING_POLICIES",
    "MESH_AXIS",
    "ExecutionContext",
    "as_context",
]

BACKENDS = ("numpy", "jax")
# "entry"/"entry_pallas" are the table-free engines: product entries are
# synthesized on device from the LUT config masks (no HBM table build).
KERNEL_IMPLS = ("xla", "pallas", "gemm", "entry", "entry_pallas")
SHARD_AXES = ("configs", "lanes")
PRNG_IMPLS = ("threefry2x32", "rbg", "unsafe_rbg")
TUNING_POLICIES = ("off", "cached", "search")
MESH_AXIS = "shard"


@functools.lru_cache(maxsize=None)
def _mesh_for(n_devices: int):
    """1-D mesh over the first ``n_devices`` devices (cached per size)."""
    import jax

    devices = jax.devices()
    if n_devices > len(devices):
        raise ValueError(
            f"n_devices={n_devices} but only {len(devices)} JAX devices are "
            "available -- for CPU validation set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 before JAX "
            "first initializes"
        )
    return jax.make_mesh((n_devices,), (MESH_AXIS,),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=devices[:n_devices])


@dataclass(frozen=True)
class ExecutionContext:
    """The single execution-policy object consumed by every DSE engine."""

    backend: str = "numpy"
    ga_backend: str | None = None
    n_devices: int | None = None
    shard_axes: tuple[str, ...] = SHARD_AXES
    kernel_impl: str | None = None
    interpret: bool | None = None
    prng_impl: str | None = None
    tuning: str = "off"
    telemetry: object | None = None

    def __post_init__(self) -> None:
        if self.telemetry is not None:
            # normalize "on"/"off" to sink objects at construction so the
            # field is stable (hashable, and "on" allocates its sink once)
            from ..obs.telemetry import Telemetry, as_telemetry

            if not isinstance(self.telemetry, Telemetry):
                object.__setattr__(
                    self, "telemetry", as_telemetry(self.telemetry)
                )
        if self.backend not in BACKENDS:
            raise ValueError(
                f"backend must be 'numpy' or 'jax', got {self.backend!r}"
            )
        if self.ga_backend not in (None,) + BACKENDS:
            raise ValueError(
                f"ga_backend must be None, 'numpy' or 'jax', got {self.ga_backend!r}"
            )
        if self.kernel_impl not in (None,) + KERNEL_IMPLS:
            raise ValueError(
                f"kernel_impl must be one of {(None,) + KERNEL_IMPLS}, "
                f"got {self.kernel_impl!r}"
            )
        if self.prng_impl not in (None,) + PRNG_IMPLS:
            raise ValueError(
                f"prng_impl must be one of {(None,) + PRNG_IMPLS}, "
                f"got {self.prng_impl!r}"
            )
        if self.tuning not in TUNING_POLICIES:
            raise ValueError(
                f"tuning must be one of {TUNING_POLICIES}, got {self.tuning!r}"
            )
        axes = self.shard_axes
        if isinstance(axes, str):
            axes = (axes,)
        axes = tuple(axes)
        object.__setattr__(self, "shard_axes", axes)
        bad = [a for a in axes if a not in SHARD_AXES]
        if bad or len(set(axes)) != len(axes):
            raise ValueError(
                f"shard_axes must be distinct names from {SHARD_AXES}, got {axes!r}"
            )
        if self.n_devices is not None:
            if not isinstance(self.n_devices, int) or self.n_devices < 1:
                raise ValueError(
                    f"n_devices must be a positive int or None, got {self.n_devices!r}"
                )
            if self.n_devices > 1:
                if self.backend != "jax":
                    raise ValueError(
                        "sharded execution (n_devices > 1) requires backend='jax', "
                        f"got backend={self.backend!r}"
                    )
                if not axes:
                    raise ValueError(
                        "n_devices > 1 with empty shard_axes: nothing to shard "
                        "-- name at least one of "
                        f"{SHARD_AXES} or drop the mesh"
                    )
                _mesh_for(self.n_devices)  # eager: fail at construction

    # -- resolution helpers --------------------------------------------------

    @property
    def is_jax(self) -> bool:
        return self.backend == "jax"

    @property
    def tel(self):
        """This context's telemetry sink (never None): the explicit sink, or
        the process-wide current one when the field was left default."""
        from ..obs.telemetry import current

        return current() if self.telemetry is None else self.telemetry

    @property
    def resolved_ga_backend(self) -> str:
        return self.backend if self.ga_backend is None else self.ga_backend

    @property
    def device_count(self) -> int:
        return 1 if self.n_devices is None else self.n_devices

    def shards(self, axis: str) -> bool:
        """Whether batch axis ``axis`` ('configs' | 'lanes') is mesh-sharded."""
        if axis not in SHARD_AXES:
            raise ValueError(f"unknown shard axis {axis!r} (not in {SHARD_AXES})")
        return self.device_count > 1 and axis in self.shard_axes

    def resolve_impl(
        self, choices: "str | tuple[str, ...]", default: str | None = None
    ) -> str | None:
        """The context's kernel impl if the engine offers it, else ``default``.

        ``choices`` is an engine name (``"fastchar"``/``"fastapp"``/
        ``"fastmoo"`` -- the menu is read from the kernel registry, the one
        source of truth for what each engine can run) or, for backward
        compatibility, an explicit tuple of impl names.  Engines have
        different menus (fastchar has no 'gemm'; fastapp has no rank kernel),
        so a context-level preference only applies where it names something
        the calling engine can actually run.
        """
        if isinstance(choices, str):
            from ..kernels import registry

            choices = registry.impl_names(choices)
        if self.kernel_impl in choices:
            return self.kernel_impl
        return default

    def tuned_tiles(self, kernel: str, **shape) -> dict:
        """Block shapes of registered kernel ``kernel`` for ``shape`` under
        this context's ``tuning`` policy (registry defaults when "off")."""
        from ..kernels.tuning import tiles_for

        return tiles_for(self, kernel, **shape)

    # -- device handles (JAX imported lazily) --------------------------------

    def mesh(self):
        """The 1-D device mesh (axis :data:`MESH_AXIS`) for sharded dispatch."""
        return _mesh_for(self.device_count)

    def devices(self) -> list:
        import jax

        return jax.devices()[: self.device_count]

    def shard_call(self, fn, in_specs, out_specs):
        """``jax.shard_map`` of ``fn`` over this context's mesh."""
        import jax

        return jax.shard_map(fn, mesh=self.mesh(), in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def prng_key(self, seed: int):
        """A JAX PRNG key under this context's PRNG policy.

        ``None`` keeps the legacy raw ``PRNGKey`` (bit-compatible with the
        engines' historical streams); a named impl returns a typed key array
        so the generator choice travels with the key through jit/vmap/
        shard_map instead of being re-guessed from raw uint32 data.
        """
        import jax

        if self.prng_impl is None:
            return jax.random.PRNGKey(seed)
        return jax.random.key(seed, impl=self.prng_impl)


def as_context(
    backend: "str | ExecutionContext | None",
    ga_backend: str | None = None,
    default: ExecutionContext | None = None,
) -> ExecutionContext:
    """Normalize a legacy ``backend`` string (or an existing context) to an
    :class:`ExecutionContext` -- the single deprecated-shim entry point.

    ``backend=None`` returns ``default`` (or a fresh numpy context).  Passing a
    context alongside a conflicting ``ga_backend`` string is an error; matching
    or ``None`` strings are accepted so shim call sites can forward both.
    """
    if isinstance(backend, ExecutionContext):
        if ga_backend is not None and ga_backend != backend.resolved_ga_backend:
            raise ValueError(
                f"conflicting ga_backend={ga_backend!r} with context "
                f"{backend.resolved_ga_backend!r} -- pass one or the other"
            )
        return backend
    if backend is None:
        if default is not None:
            return as_context(default, ga_backend=ga_backend)
        backend = "numpy"
    return ExecutionContext(backend=backend, ga_backend=ga_backend)
