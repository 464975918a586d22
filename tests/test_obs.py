"""Telemetry subsystem acceptance (repro.obs).

Four contracts:

  * **Spans** nest via a contextvar stack (thread-isolated), round-trip
    through JSONL, and export to Chrome-trace JSON with parent containment.
    In a JAX process each span is also a ``TraceAnnotation``, so it shows
    on the host plane of a ``jax.profiler`` trace; a process without JAX
    never imports it.
  * **Device taps** are per-*dispatch* ``io_callback`` sinks: a tap inside a
    ``fori_loop`` fires N times per compiled-program execution (never once
    per trace), and a disabled (NULL) tap stages nothing -- the program is
    bit-identical to an uninstrumented build.
  * **CompiledNSGA2** with ``telemetry="on"`` emits a per-generation
    feasible-front hypervolume curve (incremental front buffer, O(front)
    per generation) that is monotone; the checkpoint hv history stays
    archive-based and **bit-identical** to the untapped program's.
  * **run_dse** stage spans cover >= 95% of the run's wall clock, and
    ``DSEResult.timings`` records the stages regardless of telemetry state.
"""

import json
import os
import pathlib
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.engine import ExecutionContext
from repro.obs import device as obs_device
from repro.obs import telemetry as tm
from repro.obs.export import chrome_trace_dict, read_jsonl

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# Spans: nesting, threads, export round-trip
# ---------------------------------------------------------------------------


def test_span_nesting_parent_ids():
    tel = tm.Telemetry("t")
    with tel.span("outer", method="ga") as outer:
        with tel.span("inner") as inner:
            pass
        with tel.span("inner2") as inner2:
            pass
    spans = {s.name: s for s in tel.spans}
    assert set(spans) == {"outer", "inner", "inner2"}
    assert spans["outer"].parent_id is None
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["inner2"].parent_id == spans["outer"].span_id
    assert spans["outer"].attrs == {"method": "ga"}
    # children finished before the parent, and lie inside it
    assert spans["outer"].t0 <= spans["inner"].t0
    assert spans["inner"].t1 <= spans["outer"].t1
    assert outer.duration_s >= inner.duration_s + inner2.duration_s


def test_wrap_decorator():
    tel = tm.Telemetry("t")

    @tel.wrap("work.unit", kind="test")
    def work(x):
        return x + 1

    assert work(2) == 3
    (sp,) = tel.spans
    assert sp.name == "work.unit" and sp.attrs == {"kind": "test"}


def test_span_stack_is_thread_isolated():
    tel = tm.Telemetry("t")

    def worker():
        with tel.span("in-thread"):
            pass

    with tel.span("root"):
        th = threading.Thread(target=worker)
        th.start()
        th.join()
    spans = {s.name: s for s in tel.spans}
    # a fresh thread starts with an empty span stack: no cross-thread parent
    assert spans["in-thread"].parent_id is None
    assert spans["in-thread"].tid != spans["root"].tid


def test_jsonl_round_trip(tmp_path):
    tel = tm.Telemetry("t")
    with tel.span("a", n=3):
        with tel.span("b"):
            pass
    tel.count("c.x", 2)
    tel.gauge("g.y", 0.5)
    tel.observe("h.z", 1.0)
    tel.observe("h.z", 3.0)
    tel.emit("s.w", {"gen": 1, "hv": 0.25})
    path = tmp_path / "tel.jsonl"
    tel.to_jsonl(str(path))
    recs = read_jsonl(str(path))
    by_type = {}
    for r in recs:
        by_type.setdefault(r["type"], []).append(r)
    names = {r["name"] for r in by_type["span"]}
    assert names == {"a", "b"}
    b = next(r for r in by_type["span"] if r["name"] == "b")
    a = next(r for r in by_type["span"] if r["name"] == "a")
    assert b["parent_id"] == a["span_id"]
    assert by_type["counter"] == [{"type": "counter", "name": "c.x", "value": 2}]
    assert by_type["gauge"][0]["value"] == 0.5
    hist = by_type["histogram"][0]
    assert hist["count"] == 2 and hist["min"] == 1.0 and hist["max"] == 3.0
    assert by_type["series"][0]["records"] == [{"gen": 1, "hv": 0.25}]


def test_chrome_trace_structure(tmp_path):
    tel = tm.Telemetry("t")
    with tel.span("root", pop=16):
        with tel.span("child"):
            time.sleep(0.001)
    tel.count("dispatch.x", 4)
    d = chrome_trace_dict(tel)
    events = {e["name"]: e for e in d["traceEvents"]}
    assert events["root"]["ph"] == "X" and events["child"]["ph"] == "X"
    # child interval contained in root's, in the epoch-anchored us timeline
    r, c = events["root"], events["child"]
    assert r["ts"] <= c["ts"]
    assert c["ts"] + c["dur"] <= r["ts"] + r["dur"] + 1e-3
    assert r["args"] == {"pop": 16}
    assert d["otherData"]["counters"]["dispatch.x"] == 4
    # the file is plain JSON (what Perfetto loads)
    path = tmp_path / "trace.json"
    tel.to_chrome_trace(str(path))
    with open(path) as f:
        assert json.load(f)["traceEvents"]


# ---------------------------------------------------------------------------
# Metrics + the context plumbing
# ---------------------------------------------------------------------------


def test_counters_propagate_to_parent_spans_stay_local():
    parent = tm.Telemetry("parent")
    child = tm.Telemetry("child", parent=parent)
    child.count("k", 3)
    child.gauge("g", 1.5)
    child.observe("h", 2.0)
    with child.span("s"):
        pass
    assert parent.counter("k") == 3 and child.counter("k") == 3
    assert parent.gauges["g"] == 1.5
    assert parent.histogram_summary("h")["count"] == 1
    assert len(parent.spans) == 0 and len(child.spans) == 1
    # set_counter is a local write (STATS back-compat), not propagated
    child.set_counter("k", 0)
    assert child.counter("k") == 0 and parent.counter("k") == 3


def test_as_telemetry_and_context_normalization():
    assert tm.as_telemetry(None) is tm.GLOBAL
    assert tm.as_telemetry("off") is tm.NULL
    on = tm.as_telemetry("on")
    assert on.device_taps and on.parent is tm.GLOBAL
    assert tm.as_telemetry(on) is on
    with pytest.raises(ValueError):
        tm.as_telemetry("loud")

    ctx = ExecutionContext(backend="jax", telemetry="on")
    assert isinstance(ctx.telemetry, tm.Telemetry) and ctx.telemetry.device_taps
    assert ctx.tel is ctx.telemetry
    off = ExecutionContext(backend="jax", telemetry="off")
    assert off.telemetry is tm.NULL
    plain = ExecutionContext(backend="jax")
    assert plain.telemetry is None and plain.tel is tm.current()
    # contexts stay hashable (they key jit/memo caches all over the stack)
    assert hash(ctx) != 0 or True
    import dataclasses

    assert dataclasses.replace(ctx, tuning="off").telemetry is ctx.telemetry


def test_use_makes_a_sink_current():
    tel = tm.Telemetry("scoped")
    assert tm.current() is tm.GLOBAL
    with tm.use(tel):
        assert tm.current() is tel
        tm.current().count("seen")
    assert tm.current() is tm.GLOBAL
    assert tel.counter("seen") == 1 and tel.parent is None


def test_note_trace_counts_retraces_not_calls():
    tel = tm.Telemetry("t")
    with tm.use(tel):

        @jax.jit
        def f(x):
            tm.note_trace("f")
            return x + 1

        f(jnp.ones(2))
        f(jnp.ones(2))
        f(jnp.ones(2))
        assert tel.counter("jit.retrace.f") == 1
        f(jnp.ones(3))  # new shape -> one retrace
        assert tel.counter("jit.retrace.f") == 2


def test_record_pad_waste_from_kernel_launch():
    from repro.kernels.axo_matmul_kernel import axo_matmul_pallas

    tel = tm.Telemetry("t")
    rng = np.random.default_rng(0)
    m, k, n, rank = 4, 40, 12, 1
    a = rng.standard_normal((m, k)).astype(np.float32)
    b = rng.standard_normal((k, n)).astype(np.float32)
    fa = rng.standard_normal((rank, m, k)).astype(np.float32)
    gb = rng.standard_normal((rank, k, n)).astype(np.float32)
    with tm.use(tel):
        axo_matmul_pallas(jnp.asarray(a), jnp.asarray(b), jnp.asarray(fa),
                          jnp.asarray(gb), interpret=True)
    # m=4->8, k=40->128, n=12->128: heavy padding on this tiny launch
    waste = tel.gauges["axo_matmul.pad_waste"]
    assert 0.9 < waste < 1.0
    assert tel.histogram_summary("axo_matmul.pad_waste")["count"] == 1


# ---------------------------------------------------------------------------
# The disabled path is a true no-op
# ---------------------------------------------------------------------------


def test_null_telemetry_records_nothing():
    tel = tm.NULL
    with tel.span("x", a=1):
        tel.count("c")
        tel.gauge("g", 1.0)
        tel.observe("h", 1.0)
        tel.emit("s", {"v": 1})
    assert not tel.counters and not tel.gauges
    assert not tel.histograms and not tel.series and not tel.spans
    assert tel.span("a") is tel.span("b")  # shared reusable CM
    fn = tel.wrap("w")(lambda: 7)
    assert fn() == 7 and not tel.spans


def test_null_tap_stages_nothing_into_the_program():
    live = tm.Telemetry("live")
    tap_live = live.device_tap("t", ("x",))
    tap_null = tm.NULL.device_tap("t", ("x",))

    def g_live(x):
        tap_live(x)
        return x * 2

    def g_null(x):
        tap_null(x)
        return x * 2

    def g_bare(x):
        return x * 2

    x = jnp.float32(1.0)
    assert "callback" in str(jax.make_jaxpr(g_live)(x))
    # disabled telemetry: the traced program is the uninstrumented program
    assert str(jax.make_jaxpr(g_null)(x)) == str(jax.make_jaxpr(g_bare)(x))


def test_disabled_telemetry_overhead_guard():
    """Per-op bound: instrumented hot paths make tens of telemetry calls per
    millisecond-scale dispatch, so sub-microsecond no-op calls keep the
    disabled path under the 1% acceptance budget with a wide margin."""
    tel = tm.NULL
    n = 50_000
    t0 = time.perf_counter()
    for i in range(n):
        with tel.span("x", a=i):
            tel.count("c")
            tel.observe("h", 1.0)
            tel.gauge("g", 1.0)
    per_op = (time.perf_counter() - t0) / (4 * n)
    assert per_op < 5e-6, f"null telemetry op took {per_op * 1e6:.2f}us"


# ---------------------------------------------------------------------------
# Spans on the profiler's clock
# ---------------------------------------------------------------------------


def test_span_appears_on_the_profilers_host_plane(tmp_path):
    tel = tm.Telemetry("traced")
    with jax.profiler.trace(str(tmp_path)):
        with tel.span("obs.test.traced_span"):
            jnp.arange(8.0).sum().block_until_ready()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    names = {ev.name for plane in data.planes if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events}
    assert "obs.test.traced_span" in names
    assert [s.name for s in tel.spans] == ["obs.test.traced_span"]


def test_span_in_a_process_without_jax_stays_jax_free():
    code = ("import sys\n"
            "from repro.obs import telemetry as tm\n"
            "tel = tm.Telemetry()\n"
            "with tel.span('numpy.only'):\n"
            "    pass\n"
            "print(len(tel.spans), 'jax' in sys.modules)\n")
    src = str(pathlib.Path(tm.__file__).resolve().parents[2])
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env=dict(os.environ, PYTHONPATH=src), check=True)
    assert out.stdout.split() == ["1", "False"]


def test_enabled_span_without_a_profiler_costs_microseconds():
    """A span in a JAX process enters a TraceAnnotation, which records
    nothing while no profiler runs: a span stays in the microseconds."""
    tel = tm.Telemetry("cost")
    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        with tel.span("x"):
            pass
    per_span = (time.perf_counter() - t0) / n
    assert per_span < 50e-6, f"a span took {per_span * 1e6:.2f}us"


# ---------------------------------------------------------------------------
# Device taps: once per dispatch, never once per trace
# ---------------------------------------------------------------------------


def test_tap_fires_per_dispatch_inside_fori_loop():
    tel = tm.Telemetry("t")
    tap = tel.device_tap("loop", ("i", "x"))

    @jax.jit
    def f(x):
        def body(i, acc):
            tap(i, acc)
            return acc + 1.0

        return jax.lax.fori_loop(0, 4, body, x)

    for _ in range(3):
        f(jnp.float32(0.0))
    obs_device.flush()
    # 4 loop iterations x 3 dispatches -- NOT 4 (per trace) or 1
    recs = tel.series["loop"]
    assert len(recs) == 12
    assert tel.counter("tap.loop") == 12
    assert sorted(int(r["i"]) for r in recs[:4]) == [0, 1, 2, 3]
    assert all("_host_t" in r for r in recs)


def test_tap_under_vmap_fires_per_lane():
    tel = tm.Telemetry("t")
    tap = tel.device_tap("lane", ("x",))

    @jax.jit
    def f(xs):
        def one(x):
            tap(x)
            return x * 2

        return jax.vmap(one)(xs)

    f(jnp.arange(3, dtype=jnp.float32))
    obs_device.flush()
    # one firing per batch element with the unbatched value -- the reason
    # sweep programs stay untapped (lanes would interleave into one series)
    recs = tel.series["lane"]
    assert len(recs) == 3
    assert sorted(float(r["x"]) for r in recs) == [0.0, 1.0, 2.0]


def test_tap_arity_is_checked():
    tap = tm.Telemetry("t").device_tap("t", ("a", "b"))
    with pytest.raises(TypeError):
        tap(jnp.float32(1.0))


def test_batched_tap_flushes_rows_and_drops_masked():
    tel = tm.Telemetry("t")
    tap = tel.device_batched_tap("chunk", ("g", "v"))

    @jax.jit
    def f():
        rows = jnp.stack(
            [
                jnp.array([0.0, 10.0], jnp.float32),
                jnp.array([1.0, 11.0], jnp.float32),
                jnp.array([-1.0, 0.0], jnp.float32),  # padding row
            ]
        )
        tap(rows, rows[:, 0] >= 0.0)
        return rows.sum()

    for _ in range(2):
        f()
    obs_device.flush()
    # one flush per dispatch -> 2 valid rows each; the masked padding row
    # never reaches the series or the counter
    recs = tel.series["chunk"]
    assert len(recs) == 4
    assert tel.counter("tap.chunk") == 4
    assert [int(r["g"]) for r in recs[:2]] == [0, 1]
    assert [float(r["v"]) for r in recs[:2]] == [10.0, 11.0]
    assert all("_host_t" in r for r in recs)


# ---------------------------------------------------------------------------
# Per-generation hypervolume from inside CompiledNSGA2's fori_loop
# ---------------------------------------------------------------------------


def _toy_objs(X):
    a = X[:, :8].sum(axis=1)
    b = (1.0 - X[:, 8:]).sum(axis=1)
    return jnp.stack([a, b], axis=-1)


def test_tapped_nsga2_per_generation_hv_curve():
    from repro.core.fastmoo import CompiledNSGA2

    ref = np.array([9.0, 9.0])
    ctx = ExecutionContext(backend="jax", telemetry="on")
    runner = CompiledNSGA2(_toy_objs, n_bits=16, pop_size=16, n_gen=10,
                           hv_ref=ref, ctx=ctx)
    assert runner._tapped
    r = runner.run(seed=0)
    tel = ctx.telemetry
    taps = tel.series["fastmoo.gen"]
    # one record per generation per dispatch
    assert len(taps) == 10
    assert [int(t["gen"]) for t in taps] == list(range(10))
    hvs = [float(t["hv"]) for t in taps]
    # front only grows -> per-generation hv is monotone non-decreasing
    assert all(b >= a for a, b in zip(hvs, hvs[1:]))
    # the tap hv comes from the incremental front buffer: equal to the
    # archive-based checkpoint up to f32 summation order (the checkpoint
    # history itself stays bitwise archive-based, asserted below)
    assert np.isclose(hvs[-1], r.hv_history[-1][1], rtol=1e-6)
    # constraint-violation stats + front size ride along
    assert all(float(t["pop_feas"]) == 1.0 for t in taps)  # unconstrained run
    assert all(int(t["arc_feasible"]) > 0 for t in taps)
    fronts = [int(t["front"]) for t in taps]
    assert all(0 < f <= runner.front_capacity for f in fronts)

    # a second dispatch accumulates (per dispatch, not per trace)
    runner.run(seed=1)
    assert len(tel.series["fastmoo.gen"]) == 20
    assert tel.counter("dispatch.fastmoo.run") == 2

    # the tapped program's recorded history matches the untapped program's
    plain = CompiledNSGA2(_toy_objs, n_bits=16, pop_size=16, n_gen=10,
                          hv_ref=ref)
    assert not plain._tapped
    r_plain = plain.run(seed=0)
    np.testing.assert_array_equal(
        [h for _, h in r.hv_history], [h for _, h in r_plain.hv_history]
    )


def test_untapped_context_emits_no_series():
    from repro.core.fastmoo import CompiledNSGA2

    tel = tm.Telemetry("quiet")  # device_taps defaults to False
    ctx = ExecutionContext(backend="jax", telemetry=tel)
    runner = CompiledNSGA2(_toy_objs, n_bits=16, pop_size=16, n_gen=4,
                           hv_ref=np.array([9.0, 9.0]), ctx=ctx)
    assert not runner._tapped
    runner.run(seed=0)
    assert "fastmoo.gen" not in tel.series
    assert tel.counter("dispatch.fastmoo.run") == 1  # counters still flow
    assert any(s.name == "fastmoo.run" for s in tel.spans)


# ---------------------------------------------------------------------------
# run_dse: stage spans, coverage, DSEResult.timings
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def ds4():
    from repro.core.dataset import build_training_dataset
    from repro.core.operator_model import spec_for

    spec = spec_for(4)
    ds = build_training_dataset(spec, n_random=80, seed=0, backend="jax")
    return spec, ds


def test_run_dse_spans_cover_wall_time(ds4, tmp_path):
    from repro.core.dse import DSESettings, run_dse

    spec, ds = ds4
    tel = tm.Telemetry("run", device_taps=True)
    st = DSESettings(pop_size=8, n_gen=3, n_quad_grid=(0,), pool_size=2,
                     seed=0, backend="jax")
    r = run_dse(spec, ds, "map+ga", settings=st, telemetry=tel)

    spans = list(tel.spans)
    root = next(s for s in spans if s.name == "dse.run")
    stage_names = {s.name for s in spans if s.parent_id == root.span_id}
    assert {"dse.characterize", "dse.map", "dse.ga", "dse.validate"} <= stage_names
    stage_total = sum(s.duration_s for s in spans
                      if s.parent_id == root.span_id)
    # acceptance: stage spans account for >= 95% of the run's wall clock
    assert stage_total >= 0.95 * root.duration_s

    # per-stage timings are recorded on the result and add up to wall_s
    assert set(r.timings) == {"characterize", "map", "ga", "validate"}
    assert all(v >= 0.0 for v in r.timings.values())
    assert sum(r.timings.values()) <= r.wall_s
    assert sum(r.timings.values()) >= 0.95 * r.wall_s

    # engines reported their dispatches into the same sink
    assert any(k.startswith("dispatch.") for k in tel.counters)
    assert any(k.startswith("registry.dispatch.") for k in tel.counters)

    # ... and the whole run exports as one Perfetto-loadable trace
    path = tmp_path / "dse_trace.json"
    tel.to_chrome_trace(str(path))
    with open(path) as f:
        names = {e["name"] for e in json.load(f)["traceEvents"]}
    assert "dse.run" in names and "dse.ga" in names


def test_run_dse_timings_without_telemetry(ds4):
    from repro.core.dse import DSESettings, run_dse

    spec, ds = ds4
    st = DSESettings(pop_size=8, n_gen=2, n_quad_grid=(0,), pool_size=2,
                     seed=0, backend="jax")
    # telemetry "off": stage timings still land on the result
    r = run_dse(spec, ds, "ga", settings=st, telemetry="off")
    assert set(r.timings) == {"characterize", "ga", "validate"}  # no map stage
    assert sum(r.timings.values()) <= r.wall_s
    assert all(v >= 0.0 for v in r.timings.values())


def test_run_dse_sweep_lane_timings(ds4):
    from repro.core.dse import DSESettings, run_dse_sweep

    spec, ds = ds4
    tel = tm.Telemetry("sweep")
    st = DSESettings(pop_size=8, n_gen=2, n_quad_grid=(0,), pool_size=2,
                     seed=0, backend="jax",
                     context=ExecutionContext(backend="jax", telemetry=tel))
    results = run_dse_sweep(spec, ds, "ga", settings=st, seeds=(0, 1),
                            const_sf_grid=(0.5, 1.5))
    assert len(results) == 4
    for r in results:
        # shared stages carry the whole-sweep duration; validate is per-lane
        assert {"characterize", "ga", "validate"} <= set(r.timings)
        assert r.timings["validate"] <= r.timings["ga"] + r.wall_s
    shared = {k: results[0].timings[k] for k in ("characterize", "ga")}
    assert all(r.timings["characterize"] == shared["characterize"]
               for r in results)
    names = {s.name for s in tel.spans}
    assert {"dse.sweep", "dse.characterize", "dse.ga", "dse.validate"} <= names
