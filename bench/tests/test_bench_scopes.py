"""The scope reader (``scopes.py``): a reader of the XPlane protobuf in the
standard library alone, which sums each decode step's device time by the
program's named scopes and keeps only the steps that lost no events.

Two traces recorded on a v5e: ``probe.xplane.pb``, a jitted step without
scopes (see ``test_bench_trace.py``), and ``scoped.xplane.pb``, one served
batch of the Granite decoder cut to two layers with AxO attention on the
Pallas kernel (``record_scoped_trace.py``)."""

import re
from pathlib import Path
from types import SimpleNamespace

import pytest

import scopes

DATA = Path(__file__).resolve().parent / "data"
PROBE = DATA / "probe.xplane.pb"
SCOPED = DATA / "scoped.xplane.pb"
AXO = {"lut_config": "011111111011111111011111111011111111", "rank": 1,
       "layers": ["attn"]}


def _ctx(trace, axo=AXO):
    return {"run": SimpleNamespace(trace_path=trace), "layer": {"axo": axo}}


def test_reader_agrees_with_profile_data_on_every_device_event():
    from jax.profiler import ProfileData

    (plane,) = scopes.device_planes(PROBE)
    (want,) = [p for p in ProfileData.from_file(str(PROBE)).planes
               if p.name == plane.name]
    for line in want.lines:
        # ProfileData keeps whole nanoseconds; the reader, picoseconds
        got = sorted((plane.event_names[m], s // 1000, d // 1000)
                     for m, s, d in plane.lines[line.name])
        ref = sorted((e.name, e.start_ns, e.duration_ns) for e in line.events)
        assert got == ref, line.name


def test_probe_ops_carry_their_name_stack():
    (plane,) = scopes.device_planes(PROBE)
    ops = {scopes.tf_op(st) for st in plane.event_stats.values()}
    assert {"jit(decode_step)/gather", "jit(decode_step)/reduce_max",
            "jit(decode_step)/dot_general", "jit(decode_step)/transpose",
            "jit(decode_step)/jit(axo_matmul_pallas)/pallas_call"} <= ops
    # a program without the scopes: every reading falls silent
    times = scopes.scope_times(str(PROBE))
    assert len(times.complete) == 3 and not times.incomplete
    assert times.named_share == 0.0
    for read in (scopes.axo_glue_ms_per_step, scopes.mlp_ms_per_step,
                 scopes.kv_update_ms_per_step):
        assert read(_ctx(PROBE)) is None


@pytest.mark.parametrize("op_name,scope", [
    ("jit(decode_step)/layers/while/body/closed_call/attn.proj/axo.gather/"
     "gather", "axo.gather"),
    ("jit(decode_step)/layers/while/body/closed_call/attn.proj/axo.matmul/"
     "jit(axo_matmul_pallas)/pallas_call", "axo.matmul"),
    ("jit(decode_step)/layers/while/body/closed_call/mlp/dot_general", "mlp"),
    ("jit(decode_step)/layers/while/body/squeeze", "layers"),
    ("jit(decode_step)/head/bsd,vd->bsv/dot_general", "head"),
    ("jit(decode_step)/jit(_take)/gather", "other"),
    ("jit(decode_step)/mlp_x/attn.corex/add", "other"),
])
def test_scope_is_the_innermost_named_component(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_complete_step_filter_drops_a_step_that_lost_ops():
    (plane,) = scopes.device_planes(PROBE)
    whole = scopes.program_steps([plane])
    second = whole[1]
    ops = plane.lines[scopes.OPS_LINE]
    inside = [e for e in ops if second.start_ps <= e[1] < second.end_ps]
    lost = set(map(tuple, inside[:5]))
    plane.lines[scopes.OPS_LINE] = [e for e in ops if tuple(e) not in lost]

    complete, incomplete = scopes.split_complete(scopes.program_steps([plane]))
    assert [s.start_ps for s in incomplete] == [second.start_ps]
    assert incomplete[0].n_ops == second.n_ops - 5
    assert [s.start_ps for s in complete] == [whole[0].start_ps, whole[2].start_ps]
    assert scopes.split_complete([]) == ([], [])


def test_scoped_trace_names_the_decode_steps_device_time():
    times = scopes.scope_times(str(SCOPED))
    assert len(times.complete) >= 3 and not times.incomplete
    assert times.named_share >= 0.9
    medians = times.medians()
    # AxO serves every attention projection: attn.proj holds only axo.*
    for scope in set(scopes.SCOPES) - {"attn.proj"}:
        assert medians[scope] > 0, scope


def test_metric_readers_on_the_scoped_trace():
    glue = scopes.axo_glue_ms_per_step(_ctx(SCOPED))
    mlp = scopes.mlp_ms_per_step(_ctx(SCOPED))
    kv = scopes.kv_update_ms_per_step(_ctx(SCOPED))
    times = scopes.scope_times(str(SCOPED))
    assert glue == pytest.approx(times.median_ms("axo.quantize", "axo.gather"))
    assert 0 < glue and 0 < mlp and 0 < kv
    step = times.median_ms(*scopes.SCOPES, scopes.OTHER)
    assert glue + mlp + kv < step
    # the glue is the AxO cell's alone; no trace, no reading
    assert scopes.axo_glue_ms_per_step(_ctx(SCOPED, axo=None)) is None
    assert scopes.mlp_ms_per_step(_ctx(None)) is None


def _tiny_server(tiny_cell, kind):
    import run

    cell = tiny_cell(kind)
    serve = run.load_module(run.BENCH / "drivers" / "serve.py")
    cfg, fold = serve.served_model(cell.config)
    params = serve.make_weights(cfg, 0.06, 7, fold)
    dep = serve.deploy(params, cfg, cell.traffic["axo"])
    return serve, cfg, params, dep, cell


def test_axo_deploy_s_reads_the_programs_deploy_span(tiny_cell):
    from repro import obs

    _, _, _, dep, _ = _tiny_server(tiny_cell, "axo")
    last = [s for s in obs.GLOBAL.spans if s.name == "axo.deploy"][-1]
    assert last.attrs["entries"] == dep.n_entries > 0
    assert scopes.axo_deploy_s(_ctx(None)) == last.duration_s > 0


@pytest.fixture
def no_persistent_cache():
    """Compile afresh: the persistent cache's key leaves out op metadata, so
    a program cached before a scope changed would keep its old names."""
    import jax

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("kind", ["axo", "exact"])
def test_decode_step_hlo_names_every_scope(tiny_cell, no_persistent_cache, kind):
    import jax
    import jax.numpy as jnp

    from repro.launch.steps import init_cache, make_decode_step
    from repro.models.sharding import BASE_RULES

    _, cfg, params, dep, cell = _tiny_server(tiny_cell, kind)
    t = cell.traffic
    cache = init_cache(cfg, t["batch"], t["prompt_len"] + t["gen"])
    tokens = jnp.zeros((t["batch"], 1), jnp.int32)
    kw = {} if dep is None else {"axo": dep}
    hlo = jax.jit(make_decode_step(cfg, BASE_RULES)).lower(
        params, cache, tokens, jnp.int32(t["prompt_len"]), **kw).compile().as_text()
    named = {part for op in re.findall(r'op_name="([^"]*)"', hlo)
             for part in op.split("/")}
    want = set(scopes.SCOPES)
    if dep is None:
        want = {s for s in want if not s.startswith("axo.")}
    assert want <= named
    assert named & set(scopes.SCOPES) == want


def test_every_named_scope_of_the_program_is_read():
    """Each literal ``jax.named_scope`` name in the program is one of the
    scopes this reader attributes time to (``scope_names/``)."""
    src = Path(__file__).resolve().parents[2] / "src" / "repro"
    named = {m for p in src.rglob("*.py")
             for m in re.findall(r'named_scope\(\s*"([^"]+)"', p.read_text())}
    assert named and named <= set(scopes.SCOPES), named - set(scopes.SCOPES)


# Each listed reader of the serving cells on the committed traces, as PR 14's
# harness read them (the four scope readers from its ``scopes.py``): the
# cell's full-size counts over each trace's window, so the shares are
# readings to hold fixed, not shares of this small trace's work.
BEFORE = {
    ("scoped", "axo_decode"): {
        "decode_step_ms": 1.9690530000000053,
        "axo_matmul_roofline": 20748.62861999174,
        "serve_mfu": 504.7241303324168,
        "device_idle_share.serve": 54.255167434274846,
        "host_gap_ms_per_step": 2.181259,
        "axo_glue_ms_per_step": 1.0718017979999999,
        "mlp_ms_per_step": 0.18560749999999998,
        "kv_update_ms_per_step": 0.0011624219999999998},
    ("scoped", "exact_decode"): {
        "decode_step_ms": 1.9690530000000053,
        "axo_matmul_roofline": None,
        "serve_mfu": 504.7241303324168,
        "device_idle_share.serve": 54.255167434274846,
        "host_gap_ms_per_step": 2.181259,
        "axo_glue_ms_per_step": None,
        "mlp_ms_per_step": 0.18560749999999998,
        "kv_update_ms_per_step": 0.0011624219999999998},
    ("probe", "axo_decode"): {
        "decode_step_ms": 0.03672700000000029,
        "axo_matmul_roofline": 6157479.59247194,
        "serve_mfu": 574.2140605731098,
        "device_idle_share.serve": 99.05025125760784,
        "host_gap_ms_per_step": 3.4024615,
        "axo_glue_ms_per_step": None,
        "mlp_ms_per_step": None,
        "kv_update_ms_per_step": None},
    ("probe", "exact_decode"): {
        "decode_step_ms": 0.03672700000000029,
        "axo_matmul_roofline": None,
        "serve_mfu": 574.2140605731098,
        "device_idle_share.serve": 99.05025125760784,
        "host_gap_ms_per_step": 3.4024615,
        "axo_glue_ms_per_step": None,
        "mlp_ms_per_step": None,
        "kv_update_ms_per_step": None},
}


@pytest.mark.parametrize("trace,traffic", sorted(BEFORE))
def test_listed_readers_read_the_committed_traces_as_before(trace, traffic):
    import json

    import counts
    import run
    import trace_reduce

    path = DATA / f"{trace}.xplane.pb"
    summary = trace_reduce.reduce_trace(path)
    config = json.loads((run.BENCH / "configs" / "granite-3-2b.json").read_text())
    t = json.loads((run.BENCH / "traffic" / f"{traffic}.json").read_text())
    family = run.load_module(run.BENCH / "models" / "granite.py")
    spans = [("bench.decode", s / 1e9, e / 1e9)
             for name, s, e in summary.module_events if "decode_step" in name]
    ctx = {"run": SimpleNamespace(trace_path=path, window_s=summary.window_s,
                                  t_window=0.0, spans=spans),
           "layer": {"batches": 2, "batch": t["batch"], "prompt_len": t["prompt_len"],
                     "gen": t["gen"], "axo": t["axo"], "model": family.shapes(config)},
           "trace": summary, "peaks": counts.peaks_for("TPU v5 lite"),
           "config": config, "traffic": t}
    listed = {m["name"] for m in run.load_json(run.ROOT / "BENCHMARK.json")["per_layer"]
              if "granite.exact.decode" in m["workloads"]
              or "granite.axo.decode" in m["workloads"]}
    # axo_deploy_s reads the process's own deploy span, not a trace
    assert listed - {"axo_deploy_s"} == set(BEFORE[trace, traffic])
    got = {name: run.load_module(run.BENCH / "metrics" / f"{name}.py").read(ctx)
           for name in BEFORE[trace, traffic]}
    assert got == BEFORE[trace, traffic]
