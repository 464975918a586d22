"""Record ``data/scoped.xplane.pb`` on a TPU: three decode steps of the
Granite decoder at published widths cut to two layers, with the rank-1 AxO
operator of ``granite.axo.decode`` in every attention projection on the
Pallas kernel, under the profiler.  To keep the file small, only the device
and host planes are kept, each as recorded.

    python3 bench/tests/record_scoped_trace.py <out.xplane.pb>

The test that reads it (``test_bench_scopes.py``) needs no chip.
"""

import json
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import generate  # noqa: E402
import run  # noqa: E402
import scopes  # noqa: E402


def keep_planes(xspace: bytes) -> bytes:
    """The XSpace with its TPU planes and its host plane alone."""
    out = bytearray()
    for name, (start, end) in scopes.plane_spans(xspace):
        if name.startswith("/device:TPU:") or name == "/host:CPU":
            out.append(0x0A)                    # field 1, length-delimited
            n = end - start
            while n >= 0x80:
                out.append(n & 0x7F | 0x80)
                n >>= 7
            out.append(n)
            out += xspace[start:end]
    return bytes(out)


def main(out: str) -> None:
    serve = run.load_module(BENCH / "drivers" / "serve.py")
    config = dict(run.load_json(BENCH / "configs" / "granite-3-2b.json"),
                  num_hidden_layers=2)
    traffic = dict(run.load_json(BENCH / "traffic" / "axo_decode.json"), gen=4)
    cfg, fold = serve.served_model(config)
    params = serve.make_weights(cfg, float(config["initializer_range"]), 0, fold)
    dep = serve.deploy(params, cfg, traffic["axo"])
    bench_run = run.Run(seed=0, seconds=0.0, trace=True)
    server = serve.Server(cfg, traffic, params, dep, bench_run)
    prompts = generate.prompts(traffic, cfg.vocab, 0, 0)
    server.batch(prompts, [])                       # compile every program
    logits, cache = server.prefill(params, jnp.asarray(prompts))
    nxt = server.argmax(logits)
    np.asarray(nxt)
    with tempfile.TemporaryDirectory() as tmp:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        for i in range(server.p, server.p + server.g - 1):
            with bench_run.span("bench.decode"):
                logits, cache = server.decode(params, cache, nxt, jnp.int32(i))
                nxt = server.argmax(logits)
                np.asarray(nxt)
        jax.profiler.stop_trace()
        (found,) = Path(tmp).glob("plugins/profile/*/*.xplane.pb")
        Path(out).write_bytes(keep_planes(found.read_bytes()))
    print(json.dumps({"out": out, "bytes": Path(out).stat().st_size,
                      "device": jax.devices()[0].device_kind}))


if __name__ == "__main__":
    main(sys.argv[1])
