"""Operation and byte counts against hand counts at the cells' shapes, and
the peak table.  The projection shapes come from the model family
(``bench/models/granite.py``), from the configuration file alone."""

import json

import pytest

import counts
import run

CONFIG = json.loads((run.BENCH / "configs" / "granite-3-2b.json").read_text())
GRANITE = run.load_module(run.BENCH / "models" / "granite.py").shapes(CONFIG)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        counts.peaks_for("cpu")


def test_v5e_peaks_as_published():
    p = counts.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "v5e" in json.loads(counts.PEAKS.read_text())["source"]


@pytest.mark.parametrize("m,ops,nbytes", [
    # decode: 8 rows; K=N=2048 (wq, wo)
    (8, 2 * 8 * 2048 * 2048 * 2, 2048 * 2048 + 2 * 8 * 2048 * 2 + 2 * 256 * 4),
    # prefill: 8 x 512 rows
    (4096, 2 * 4096 * 2048 * 2048 * 2,
     2048 * 2048 + 2 * 4096 * 2048 * 2 + 2 * 256 * 4),
])
def test_axo_matmul_work_at_the_cell_shapes(m, ops, nbytes):
    assert counts.axo_matmul_work(m, 2048, 2048, rank=1) == (ops, nbytes)
    assert ops == {8: 134_217_728, 4096: 68_719_476_736}[m]


def test_axo_matmul_work_kv_projection():
    # wk/wv: (2048, 512), 8 decode rows, rank 1
    ops, nbytes = counts.axo_matmul_work(8, 2048, 512, rank=1)
    assert ops == 33_554_432
    assert nbytes == 1_048_576 + 32_768 + 8_192 + 2_048


def test_granite_model_flops_per_token():
    # per layer: wq 4194304 + wk 1048576 + wv 1048576 + wo 4194304
    #            + 3 x 16777216 (gate, up, down) = 60817408
    # 40 layers + tied head 2048 x 49155 = 2533365760 matmul params
    assert len(GRANITE["layers"]) == GRANITE["attn_layers"] == 40
    for layer in GRANITE["layers"]:
        assert sum(k * n for group in layer.values() for k, n in group.values()) \
            == 60_817_408
    assert counts.model_flops(GRANITE, 1, 0) == 2 * 2_533_365_760
    # attention: 2 matmuls x 2 flops x 40 layers x 2048 width per context slot
    assert counts.model_flops(GRANITE, 1, 100) - counts.model_flops(GRANITE, 1, 0) \
        == 327_680 * 100


def test_serve_flops_of_one_batch():
    b, p, g = 8, 512, 64
    prefill = b * p * 2 * 40 * 60_817_408 + b * 4 * 40 * 2048 * (p * (p + 1) // 2) \
        + b * 2 * 2048 * 49155
    decode = sum(b * (2 * 2_533_365_760 + 327_680 * (p + i + 1)) for i in range(g - 1))
    assert counts.serve_flops(GRANITE, b, p, g) == prefill + decode


def test_axo_calls_of_one_batch():
    calls = counts.axo_calls(GRANITE, ("attn",), 8, 512, 64)
    assert len(calls) == 64 * 40 * 4
    assert calls[0] == (4096, 2048, 2048) and calls[1] == (4096, 2048, 512)
    assert calls[-1] == (8, 2048, 2048)
    assert counts.axo_calls(GRANITE, (), 8, 512, 64) == []


@pytest.mark.parametrize("traffic", ["axo_decode", "exact_decode"])
def test_counts_of_the_cells_batches_are_the_parents(traffic):
    """What the counts gave before the family held the shapes (PR 14's
    ``counts.py`` over the dense dimensions), to the last digit: the AxO
    calls' order too, which fixes the roofline's sum."""
    t = json.loads((run.BENCH / "traffic" / f"{traffic}.json").read_text())
    b, p, g = t["batch"], t["prompt_len"], t["gen"]
    assert counts.serve_flops(GRANITE, b, p, g) == 6_428_295_168_000
    calls = counts.axo_calls(GRANITE, ("attn",), b, p, g)
    # each step: every layer's wq, wk, wv, wo
    assert calls == [(m, 2048, n) for m in [256] + [8] * 127
                     for _ in range(40) for n in (2048, 512, 512, 2048)]
    assert len(calls) == 20_480


def test_idle_between_counts_uncovered_time():
    busy = [[0, 10], [20, 30], [35, 40]]
    assert counts.idle_between(busy, 5, 37) == 32 - (5 + 10 + 2)
    assert counts.idle_between(busy, 10, 20) == 10


def test_idle_share_is_not_clamped():
    """A busy time past the window reads below 0 instead of hiding."""
    from types import SimpleNamespace as NS

    ctx = {"trace": NS(busy_s=10.5, n_devices=1), "run": NS(window_s=10.0)}
    assert counts.idle_share(ctx) == pytest.approx(-5.0)
    ctx["trace"].busy_s = 7.5
    assert counts.idle_share(ctx) == pytest.approx(25.0)
