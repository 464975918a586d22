"""Model FLOPs of every prefill and decode token served in the window over
the window and the chip's bfloat16 peak.  The operator's rank terms do not
count, so the AxO and the exact model read the same work."""

from counts import serve_flops


def read(ctx):
    lay, run = ctx["layer"], ctx["run"]
    if not lay.get("batches"):
        return None
    flops = lay["batches"] * serve_flops(lay["model"], lay["batch"],
                                         lay["prompt_len"], lay["gen"])
    return 100.0 * flops / run.window_s / ctx["peaks"]["bf16_flops_per_s"]
