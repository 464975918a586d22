"""The AxO matmul kernel's share of its roofline: the least time the chip
needs for the work the operator defines (every call of the window: ops on
the int8 peak, bytes on HBM bandwidth, the larger), over the kernel's device
time in the trace."""

from counts import axo_calls, axo_matmul_work

KERNEL = "axo_matmul"


def read(ctx):
    tr, lay, peaks = ctx["trace"], ctx["layer"], ctx["peaks"]
    if tr is None or not lay.get("axo") or not tr.kernel_count(KERNEL):
        return None
    rank = int(lay["axo"]["rank"])
    least = 0.0
    for m, k, n in axo_calls(lay["model"], tuple(lay["axo"]["layers"]),
                             lay["batch"], lay["prompt_len"], lay["gen"]):
        ops, nbytes = axo_matmul_work(m, k, n, rank)
        least += max(ops / peaks["int8_ops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least * lay["batches"] / tr.kernel_s(KERNEL)
