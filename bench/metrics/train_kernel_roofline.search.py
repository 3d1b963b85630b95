"""Training kernel (its custom call ``..._train_forward_...``, in the
evaluation and the refinement programs) time against its roofline: the
larger of its operations over the bf16 peak and its bytes over the HBM
bandwidth (``harness.search_costs.train_kernel``: real time steps only),
over its time in the trace; %."""
from harness import costs


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not ctx.get("kernel_ops"):
        return None
    secs, n = tr.op_time("train_forward")
    if not n or secs <= 0:
        return None
    pk = costs.peaks(ctx["device_kind"])
    least = max(ctx["kernel_ops"] / pk["flops_bf16"],
                ctx["kernel_bytes"] / pk["hbm_bytes_per_s"]) / ctx["chips"]
    return 100.0 * least / secs
