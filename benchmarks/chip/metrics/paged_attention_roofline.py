"""The paged attention kernel's least time for the traced steps' attention
(``work.attention_work``: live KV read once per step and layer) over its
time in the device trace."""

KERNELS = {"paged_attention":
           r'custom-call\(s32\[.*custom_call_target="tpu_custom_call"'}


def read(run):
    if run.trace is None or run.traced_work is None:
        return None
    t = run.trace.kernel_s["paged_attention"]
    return 100.0 * run.traced_work["attention"].least_s / t if t > 0 else None
