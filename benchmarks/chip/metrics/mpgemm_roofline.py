"""The GEMM kernels' least time for the traced steps' matrix products
(``work.gemm_work``: counted from the configuration and the tokens
processed) over the GEMM kernels' time in the device trace."""

KERNELS = {"mpgemm": r'^(?!.*custom-call\(s32\[).*custom_call_target="tpu_custom_call"'}


def read(run):
    if run.trace is None or run.traced_work is None:
        return None
    t = run.trace.kernel_s["mpgemm"]
    return 100.0 * run.traced_work["gemm"].least_s / t if t > 0 else None
