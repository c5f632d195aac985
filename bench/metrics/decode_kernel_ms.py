"""``decode_kernel_ms``: device time per cold start of the decode kernels'
operations in the trace, inside the ``bench.coldstart`` spans."""

KERNELS = ("fused_verify_decrypt", "sha256_lanes", "aes_bitsliced")


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans_named("bench.coldstart")
    ns = run.trace.op_ns(KERNELS, within=spans) if spans else None
    return None if ns is None else ns / len(spans) / 1e6
