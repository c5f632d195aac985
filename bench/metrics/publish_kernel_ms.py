"""``publish_kernel_ms``: device time per publish of the publish kernels'
operations in the trace (key derivation and naming on the SHA kernel,
encryption on the AES kernel), inside the ``bench.publish`` spans."""

KERNELS = ("sha256_lanes", "aes_bitsliced", "fused_verify_decrypt")


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans_named("bench.publish")
    ns = run.trace.op_ns(KERNELS, within=spans) if spans else None
    return None if ns is None else ns / len(spans) / 1e6
