"""The Mamba-2 recurrence (the operations under the program's ``ssm_scan``
scope, prefill and decode together) in the traced slice: the least time the
chip could take for the tokens laid into the slice (state read and written
once a decode step a row) over those operations' device time. The reducer
keys time by instruction name, so the scan's operations are known by what
they produce (the module's ``ssm_scan_op``)."""
from perfbench import archs


def read(ctx):
    arch, tr, peaks = archs.of(ctx["model"]), ctx.get("trace"), ctx.get("peaks")
    if not hasattr(arch, "ssm_scan_op") or not tr or peaks is None:
        return None
    m = ctx["model"]
    seconds = sum(
        v for k, v in (tr.get("all_ops_s") or {}).items() if arch.ssm_scan_op(m, k))
    work = arch.traced_work(ctx)
    tokens = work["prefill_tokens"] + work["decode_tokens"]
    if seconds <= 0.0 or tokens <= 0.0:
        return None
    least = max(
        arch.ssm_scan_flops(m, tokens) / peaks["bf16_flops_per_s"],
        arch.ssm_scan_bytes(m, work["prefill_tokens"], work["decode_tokens"], work["prompts"])
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
