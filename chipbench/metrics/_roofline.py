"""A kernel's share of its roofline, in %: the least time the chip could
take for the window's work of that kernel (the larger of its operations
over the peak rate and its bytes over the HBM bandwidth, from
``chipbench/kernels/<kernel>.py`` and ``peaks.json``) over the device
time of the kernel's operations in the trace (the kernel module's
``OP``). The work is that of the queries served in the window, not of
the pad rows of their batches (``window(ctx)`` of the kernel module).
Nothing to read, when the trace holds none of the kernel's operations."""
import importlib


def read_kernel(ctx, kernel: str):
    if ctx.events is None or ctx.peaks is None:
        return None
    mod = importlib.import_module(f"chipbench.kernels.{kernel}")
    sec = ctx.events.op_seconds(mod.OP, ctx.cell["chips"])
    work = mod.window(ctx) if sec is not None else None
    if work is None:
        return None
    ops, nbytes = work
    least = max(ops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / sec
