"""Share of its roofline that the packed strip-scan kernel reaches in the
batch cells: the least time the chip needs for the scan work of the
batches run in the traced slice (``roofline.scan_work``, held against the
int8 peak and the HBM bandwidth), over the kernel's summed device time."""

PATTERNS = (r"_strip_class_call", r"_strip_kernel")


def read(ctx):
    t, marks = ctx.trace, ctx.marks
    if t is None or "stop" not in marks:
        return None
    batches = marks["stop"]["n_done"] - marks["start"]["n_done"]
    kernel_s = t.op_time_s(PATTERNS)
    if batches <= 0 or kernel_s <= 0:
        return None
    flops, nbytes = ctx.roofline.scan_work(ctx.config,
                                           ctx.traffic["batch"])
    share, bound = ctx.roofline.roofline_share(
        batches * flops, batches * nbytes, kernel_s, ctx.peaks)
    ctx.log(f"strip_scan_roofline: batches={batches} kernel_s={kernel_s} "
            f"bound={bound}")
    return share
