"""Share of its roofline that the paged strip-scan kernel reaches in the
served cell: the scan work of the requests the queue dispatched in the
traced slice (``serving.requests.ok`` / ``serving.batches`` counter
deltas; each dispatch holds the mean count), over the kernel's summed
device time."""

PATTERNS = (r"_paged_class_call", r"_paged_strip_kernel")


def read(ctx):
    t, marks = ctx.trace, ctx.marks
    if t is None or "stop" not in marks:
        return None
    c0, c1 = marks["start"]["counters"], marks["stop"]["counters"]
    requests = c1.get("serving.requests.ok", 0) - c0.get(
        "serving.requests.ok", 0)
    batches = c1.get("serving.batches", 0) - c0.get("serving.batches", 0)
    kernel_s = t.op_time_s(PATTERNS)
    if batches <= 0 or kernel_s <= 0:
        return None
    flops, nbytes = ctx.roofline.scan_work(ctx.config, requests / batches)
    share, bound = ctx.roofline.roofline_share(
        batches * flops, batches * nbytes, kernel_s, ctx.peaks)
    ctx.log(f"paged_scan_roofline: dispatches={batches} requests={requests} "
            f"kernel_s={kernel_s} bound={bound}")
    return share
