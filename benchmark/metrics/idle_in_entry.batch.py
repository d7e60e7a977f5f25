"""Share of the traced slice in which the device runs no operation while
the host is inside a search entry span (``ivf_flat::search``,
``ivf_pq::search``, ``refine::refine``): idle time the program's own host
side causes. ``device_idle.batch`` less this is idle time spent in the
harness's fetch and wait."""

import host_spans


def read(ctx):
    found = host_spans.entry(ctx)
    t = ctx.trace
    if found is None or not t.devices:
        return None
    return 100.0 * host_spans.overlap(host_spans.idle(t), found[0]) \
        / (t.t1 - t.t0)
