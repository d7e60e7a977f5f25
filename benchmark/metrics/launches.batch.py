"""Programs one batch launches: the rise of the ``ivf_flat.search.launches``,
``ivf_pq.search.launches`` and ``refine.launches`` counters between the
traced slice's start and stop marks, over the batches run between them.
A count: it repeats exactly from run to run."""

import host_spans


def read(ctx):
    found = host_spans.entry(ctx)
    if found is None:
        return None
    c0, c1 = ctx.marks["start"]["counters"], ctx.marks["stop"]["counters"]
    launched = sum(c1.get(c, 0) - c0.get(c, 0)
                   for c in host_spans.LAUNCH_COUNTERS)
    if launched <= 0:
        return None
    return launched / found[1]
