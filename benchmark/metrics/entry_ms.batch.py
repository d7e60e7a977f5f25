"""Host time inside the search entry per batch: the union of the
``ivf_flat::search``, ``ivf_pq::search`` and ``refine::refine`` spans in
the traced slice, on the profiler's clock, over the batches run in it.
The inside counterpart of ``dispatch_ms.batch``."""

import host_spans


def read(ctx):
    found = host_spans.entry(ctx)
    if found is None:
        return None
    iv, n = found
    return 1e-6 * host_spans.total(iv) / n
