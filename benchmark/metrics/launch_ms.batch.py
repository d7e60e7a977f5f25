"""Host time spent launching programs per batch: the union of the
``ivf_flat::launch``, ``ivf_pq::launch`` and ``refine::launch`` spans in
the traced slice (each ends when its jitted call returns, so it times the
enqueue), over the batches run in it. Part of ``entry_ms.batch``; the
rest of the entry is its Python prologue and planning."""

import host_spans


def read(ctx):
    found = host_spans.entry(ctx)
    if found is None:
        return None
    launches = host_spans.spans(ctx.trace, host_spans.LAUNCH_SPANS)
    if not launches:
        return None
    return 1e-6 * host_spans.total(launches) / found[1]
