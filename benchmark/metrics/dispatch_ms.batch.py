"""Mean host time of one batch call, from the call into the search entry
point to its return, before the harness waits for the device: the host
side of the search entry (argument checks, planning, dispatch)."""


def read(ctx):
    calls = ctx.record.get("calls")
    if not calls:
        return None
    return 1e3 * sum(r - c for c, r, _ in calls) / len(calls)
