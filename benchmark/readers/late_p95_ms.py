"""How late the load generator ran: submit time minus due time, 95th
percentile over the requests due in the window, in milliseconds."""


def read(ctx):
    late = sorted(ctx["run"].get("late_ms", ()))
    if not late:
        return None
    return late[min(len(late) - 1, int(0.95 * len(late)))]
