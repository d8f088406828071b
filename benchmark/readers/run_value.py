"""A number the driver worked out for the window itself, by its key:
what stands beside the end-to-end metrics without being one."""


def read(ctx, key: str):
    return ctx["run"]["end_to_end"].get(key)
