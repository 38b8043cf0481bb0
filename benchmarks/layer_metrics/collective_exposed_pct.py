"""Time of collective ops on the busiest device's serial ops line, over
the window."""


def read(ctx):
    exposed = ctx["trace"]["collective_exposed_s"]
    if exposed <= 0:
        return None
    return 100.0 * exposed / ctx["trace"]["window_s"]
