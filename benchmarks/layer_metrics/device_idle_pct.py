"""1 - busy / window on the busiest device, from the profiler trace."""


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr["busy_s_busiest"] / tr["window_s"])
