"""``loader.fill`` of every class but TRAIN, per epoch: the loader filling
validation and test minibatches from the host."""
from benchmarks.lib import program_spans as ps


def read(ctx):
    run = ps.of_run(ctx)
    if run is None:
        return None
    return ps.total_ms(
        run["spans"],
        lambda s: s[0] == "loader.fill" and s[5].get("clazz") != "train",
        run["n"])
