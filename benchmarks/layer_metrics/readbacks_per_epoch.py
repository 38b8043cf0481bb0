"""``trainer.readbacks`` over the window's epochs."""


def read(ctx):
    if not ctx["epochs"]:
        return None
    return ctx["readbacks"] / ctx["epochs"]
