"""Peak bytes in use on the fullest device over the chip's memory."""


def read(ctx):
    if not ctx["memory_peak_bytes"]:
        return None
    return 100.0 * ctx["memory_peak_bytes"] / ctx["peaks"]["hbm_bytes"]
