"""Growth of ``run_time_`` over the window, summed over every unit but
the fused trainer, per epoch."""


def read(ctx):
    t0, t1 = ctx["unit_time0"], ctx["unit_time1"]
    if not ctx["epochs"] or not t0:
        return None
    grown = sum(t1[name][0] - t0[name][0] for name in t1
                if name != ctx["trainer_name"] and name in t0)
    return 1e3 * grown / ctx["epochs"]
