"""Padded int32 compares per second of device time of the epoch program,
in G compares/s: ``counting.epoch_compares`` of the problem's shapes,
times the epochs in the window, over the device time of that program's
module events in the trace."""


def read(run):
    if run.trace is None:
        return None
    name = run.stats["module_name"]
    mods = [e for e in run.trace.modules()
            if e.name == name or e.name.startswith(name + "(")]
    dev_s = sum(e.dur_ns for e in mods) * 1e-9
    if dev_s <= 0:
        return None
    return run.stats["compares_per_epoch"] * run.stats["epochs"] / dev_s / 1e9
