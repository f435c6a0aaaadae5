"""Lattice driver: mean device-idle time between sweeps, in ms.

From the trace: on each device, the time from the last op of one sweep to
the first op of the next (records to the host, the harness's loop, the
next dispatch), averaged over the sweep pairs and the devices."""


def read(ctx):
    if ctx.red is None:
        return None
    gaps = [g for d in ctx.red.devices for g in d.sweep_gaps_ns]
    return sum(gaps) / len(gaps) / 1e6 if gaps else None
