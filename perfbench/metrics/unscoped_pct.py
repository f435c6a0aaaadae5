"""Round step: the share of the device's busy time spent in ops that no
round stage names, in %, averaged over the chips. Such ops are the scan's
own plumbing (record stacking, loop counter), the per-sweep set-up and any
module the program did not register (``perfbench/program_trace.py``). A
rising share means a stage's ops lost their name."""


def read(ctx):
    from perfbench.program_trace import current, stage_ns

    pt = current() if ctx.red is not None else None
    stages = stage_ns(pt) if pt is not None else None
    if stages is None:
        return None
    from repro.obs.stages import UNSCOPED

    shares = [
        100.0 * per.get(UNSCOPED, 0.0) / d.busy_ns
        for per, d in zip(stages, pt.red.devices) if d.busy_ns
    ]
    return sum(shares) / len(shares) if shares else None
