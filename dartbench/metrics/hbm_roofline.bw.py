"""Share of the HBM roofline, in percent: the least time the chip could
take -- every payload byte the stretch's ops asked for read once and
written once, 2 x bytes over peak HBM bytes/s from peaks.json -- over
the device's busy time in the traced stretch.  The ops are byte copies
and compute nothing, so bandwidth bounds them.  Bytes come from the ops
issued, never from plan shapes or buckets."""


def read(run):
    if run.trace is None:
        return None
    busy = run.trace.busy_union_s()
    if busy <= 0 or not run.stretch["bytes"]:
        return None
    least = 2 * run.stretch["bytes"] / run.peaks["hbm_bytes_per_s"]
    return 100 * least / busy
