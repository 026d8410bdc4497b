"""Device time of the decode steps' row-parallel psums
(``counts/all_reduce.py``'s ``MATCH``) over the device's busy time in the
traced window, %, averaged over the chips: the share of the chip's busy
time spent in the step's exchange."""


def read(run):
    if run.trace is None:
        return None
    spent = run.trace["kernel_s"].get("all_reduce", 0.0)
    busy = run.trace["busy_s"]
    return 100.0 * spent / busy if spent > 0 and busy > 0 else None
