"""Least time of the decode steps' row-parallel exchange in the traced
window over the matched psums' summed device time, %: each psum's ring
bytes over [slots x tree tokens, d] (``counts/all_reduce.py``) over the
chip's ICI bandwidth."""
from bench.metrics._common import kernel_share


def read(run):
    if run.trace is None:
        return None
    k = run.counts("all_reduce")
    c, srv = run.conf, run.mix["server"]
    calls = run.trace["kernel_calls"].get("all_reduce", 0)
    byts = calls * k.call(srv["slots"] * c["serving"]["tree_tokens"],
                          c["hidden_size"], run.chips)
    bandwidth = k.ICI_BYTES_PER_S[run._device.device_kind]
    return kernel_share(run, "all_reduce", byts / bandwidth)
