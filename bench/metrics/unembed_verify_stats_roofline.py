"""Least time of every unembed_verify_stats call in the traced window over
the kernel's summed device time, %.  One call per decode step over the
occupied slots' tree tokens; the head is read once per call."""
from bench.metrics._common import decode_steps, kernel_share


def read(run):
    k = run.counts("unembed_verify_stats")
    c, p = run.conf, run.peaks
    least = 0.0
    for s in decode_steps(run):
        f, b = k.call(slots=len(s.work), tree_tokens=c["serving"]["tree_tokens"],
                      d_model=c["hidden_size"], vocab=c["vocab_size"])
        least += max(f / p["bf16_flops_per_s"], b / p["hbm_bytes_per_s"])
    return kernel_share(run, "unembed_verify_stats", least)
