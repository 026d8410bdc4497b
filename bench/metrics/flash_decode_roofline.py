"""Least time of every flash_decode call in the traced window over the
kernel's summed device time, %.  One call per layer per decode step, over
the occupied slots at the lengths they had cached."""
from bench.metrics._common import decode_steps, kernel_share


def read(run):
    k = run.counts("flash_decode")
    c, p = run.conf, run.peaks
    least = 0.0
    for s in decode_steps(run):
        f, b = k.call([ctx for ctx, _ in s.work],
                      heads=c["num_attention_heads"],
                      kv_heads=c["num_key_value_heads"],
                      head_dim=c["head_dim"],
                      tree_tokens=c["serving"]["tree_tokens"])
        least += c["num_hidden_layers"] * max(f / p["bf16_flops_per_s"],
                                              b / p["hbm_bytes_per_s"])
    return kernel_share(run, "flash_decode", least)
