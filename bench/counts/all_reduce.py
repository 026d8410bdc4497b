"""Bytes one chip must send in one ring all-reduce of the tensor-parallel
decode step's row-parallel exchange (``layers.tp_reduce``: the psum after
attention ``wo`` and after the MLP down-projection), and the chip's
interconnect bandwidth it is set against.

A ring all-reduce over n chips is a reduce-scatter and an all-gather: each
chip sends (n - 1)/n of the operand in each, 2 (n - 1)/n of it in all.
Bytes sent over the chip's whole ICI bandwidth is a lower bound on the
op's time, so the share read against it cannot pass 100%.
"""

# The psum's device op in a TPU trace: ``%psum.34 = bf16[16,64,4096]{...}
# all-reduce(...)``.  The decode step's psums of the hidden state are 3-D
# bf16 all-reduces over [B, T, d], T the 64 tokens of the ``medusa_63``
# tree.  The prefill's psums over [B, S, d] (S a prompt bucket), the verify
# epilogue's f32 reductions and the vocabulary all-gathers are not matched.
MATCH = r"= bf16\[\d+,64,\d+\]\{[^}]*\} all-reduce\("

# Google Cloud documentation, "TPU v5e": 1,600 Gbit/s of inter-chip
# interconnect (ICI) bandwidth per chip.
ICI_BYTES_PER_S = {"TPU v5 lite": 1600e9 / 8}


def call(rows: int, d_model: int, chips: int, act_bytes: int = 2) -> float:
    """Bytes one chip sends in a ring all-reduce of a [rows, d_model]
    operand over ``chips`` chips."""
    return 2 * (chips - 1) / chips * rows * d_model * act_bytes
