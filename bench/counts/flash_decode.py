"""FLOPs and HBM bytes one ``flash_decode`` call needs (one layer, all
slots): the partial-softmax sweep of every slot's committed cache, q.k and
p.v for the folded query rows (query heads x tree tokens) against the
positions actually cached in that slot, not ``max_len``.

Bytes: K and V of those positions, q in, the f32 partial-softmax outputs
(acc, m, l) out.
"""

# The kernel's device op in a TPU trace is named after its caller
# ("%closed_call.39", "%_lambda_.1"), so it is found by its signature: a
# tpu_custom_call returning f32 (acc [B, Hkv, R, D], m [.., 1], l [.., 1]).
MATCH = (r"= \(f32\[\d+,\d+,\d+,\d+\]\{[^}]*\}, f32\[\d+,\d+,\d+,1\]\{[^}]*\}, "
         r"f32\[\d+,\d+,\d+,1\]\{[^}]*\}\) custom-call\(.*tpu_custom_call")


def call(lengths, *, heads: int, kv_heads: int, head_dim: int,
         tree_tokens: int, kv_bytes: int = 2, q_bytes: int = 2):
    """(flops, bytes) of one call over slots whose caches hold
    ``lengths`` positions."""
    ctx = sum(int(n) for n in lengths)
    slots = len(lengths)
    rows = heads * tree_tokens                    # folded (group x tree) rows
    flops = 4 * rows * head_dim * ctx             # q.k and p.v
    byts = (2 * kv_heads * head_dim * kv_bytes * ctx
            + slots * rows * head_dim * q_bytes
            + slots * rows * (head_dim + 2) * 4)
    return flops, byts
