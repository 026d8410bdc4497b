"""FLOPs and HBM bytes one ``unembed_verify_stats`` call needs: the tree
tokens' hidden rows times the [d, V] lm head, with only per-row statistics
written back (argmax, max, sum, and the T x T candidate logits).

Bytes: the head once per call, the hidden rows and candidates in, the
statistics out.  A kernel that reads the head once per slot spends more
bytes than this and reads a lower share.
"""

# Found by its signature in the trace: a tpu_custom_call returning
# (argm s32[B, T, 1], m f32[B, T, 1], l f32[B, T, 1], cand_w f32[B, T, T]).
MATCH = (r"= \(s32\[\d+,\d+,1\]\{[^}]*\}, f32\[\d+,\d+,1\]\{[^}]*\}, "
         r"f32\[\d+,\d+,1\]\{[^}]*\}, f32\[\d+,\d+,\d+\]\{[^}]*\}\) "
         r"custom-call\(.*tpu_custom_call")


def call(*, slots: int, tree_tokens: int, d_model: int, vocab: int,
         w_bytes: int = 2, h_bytes: int = 2):
    rows = slots * tree_tokens
    flops = 2 * rows * d_model * vocab
    byts = (d_model * vocab * w_bytes + rows * d_model * h_bytes + rows * 4
            + rows * 3 * 4 + rows * tree_tokens * 4)
    return flops, byts
