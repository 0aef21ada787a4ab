"""Operations and bytes the algorithm needs, computed from shapes.

These are chipbench's own: roofline shares and MFU divide by them, so
no PR that claims a gain can change them. ``cfg`` is a configuration
file's dict (OPT key names).
"""


def _dims(cfg):
    d = cfg["hidden_size"]
    return (d, cfg["ffn_dim"], cfg["num_hidden_layers"],
            cfg["vocab_size"], cfg["num_attention_heads"])


def train_flops_per_token(cfg, seq_len):
    """Forward + backward FLOPs one token requires, no recompute: the
    backward is twice the forward. Per layer 8 d^2 (q, k, v, o) +
    4 d f (FFN) + causal-useful attention 2 T d (scores and values over
    the T/2 keys a causal query sees on average); head 2 d V. The
    embedding gather and elementwise work are not counted."""
    d, f, n_layer, vocab, _ = _dims(cfg)
    fwd = n_layer * (8 * d * d + 4 * d * f + 2 * seq_len * d) \
        + 2 * d * vocab
    return 3 * fwd


def flash_flops_per_step(cfg, batch, seq_len):
    """Causal-useful FLOPs of the flash kernels in one train step:
    forward 2 matmuls, backward 5 (s, dp, dv, dk, dq), each
    2 * T^2/2 * dk per head -> 7 * T^2 * d per sequence and layer."""
    d, _, n_layer, _, _ = _dims(cfg)
    return 7 * seq_len * seq_len * d * n_layer * batch


def decode_step_bytes(cfg, dtype_bytes, live_kv_tokens, rows):
    """Bytes one decode step must read: every weight once (layers and
    the output head; of the embedding tables only the step's rows) and
    the K and V vectors of every token the step's rows attend to."""
    d, f, n_layer, vocab, _ = _dims(cfg)
    layer = 4 * d * d + 2 * d * f + f + 5 * d      # matrices, biases, LN
    weights = n_layer * layer + d * vocab + 2 * rows * d
    kv = 2 * n_layer * d * live_kv_tokens
    return dtype_bytes * (weights + kv)
