"""Multi-head latent attention's score and value (ISSUE 34).

A head's key is two parts: `k_nope` of its own (D lanes, up-projected
from the compressed latent) and `k_pe`, ONE rotary key of Dr lanes that
every head reads; its query is `[q_nope | q_pe]` to match and its value
is D wide. The Program op `mla_attention` takes the five projections as
they come, [B, T, H*D] / [B, T, H*Dr] / [B, T, Dr], turns `q_pe` and
`k_pe` by their rows' positions (rotate-half, the frequencies given:
YaRN's) and hands all five to the flash kernels, whose score is the SUM
of the two products (`ops/flash_attention.py`, "a score of two parts"):
`k_pe` is never repeated over the heads and no operand is padded from
D + Dr to a multiple of 128 in HBM.
"""

from ..core.registry import register
from .flash_attention import flash_bthd
from .rotary import norm_rope


def mla_attention(q_nope, q_pe, k_nope, k_pe, v, n_head, inv_freq, scale,
                  force=None):
    """Causal softmax((q_nope k_nope^T + rope(q_pe) rope(k_pe)^T) *
    scale) v over [B, T, .] operands: [B, T, H*D]."""
    inv_freq = tuple(float(f) for f in inv_freq)
    q_pe = norm_rope(q_pe, None, n_head, inv_freq)
    k_pe = norm_rope(k_pe, None, 1, inv_freq)
    return flash_bthd(q_nope, k_nope, v, n_head, causal=True, scale=scale,
                      force=force, q2=q_pe, k2=k_pe)


@register("mla_attention")
def _mla_attention(ctx, op):
    """QNope, KNope, V [B, T, H*D], QPe [B, T, H*Dr], KPe [B, T, Dr];
    attrs n_head, scale, inv_freq (Dr / 2 floats). Out [B, T, H*D]."""
    ctx.set_out(op, "Out", mla_attention(
        ctx.in1(op, "QNope"), ctx.in1(op, "QPe"), ctx.in1(op, "KNope"),
        ctx.in1(op, "KPe"), ctx.in1(op, "V"), int(op.attr("n_head")),
        op.attr("inv_freq"), float(op.attr("scale"))))
