"""The comparisons that decide ``correct``.

Tolerances, with the reason for each (my chip runs, PR 23). A logits
error is the largest absolute difference over the largest absolute
reference logit; storing one mantissa bit less doubles it, and fp8
e4m3, four bits less than bfloat16, multiplies it by about sixteen.

* ``LOGITS_RTOL`` (serving): the served bfloat16 model's teacher-forced
  logits against the float32 reference on the same (bfloat16-rounded)
  weights. Through 8 post-LN layers and the 1024-wide head the chip
  measured 5.8e-3 to 6.9e-3 under some 25 seeds. 2e-2 is
  three times the largest of them, so no seed trips it, and a quarter
  of what fp8 would give.
* ``NEAR_TIE`` (serving): where the engine's token is not the
  reference's argmax, it must sit within two bfloat16 steps (2 * 2**-7
  of the top logit) of it: a random-weight model's top logits are
  bunched that closely, and bfloat16 ties them exactly (chip_smoke.py's
  rule, PR 21). Measured: at most 5.1e-3 below the top.
* ``TRAIN_LOGITS_RTOL`` (training): the program's bf16-AMP forward
  (its ``for_test`` clone, float32 master weights, bfloat16 matmuls)
  against the float32 reference, on the last 64 rows of one 2048-token
  sequence through all 24 layers: measured 8.8e-3 to 1.5e-2 under ten
  seeds, mean 1.2e-2 (1.1e-2 to 1.6e-2 over all 2048 rows; float32
  operands at the TPU's default matmul precision give the same 0.9e-2
  to 1.2e-2). 3e-2 is twice the largest and ten standard deviations
  from the mean, and a fifth of what fp8 would give.
* ``LOSS_RTOL`` (training): the first step's bf16-AMP loss against the
  reference's on the same parameters and batch. A mean over 8,192
  tokens of a fresh model is about ln V and averages the rounding out:
  measured 2e-7 to 1.6e-6. 1e-4 fails a forward pass that drops a term
  and says nothing of precision; ``TRAIN_LOGITS_RTOL`` is for that.
"""

import numpy as np

LOGITS_RTOL = 2e-2
NEAR_TIE = 2 * 2.0 ** -7
TRAIN_LOGITS_RTOL = 3e-2
LOSS_RTOL = 1e-4


def logits_error(got, ref):
    """max |got - ref| / max |ref| over float32 views."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def tie_gaps(ref_logits, tokens):
    """For each position, how far below the reference's top logit the
    emitted token's logit sits, relative to the top logit's size."""
    ref = np.asarray(ref_logits, np.float32)
    top = ref.max(-1)
    got = ref[np.arange(len(tokens)), np.asarray(tokens)]
    return (top - got) / np.abs(top)


def loss_error(got, ref):
    return abs(float(got) - float(ref)) / abs(float(ref))
