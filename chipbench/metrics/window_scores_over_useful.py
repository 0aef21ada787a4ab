"""What the walk of a window costs over what the band needs: the scores
the window layers' flash kernels compute, forward walk and the
backward's two, over the scores the band holds
(``train.counters["window_scores_computed"]`` over
``["window_scores_useful"]``: the program's
``ptpu_flash_band_scores_total``, which each lowering that walks a band
adds to at trace time, both static). 1.0 is a walk with no masked
waste; blocks of 1024 with the diagonal and the band's lower edge cut
into panels of 256 read 1.125 at a window of 2048, the same blocks
merely masked 1.5, a kernel that visits every causal block 4.3 at
T 16,384. None where the program counts no band (no window layer, or
the dense form ran)."""
UNIT, SOURCE = "ratio", "program_counter"
LAYER, MOVES = "kernels", "tokens_per_s"


def read(run):
    counters = run["train"].get("counters") or {}
    computed = counters.get("window_scores_computed")
    useful = counters.get("window_scores_useful")
    if not computed or not useful or not useful[0]:
        return None
    return computed[0] / useful[0]
