"""What block diffusion adds round the kernels, as a share of device
time: the train step's ops scoped to the Program's
``block_diffusion_noise`` op (the draw) and those scoped to
``block_diffusion_attention`` that are NOT Pallas kernels (the noised
rows' own blocks as dense math, the merge by log-sum-exp with the flash
kernels' piece, the slices of the two halves and their concatenation),
forward and backward, over busy time (chip 0). None where the step has
neither op."""
from chipbench import spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
NOISE, ATTENTION = "block_diffusion_noise", "block_diffusion_attention"


def read(run):
    window = spans.of(run)
    if not window:
        return None
    program, _ = spans.step_program(window)
    if not spans.device_time(window, program, scope_type=ATTENTION):
        return None
    noise = spans.device_time(window, program, scope_type=NOISE)
    merge = spans.device_time(window, program, scope_type=ATTENTION,
                              kernel=False)
    spans.say("bd_noise_dev_share_pct: the draw %.6f s, attention outside "
              "its kernels %.6f s" % (noise, merge))
    return spans.busy_share_pct(run, noise + merge)
