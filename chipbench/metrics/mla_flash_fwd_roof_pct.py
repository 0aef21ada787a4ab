"""The flash FORWARD kernel's share of the compute roofline where a
head's key and value differ in width (latent attention): the
architecture's forward share of ``arith.flash_flops_per_step``
(``flash_flops_split``: 320 of the 1152 multiply-adds a useful score
costs at a key of 128 + 64 against a value of 128, where
``flash_fwd_roof_pct`` fixes 2/7) in the traced steps over the peak
bf16 rate over the device time of the kernel named ``flash_fwd`` on
chip 0, through ``spans.roof_pct``. Under per-layer recompute the
kernel runs twice a step and its useful FLOPs are counted once, so the
reading is at most half of what the kernel reaches. None where the
architecture states no split or no such kernel ran."""
from chipbench import cells, spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"


def read(run):
    arch = cells.load_arch(run["config"]["arch"])
    if not hasattr(arch, "flash_flops_split"):
        return None
    value, seconds = spans.roof_pct(
        run, ("flash_fwd",), arch.flash_flops_split(run["config"])[0])
    if value is not None:
        spans.say("mla_flash_fwd_roof_pct: flash_fwd %.6f s of device time"
                  % seconds["flash_fwd"])
    return value
