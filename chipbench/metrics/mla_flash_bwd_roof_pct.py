"""The flash BACKWARD kernels' share of the compute roofline where a
head's key and value differ in width (latent attention): the
architecture's backward share of ``arith.flash_flops_per_step``
(``flash_flops_split``: 832 of 1152 at a key of 128 + 64 against a
value of 128: s again, dp, dv, dq, dk) in the traced steps over the
peak bf16 rate over the device time on chip 0 of those of
``flash_bwd_dq``, ``flash_bwd_dkv`` and ``flash_bwd`` that ran, through
``spans.roof_pct``; the log line gives the time of each. None where the
architecture states no split or none of the kernels ran."""
from chipbench import cells, spans

UNIT, SOURCE = "%", "device_trace"
LAYER, MOVES = "kernels", "tokens_per_s"
KERNELS = ("flash_bwd_dq", "flash_bwd_dkv", "flash_bwd")


def read(run):
    arch = cells.load_arch(run["config"]["arch"])
    if not hasattr(arch, "flash_flops_split"):
        return None
    value, seconds = spans.roof_pct(
        run, KERNELS, arch.flash_flops_split(run["config"])[1])
    if value is not None:
        spans.say("mla_flash_bwd_roof_pct: " + ", ".join(
            "%s %.6f s" % (k, seconds[k]) for k in KERNELS
            if k in seconds) + " of device time")
    return value
