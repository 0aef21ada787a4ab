"""What the compiled train step holds of the device's memory, by the
executable's own account: ``argument + output - alias + temp`` bytes of
its ``memory_analysis()`` over the device's ``bytes_limit``, both taken
by the executor where it compiled the step ahead of its first call and
kept in the header of the kernel ledger
(``paddle_tpu.trace.kernels``, ``memory``). ``memory_peak_bytes`` reads
what the allocator had in use and misses what the step reserves (12.43
GB where the compiled step holds 15.29, PERF.md section 7); a compile
that succeeded fits, so this cannot pass 100. None where the program
keeps no kernel ledger or the backend states no limit (the CPU)."""
from chipbench import kernels, spans

UNIT, SOURCE = "%", "program_counter"
LAYER, MOVES = "train executor", "tokens_per_s"


def read(run):
    got = kernels.table()
    memory = got and got[0]["memory"]
    if not memory or not memory["bytes_limit"]:
        return None
    held = (memory["argument"] + memory["output"] - memory["alias"]
            + memory["temp"])
    spans.say("compiled_step_hbm_pct: arguments %d + outputs %d - "
              "aliased %d + temporaries %d = %d bytes of the device's "
              "%d" % (memory["argument"], memory["output"],
                      memory["alias"], memory["temp"], held,
                      memory["bytes_limit"]))
    return 100.0 * held / memory["bytes_limit"]
