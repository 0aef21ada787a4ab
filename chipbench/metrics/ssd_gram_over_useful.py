"""The Gram products ``C B^T`` the state-space-dual scan computes a
chunk over the one a group needs: a group's heads all read its ``B_t``
and ``C_t``, so the chunk's ``[L, L]`` product is the same for every
one of them. From the program's counter ``ptpu_ssd_lowerings_total``
(``train.counters["ssd_lowerings"]``, which the architecture's
``program_counters`` reads by path, direction, chunk, a group's heads,
the heads a grid step walks and the Gram products a group's chunk
takes; each lowering counts itself at trace time, none a step): the
mean of that last label over the lowerings that walk chunks. 1 where a
grid step walks a whole group (8 heads a group), or where a group's
head blocks share the one product; the number of head blocks to a
group where each makes its own (8 at one group of 64 heads walked 8 a
step). A product is 2 L L N FLOPs beside the 6 L P N + 2 L L P of each
head's own, so 8 of them add 8% to the scan's products.

A COUNT, NOT A TARGET. The number is a constant of the walk the code
has (``ops/ssd_scan.py`` writes ``per_group // step_heads`` into the
counter's label); nothing is timed. ``BENCHMARK.json`` calls it
"lower is better" because ISSUE 64 named it so, but on this chip it
moves AGAINST ``tokens_per_s``: at 1.0 (the product held in a VMEM
scratch across a group's eight head blocks) ``ssd_scan_fwd`` took 1.41
ms a call and the step 447.6 ms, at 8.0 (every block its own) 1.23 ms
and 442.1 ms (my chip runs, PR 64). What the products cost in time is
in ``ssd_roof_pct``; follow that one. The log line gives the lowerings
as counted. None where the program's counter has no
such label (before PR 64) or no scan walked chunks."""
from chipbench import spans

UNIT, SOURCE = "ratio", "program_counter"
LAYER, MOVES = "kernels", "tokens_per_s"


def read(run):
    counted = (run["train"].get("counters") or {}).get("ssd_lowerings")
    walks = {tag: n for tag, n in (counted or {}).items()
             if tag.split("/")[2] != "0"}
    if not walks:
        return None
    spans.say("ssd_gram_over_useful: lowerings by path / direction / chunk "
              "/ a group's heads / a grid step's / Gram products a group's "
              "chunk: " + ", ".join(
                  "%s x %d" % kv for kv in sorted(walks.items())))
    return sum(n * int(tag.rsplit("/", 1)[1]) for tag, n in walks.items()) \
        / sum(walks.values())
