"""model step: the operations the prompts admitted in the traced slot
REQUIRE (`minicpm_sala_costs.prefill_flops`: the products at every
position, in a sparse layer each position over the blocks it KEEPS and its
queries against the compressed keys it can see, the recurrence a token and
lightning layer, the head at the last token only) over the device time of
the prefill programs that ran in it times the chip's peak:
`moe_prefill_mfu.py`'s reader with this family's costs.  Positions past a
prompt's end in its bucket, the blocks a query masked out (the masked dense
form computes them all), the scan's chunked form beyond its recurrence and
float32 products are time without required work: they lower it.  None for
another family.

A program run is in the slot by its start, and a request's first token
leaves the engine as its prefill ends, so the slot's runs, in order, are
those of CONSECUTIVE requests in the order of their first tokens; which,
the trace does not say (it keeps a run's length, not its start).
`moe_prefill_mfu.py` moves the slot's edges later by the median run: at
most one request an edge matched wrongly, which is little among thirty
prefills of like length and too much here, where a slot holds five to ten
of 0.2-1.6 s (a 32,768-token prompt taken for a 4,096-token one's run read
74% where two-term products cap it at 50; my chip runs, PR 49).  So every
alignment of the runs with as many consecutive requests is tried, and the
one kept is that in which each run began inside the slot (its request's
first token less the run's length), the request before the first would
have begun before the slot and the one after the last after it (by the
alignment's own seconds a required operation), and the runs' lengths
follow their prompts' work: least seconds of violation and misfit."""

from benchmarks.harness.loader import sibling_reader

LAYER = "model step"
UNIT = "%"
MOVES = "batch_tokens_per_s"
PROGRAMS = ("prefill_many", "prefill_one")

costs = sibling_reader(__file__, "minicpm_sala_costs")


def read(obs):
    trace, peak = obs.get("trace"), obs.get("peaks")
    if not trace or not peak or "window_mono_s" not in trace \
            or obs.get("family") != "minicpm_sala":
        return None
    runs = [d / 1e9 for p in PROGRAMS
            for d in trace["program_ns"].get(p, [])]
    t0, t1 = trace["window_mono_s"]
    # every request whose prefill can have started in the slot, in the
    # order their prefills ran
    firsts = sorted((s["first"], s["prompt_len"])
                    for s in obs.get("replica_spans", [])
                    if s["first"] is not None
                    and t0 <= s["first"] < t1 + max(runs, default=0.0))
    work = [costs.prefill_flops(obs["sizes"], n) for _, n in firsts]
    if not runs or len(work) < len(runs):
        return None

    n = len(runs)

    def misfit(off):
        mine = list(zip(firsts[off: off + n], work[off: off + n], runs))
        rate = sum(runs) / sum(w for _, w, _ in mine)
        begun = lambda i: firsts[i][0] - rate * work[i]  # noqa: E731
        out = sum(max(0.0, t0 - (f - d)) + max(0.0, (f - d) - t1)
                  + abs(d - rate * w) for (f, _), w, d in mine)
        if off > 0:
            out += max(0.0, begun(off - 1) - t0)
        if off + n < len(work):
            out += max(0.0, t1 - begun(off + n))
        return out

    off = min(range(len(work) - n + 1), key=misfit)
    required = sum(work[off: off + len(runs)])
    return 100.0 * required / (sum(runs) * peak["bf16_flops_per_s"])
