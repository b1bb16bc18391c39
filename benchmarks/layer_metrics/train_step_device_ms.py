"""model step: device duration of the train step program (the program
that took most of the traced device time), the median over its runs."""

from benchmarks.harness import stats

LAYER = "model step"
UNIT = "ms"
MOVES = "train_tokens_per_s"


def step_program(trace):
    programs = trace["program_ns"]
    return max(programs, key=lambda p: sum(programs[p])) if programs else None


def read(obs):
    trace = obs.get("trace")
    if not trace or "steps" not in obs:
        return None
    name = step_program(trace)
    return stats.median(trace["program_ns"][name]) / 1e6 if name else None
